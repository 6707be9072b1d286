"""What the runtime held back on the fullest chip for the executables'
temporaries at its peak (`memory_stats()` `peak_bytes_reserved`), which it
counts apart from the live buffers of `train.peak_hbm_gb`; the two together
are what the chip had to have."""


def read(ctx):
    peak = ctx["counters"].get("reserved_peak_bytes")
    return peak / 1e9 if peak else None
