"""Live buffers on the fullest chip at their peak (`memory_stats()`
`peak_bytes_in_use`), read after the window and before the reference runs.
What the runtime holds back for the executables' temporaries is counted
apart: `train.reserved_hbm_gb`."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
