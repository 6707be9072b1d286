"""Flash attention fwd + dq + dkv: the least time the chip could take for
their work (compute-bound at these shapes: the FLOPs over the bf16 peak
exceed the bytes over the bandwidth) over their device time in the trace."""
from benchmarks import flops


def read(ctx):
    s, mix = ctx["scope"], ctx["mix"]
    if not s or not ctx["peaks"] or not s.get("flash_s"):
        return None
    steps = s["launches"] * mix["steps_per_dispatch"]
    least, _bound = flops.roofline_seconds(
        steps * flops.flash_train_flops(ctx["model"], mix["batch"],
                                        mix["seq_len"]),
        steps * flops.flash_train_bytes(ctx["model"], mix["batch"],
                                        mix["seq_len"]), ctx["peaks"])
    return 100.0 * least / (s["flash_s"] * ctx["cell"]["chips"])
