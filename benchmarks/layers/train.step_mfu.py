"""The whole step's share of the chip's bf16 peak: the algorithm's FLOPs
(forward + backward, causal attention counted once, no recomputation) of the
launches wholly inside the traced stretch, over its seconds."""
from benchmarks import flops


def read(ctx):
    s, mix = ctx["scope"], ctx["mix"]
    if not s or not ctx["peaks"]:
        return None
    work = s["launches"] * mix["steps_per_dispatch"] \
        * flops.train_flops_per_step(ctx["model"], mix["batch"],
                                     mix["seq_len"])
    return 100.0 * work / (s["window_s"] * ctx["peaks"]["bf16_flops"]
                           * ctx["cell"]["chips"])
