"""The work the procedure prescribes in the traced stretch, as a share of the
chip's bf16 peak: one forward of the whole blocks of every prompt whose first
block arrived in it (no logits), and every block forward the engine counted
between the trace's start and stop (attention over the keys up to the
block's end, router, 8 experts a position, the head at the B positions of a
denoising pass only)."""
from benchmarks import flops_sdar


def read(ctx):
    s = ctx["scope"]
    snaps = ctx["counters"]["snaps"]
    if not s or not ctx["peaks"] or "trace0" not in snaps \
            or "trace1" not in snaps or "bd_forwards" not in snaps["trace1"]:
        return None
    a, b = snaps["trace0"], snaps["trace1"]
    work = flops_sdar.block_forwards_flops(
        ctx["model"], b["bd_forwards"] - a["bd_forwards"],
        b["bd_commit_forwards"] - a["bd_commit_forwards"],
        b["bd_context_tokens"] - a["bd_context_tokens"])
    work += sum(flops_sdar.prompt_flops(ctx["model"], p)
                for p in s["prompts_finished"])
    if not work:
        return None
    return 100.0 * work / (s["window_s"] * ctx["peaks"]["bf16_flops"]
                           * ctx["cell"]["chips"])
