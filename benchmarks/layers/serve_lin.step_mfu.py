"""The served work's share of the chip's bf16 peak over the traced stretch
(the share of the whole step): one forward of every prompt whose first token
arrived in it and of every decoded token delivered in it (2 x matmul
weights, the delta rule's 7 d_v d_k a head a position, a full layer's
attention over the tokens present, logits only where a token is chosen)."""
from benchmarks import flops_olmo_hybrid as fl


def read(ctx):
    s = ctx["scope"]
    if not s or not ctx["peaks"] or "layer_pattern" not in ctx["model"]:
        return None
    work = fl.forward_flops(ctx["model"], s["decode_positions"],
                            len(s["decode_positions"]))
    work += sum(fl.forward_flops(ctx["model"], range(p), 1)
                for p in s["prompts_finished"])
    if not work:
        return None
    return 100.0 * work / (s["window_s"] * ctx["peaks"]["bf16_flops"]
                           * ctx["cell"]["chips"])
