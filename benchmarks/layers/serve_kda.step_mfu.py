"""The served work's share of the chip's bf16 peak over the traced stretch
(the share of the whole step): one forward of every prompt whose first token
arrived in it and of every decoded token delivered in it (2 x the weights a
token meets whatever it chooses, the delta rule's 7 d_v d_k a head a
position, a latent layer's absorbed attention over the rows present, the
held experts that were chosen, counted by the engine, and logits only where
a token is chosen)."""
from benchmarks import flops_ling as fl


def read(ctx):
    s = ctx["scope"]
    snaps = ctx["counters"]["snaps"]
    if not s or not ctx["peaks"] or "experts_held" not in ctx["model"] \
            or "moe_choices_local" not in snaps.get("trace1", {}):
        return None
    local = snaps["trace1"]["moe_choices_local"] \
        - snaps["trace0"]["moe_choices_local"]
    work = fl.forward_flops(ctx["model"], s["decode_positions"],
                            len(s["decode_positions"]), local)
    work += sum(fl.forward_flops(ctx["model"], range(p), 1, 0)
                for p in s["prompts_finished"])
    if not work:
        return None
    return 100.0 * work / (s["window_s"] * ctx["peaks"]["bf16_flops"]
                           * ctx["cell"]["chips"])
