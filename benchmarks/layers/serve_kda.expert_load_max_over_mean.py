"""The fullest HELD expert's positions over the mean held expert's, per
layer per dispatch (steps and prompt chunks), mean over the window's
dispatches and layers (the engine reads the expert layers' counts over the
experts it holds back with each dispatch's tokens)."""


def read(ctx):
    a, b = (ctx["counters"]["snaps"].get(k) for k in ("open", "close"))
    if not a or not b or "moe_experts_held" not in b \
            or not b["moe_layer_dispatches"] - a["moe_layer_dispatches"]:
        return None
    return (b["moe_load_max_over_mean_sum"]
            - a["moe_load_max_over_mean_sum"]) \
        / (b["moe_layer_dispatches"] - a["moe_layer_dispatches"])
