"""The fullest expert's positions over the mean expert's, per layer per
dispatch, mean over the window's dispatches and layers (the engine reads the
expert layers' counts back with each dispatch's tokens)."""


def read(ctx):
    a, b = (ctx["counters"]["snaps"].get(k) for k in ("open", "close"))
    if not a or not b or not b.get("moe_layer_dispatches", 0) - a.get(
            "moe_layer_dispatches", 0):
        return None
    return (b["moe_load_max_over_mean_sum"]
            - a["moe_load_max_over_mean_sum"]) \
        / (b["moe_layer_dispatches"] - a["moe_layer_dispatches"])
