"""Of the experts the window's tokens chose (live positions x experts a
token x expert layers), the share that fell on experts held here
(`moe_choices_local / moe_choices_total`). With one routing group of eight
held it lies near 12.5 %: a check that the share is an eighth and that
nothing stands in for the absent seven, not a goal (`better` is nominal)."""


def read(ctx):
    a, b = (ctx["counters"]["snaps"].get(k) for k in ("open", "close"))
    if not a or not b or "moe_choices_total" not in b \
            or not b["moe_choices_total"] - a["moe_choices_total"]:
        return None
    return 100.0 * (b["moe_choices_local"] - a["moe_choices_local"]) \
        / (b["moe_choices_total"] - a["moe_choices_total"])
