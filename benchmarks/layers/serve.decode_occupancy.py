"""Active sequences over bucket slots, per decode step, over the window:
the engine's own counters read when the window opens and closes."""


def read(ctx):
    a, b = (ctx["counters"]["snaps"].get(k) for k in ("open", "close"))
    if not a or not b or None in (a["step_slots"], b["step_slots"]) \
            or b["step_slots"] == a["step_slots"]:
        return None
    return 100.0 * (b["step_active"] - a["step_active"]) \
        / (b["step_slots"] - a["step_slots"])
