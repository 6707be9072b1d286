"""Prompt chunks per decode step over the window: how often a chunk sits
between two decode steps of the running batch."""


def read(ctx):
    a, b = (ctx["counters"]["snaps"].get(k) for k in ("open", "close"))
    if not a or not b or b["steps"] == a["steps"]:
        return None
    return (b["prefill_chunks"] - a["prefill_chunks"]) \
        / (b["steps"] - a["steps"])
