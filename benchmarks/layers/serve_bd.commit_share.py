"""The share of block forwards that are commit passes (they fix no token
and write the block's cache rows), window open to close: a change that folds
the commit into the next block's first pass moves it."""


def read(ctx):
    a, b = (ctx["counters"]["snaps"].get(k) for k in ("open", "close"))
    if not a or not b or not b.get("bd_forwards", 0) - a.get(
            "bd_forwards", 0):
        return None
    return 100.0 * (b["bd_commit_forwards"] - a["bd_commit_forwards"]) \
        / (b["bd_forwards"] - a["bd_forwards"])
