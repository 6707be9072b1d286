"""Positions fixed by denoising passes over block forwards (one sequence's
share of a dispatch), window open to close: block_length over
denoising_steps + 1 where every block starts all masked."""


def read(ctx):
    a, b = (ctx["counters"]["snaps"].get(k) for k in ("open", "close"))
    if not a or not b or not b.get("bd_forwards", 0) - a.get(
            "bd_forwards", 0):
        return None
    return (b["bd_tokens_fixed"] - a["bd_tokens_fixed"]) \
        / (b["bd_forwards"] - a["bd_forwards"])
