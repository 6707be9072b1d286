"""1 - (union of the device's operation intervals / traced stretch)."""


def read(ctx):
    s = ctx["scope"]
    if not s or not s["window_s"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
