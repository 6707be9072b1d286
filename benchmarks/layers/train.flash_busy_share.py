"""The flash kernels' device time over all device-busy time."""


def read(ctx):
    s = ctx["scope"]
    if not s or not s.get("flash_s") or not s["busy_s"]:
        return None
    return 100.0 * s["flash_s"] / s["busy_s"]
