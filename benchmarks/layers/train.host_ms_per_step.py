"""Host time of the engine's dispatch (enqueue and input `device_put`) per
optimizer step, from the benchmark's own span around `train_batches`."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    return ctx["spans"].total("dispatch", c["t_open"], c["t_close"]) \
        * 1e3 / c["steps"]
