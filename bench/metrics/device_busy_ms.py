"""Device (TPU v5e): the union of device-operation intervals in the
traced window, per slot."""


def read(ctx):
    if ctx.trace is None or ctx.slots <= 0:
        return None
    return 1000.0 * ctx.trace.busy_s / ctx.slots
