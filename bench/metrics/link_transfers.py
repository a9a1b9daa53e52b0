"""Device link: arrays moved between host and device on the fused path,
one transfer each, counters ``device.transfers{dir=...,layer=...}`` summed
over both directions and the layers, per slot of the traced window.  Each
transfer is a round trip of fixed cost at these sizes, so the count, not
the bytes, tracks the slot time they take."""


def read(ctx):
    cells = [v for k, v in ctx.counters.items()
             if k.split("{")[0] == "device.transfers"]
    if not cells or ctx.slots <= 0:
        return None
    return sum(cells) / ctx.slots
