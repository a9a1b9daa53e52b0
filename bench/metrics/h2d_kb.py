"""Device link: bytes uploaded from host to device on the fused path,
counters ``device.h2d_bytes{layer=...}`` summed over layers, in KB (1,000
bytes) per slot of the traced window.  At the accepted cells' sizes the
upload time follows the number of transfers (``link_transfers``), not
the bytes; the bytes matter where operands grow with the fleet."""


def read(ctx):
    cells = [v for k, v in ctx.counters.items()
             if k.split("{")[0] == "device.h2d_bytes"]
    if not cells or ctx.slots <= 0:
        return None
    return sum(cells) / 1000.0 / ctx.slots
