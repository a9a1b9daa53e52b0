"""Device (TPU v5e): device time of the fused scan, the XLA module
``jit_micro_scan_all``, per slot of the traced window.  It reads the
trace reduction's ``device_ops``, the ten modules with the most device
time; the scan leads them by far (one entry per row bucket), so a bucket
left out of the ten would take under a tenth of a percent of the
window."""

MODULE = "jit_micro_scan_all"


def read(ctx):
    if ctx.trace is None or ctx.slots <= 0:
        return None
    times = [s for name, s in ctx.trace.device_ops
             if name.split("(")[0] == MODULE]
    if not times:
        return None
    return 1000.0 * sum(times) / ctx.slots
