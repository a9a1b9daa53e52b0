"""Engine observe (``sim/engine.py``): span ``engine.observe``, the
per-slot series recorder, per slot of the traced window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    return per_slot_ms(ctx, span_total(ctx, "engine.observe"))
