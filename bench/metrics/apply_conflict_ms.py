"""Engine apply (``sim/engine.py``): span ``engine.apply.conflict``, the
per-row walk over rows whose server takes more than one task this slot,
per slot of the traced window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    return per_slot_ms(ctx, span_total(ctx, "engine.apply.conflict"))
