"""Phase 1 (``core/torta.py``, ``core/macro.py``): host time in span
``macro.phase1`` per slot of the traced window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    return per_slot_ms(ctx, span_total(ctx, "macro.phase1"))
