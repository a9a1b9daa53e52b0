"""Phase 1 (``core/macro.py``): span ``macro.ot``, the transport
operands, Sinkhorn, routing probabilities and their sync, per slot of the
traced window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    return per_slot_ms(ctx, span_total(ctx, "macro.ot"))
