"""Phase 2 (``core/micro_jax.py``): span ``micro.upload``, the host to
device uploads of the fused scan's operands and its dispatch, per slot of
the traced window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    return per_slot_ms(ctx, span_total(ctx, "micro.upload"))
