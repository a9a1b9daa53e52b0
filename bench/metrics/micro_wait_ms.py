"""Phase 2 (``core/micro_jax.py``): host time waiting on the fused scan,
span ``micro.host_sync``, per slot of the traced window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    return per_slot_ms(ctx, span_total(ctx, "micro.host_sync"))
