"""Phase 2 (``core/micro.py``): span ``micro.assign`` less the wait in
``micro.host_sync`` (sort, operand build, dispatch), per slot of the
traced window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    total = span_total(ctx, "micro.assign")
    if total is None:
        return None
    return per_slot_ms(ctx, total - (span_total(ctx, "micro.host_sync")
                                     or 0.0))
