"""Slot close (``sim/engine_jax.py``): span ``engine.close_step``, the
jitted close (upload, dispatch, sync, write-back), per slot of the traced
window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    return per_slot_ms(ctx, span_total(ctx, "engine.close_step"))
