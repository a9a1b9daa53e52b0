"""Engine apply (``sim/engine.py``, ``sim/engine_jax.py``): span
``engine.apply.single``, the jitted apply of the servers that take one
task (pad, upload, dispatch, sync), per slot of the traced window."""
from harness.manifest import per_slot_ms, span_total


def read(ctx):
    return per_slot_ms(ctx, span_total(ctx, "engine.apply.single"))
