"""The hot-path hook surface: a process-global active ``Observability``.

Threading an obs object through every function signature of the fused
slot step (engine -> scheduler -> micro scan -> kernel wrappers) would
contaminate APIs that exist for numerical work; instead ``Engine.run``
*activates* its obs for the duration of the run and the instrumented
call sites reach it through these module functions.  Every hook is a
near-no-op when nothing is active (one global load + ``is None`` test),
which is what lets the cheap counters stay default-on without moving
the fused-path benchmark numbers.

The activation is a stack (re-entrant): a reference-oracle engine run
nested inside an instrumented run records into its own obs (or nothing).

Compiles are counted by one ``jax.monitoring`` listener, installed on
the first activation of a counting obs: every executable built or
loaded from the persistent cache while a run is active increments
``device.compiles{program=<function name>}``.
"""
from __future__ import annotations

import contextlib
import re

from repro.obs.trace import NULL_SPAN

_ACTIVE = None            # the innermost activated Observability (or None)
_STACK = []
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listener = False


def active():
    """The currently-activated ``Observability`` (None outside a run)."""
    return _ACTIVE


@contextlib.contextmanager
def activate(obs):
    """Install ``obs`` as the active sink for the dynamic extent of a
    run; ``obs=None`` deactivates (nested oracle runs stay silent)."""
    global _ACTIVE
    if obs is not None and obs.counters is not None:
        _listen_for_compiles()
    _STACK.append(_ACTIVE)
    _ACTIVE = obs
    try:
        yield obs
    finally:
        _ACTIVE = _STACK.pop()


def _listen_for_compiles() -> None:
    global _compile_listener
    if not _compile_listener:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_compile)
        _compile_listener = True


def program_name(fun_name: str) -> str:
    """``jit(micro_scan_all)`` -> ``micro_scan_all``: the name jax gives
    a compile, as the XLA module name spells it after ``jit_``
    (``jit(<unknown>)`` -> ``_unknown``)."""
    inner = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    return re.sub(r"[^0-9A-Za-z_]+", "_", inner).rstrip("_")


def _on_compile(event: str, duration_s: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        count("device.compiles",
              program=program_name(str(kwargs.get("fun_name", "?"))))


# ---------------------------------------------------------------- hooks


def count(name: str, n: int = 1, **labels) -> None:
    obs = _ACTIVE
    if obs is not None and obs.counters is not None:
        obs.counters.inc(name, n, **labels)


def count_new_shape(name: str, shape: str) -> bool:
    """Increment a retrace counter only the first time ``shape`` is seen
    this run: the distinct bucket shapes a run meets, each a compile the
    first time the process meets it (a shape an earlier engine in the
    process compiled counts again; ``device.compiles`` counts the
    executables built).  Returns True when it counted."""
    obs = _ACTIVE
    if obs is None or obs.counters is None:
        return False
    if obs.counters.get(name, shape=shape) == 0:
        obs.counters.inc(name, shape=shape)
        return True
    return False


def count_transfer(direction: str, layer: str, arrays) -> None:
    """The arrays one dispatch moves across the host-device link, one
    transfer each: ``device.transfers{dir=h2d|d2h,layer=...}`` += their
    number, and for uploads ``device.h2d_bytes{layer=...}`` += their
    ``nbytes``.  ``arrays`` is a callable returning them, called only
    when counting."""
    obs = _ACTIVE
    if obs is not None and obs.counters is not None:
        moved = arrays()
        obs.counters.inc("device.transfers", len(moved), dir=direction,
                         layer=layer)
        if direction == "h2d":
            obs.counters.inc("device.h2d_bytes",
                             sum(a.nbytes for a in moved), layer=layer)


def begin_slot(t: int) -> None:
    """Tag the spans opened from here on with engine slot ``t``."""
    obs = _ACTIVE
    if obs is not None and obs.tracer is not None:
        obs.tracer.slot = t


def span(name: str):
    """A span context manager — the shared no-op unless a tracer is
    active (tracing is opt-in)."""
    obs = _ACTIVE
    if obs is not None and obs.tracer is not None:
        return obs.tracer.span(name)
    return NULL_SPAN


def record_forecast(pred_inbound) -> None:
    """Scheduler-side hook: the slot's per-region demand forecast
    (picked up by the series recorder at slot close)."""
    obs = _ACTIVE
    if obs is not None and obs.series is not None:
        obs.series.note_forecast(pred_inbound)
