"""Host-side phase tracing: lightweight span timers for the slot hot path.

A :class:`Tracer` records nested named spans (context-manager or
decorator API) with wall-clock durations from ``time.perf_counter``.
Spans are cheap (two clock reads + a list append) but NOT free, so
tracing is opt-in (``ObsConfig(trace=True)``); the default-on engine
observability keeps ``tracer=None`` and every ``runtime.span(...)`` call
short-circuits to a shared no-op.

With ``xla=True`` each span also enters a
``jax.profiler.TraceAnnotation`` scope, so the same phase names show up
on the host timeline of a real XLA profile (``jax.profiler.trace``)
alongside the device kernels — the host spans remain the source of truth
for the per-run summary table.

Span taxonomy used by the engine/scheduler wiring (see
ARCHITECTURE.md §Observability).  The top-level spans tile one engine
slot, so idle device time always falls under some phase:

* ``engine.intake``     — failures, warming, the source call, arrivals,
  the buffer concat and the ``SlotObs``
* ``schedule.batch``    — the whole scheduler call for the slot

  * ``macro.phase1``    — TORTA phase 1: ``macro.predict`` (the demand
    forecast) and ``macro.ot`` (operands, Sinkhorn, routing
    probabilities and their sync)
  * ``macro.sample``    — the per-origin region draw
  * ``micro.activation`` — Eq 6 activation targets
  * ``micro.assign``    — phase-2 greedy matching (any backend); on the
    fused path ``micro.pack`` (sort and scatter into numpy operands),
    ``micro.upload`` (uploads and the scan dispatch) and
    ``micro.host_sync`` (the one device->host sync per slot)

* ``engine.activate``   — decision validation and server activation
* ``engine.apply``      — decision application: ``engine.apply.single``
  (one task per server, jitted on the jax step backend),
  ``engine.apply.conflict`` (the per-row walk over same-server rows),
  ``engine.apply.replay`` (the per-task replay when a target went
  inactive)
* ``engine.buffer``     — drops and the cross-slot buffer
* ``engine.slot_close`` — drain, billing, per-slot metrics;
  ``engine.close_step`` is the jitted close (upload, dispatch, sync,
  write-back)
* ``engine.observe``    — the per-slot series recorder

Each record carries the engine slot it opened in (``runtime.begin_slot``)
and :meth:`Tracer.summary` reports each name's self time beside its
total.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional


@dataclasses.dataclass
class SpanRecord:
    name: str
    depth: int           # nesting depth at entry (0 = top level)
    parent: int          # index of the enclosing span record, -1 if none
    t_start: float       # perf_counter seconds (monotonic)
    duration_s: float = 0.0
    slot: int = -1       # engine slot the span opened in (-1 outside one)


class _Span:
    """Reentrant context manager handle for one span entry."""

    __slots__ = ("_tracer", "_name", "_idx", "_xla_ctx")

    def __init__(self, tracer: "Tracer", name: str):
        self._tracer = tracer
        self._name = name
        self._idx = -1
        self._xla_ctx = None

    def __enter__(self):
        self._idx = self._tracer._enter(self._name)
        if self._tracer.xla:
            self._xla_ctx = self._tracer._annotation(self._name)
            self._xla_ctx.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._xla_ctx is not None:
            self._xla_ctx.__exit__(exc_type, exc, tb)
            self._xla_ctx = None
        self._tracer._exit(self._idx)
        return False


class NullSpan:
    """Shared no-op span — what ``runtime.span`` returns when tracing is
    off (no allocation on the hot path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = NullSpan()


class Tracer:
    """Span recorder with per-name aggregation."""

    def __init__(self, *, xla: bool = False,
                 clock=time.perf_counter):
        self.xla = xla
        self.clock = clock
        self.records: List[SpanRecord] = []
        self._stack: List[int] = []
        self.slot = -1           # set once per engine slot

    # ------------------------------------------------------------- spans

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def traced(self, name: Optional[str] = None):
        """Decorator form: ``@tracer.traced("phase")``."""
        def wrap(fn):
            label = name or fn.__qualname__

            @functools.wraps(fn)
            def inner(*args, **kwargs):
                with self.span(label):
                    return fn(*args, **kwargs)
            return inner
        return wrap

    def _annotation(self, name: str):
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)

    def _enter(self, name: str) -> int:
        idx = len(self.records)
        parent = self._stack[-1] if self._stack else -1
        self.records.append(SpanRecord(
            name=name, depth=len(self._stack), parent=parent,
            t_start=self.clock(), slot=self.slot))
        self._stack.append(idx)
        return idx

    def _exit(self, idx: int) -> None:
        rec = self.records[idx]
        rec.duration_s = self.clock() - rec.t_start
        # tolerate exception unwinding closing spans out of order
        while self._stack and self._stack[-1] >= idx:
            self._stack.pop()

    # ---------------------------------------------------------- summary

    def summary(self) -> List[Dict]:
        """Per-name aggregate rows, ordered by total time descending:
        ``{name, count, total_s, self_s, mean_s, max_s, depth}`` (self =
        total less the time of the spans directly inside; depth = the
        minimum nesting depth the name was seen at)."""
        child_s = [0.0] * len(self.records)
        for rec in self.records:
            if rec.parent >= 0:
                child_s[rec.parent] += rec.duration_s
        agg: Dict[str, Dict] = {}
        for rec, inner in zip(self.records, child_s):
            row = agg.get(rec.name)
            if row is None:
                agg[rec.name] = {"name": rec.name, "count": 1,
                                 "total_s": rec.duration_s,
                                 "self_s": rec.duration_s - inner,
                                 "max_s": rec.duration_s,
                                 "depth": rec.depth}
            else:
                row["count"] += 1
                row["total_s"] += rec.duration_s
                row["self_s"] += rec.duration_s - inner
                row["max_s"] = max(row["max_s"], rec.duration_s)
                row["depth"] = min(row["depth"], rec.depth)
        rows = sorted(agg.values(), key=lambda r: -r["total_s"])
        for row in rows:
            row["mean_s"] = row["total_s"] / row["count"]
        return rows

    def summary_table(self) -> str:
        """The per-run span table (human-readable)."""
        rows = self.summary()
        if not rows:
            return "(no spans recorded)"
        lines = [f"{'span':<26} {'count':>7} {'total_s':>9} "
                 f"{'self_s':>9} {'mean_ms':>9} {'max_ms':>9}"]
        for r in rows:
            indent = "  " * r["depth"]
            lines.append(
                f"{indent + r['name']:<26} {r['count']:>7} "
                f"{r['total_s']:>9.3f} {r['self_s']:>9.3f} "
                f"{r['mean_s'] * 1e3:>9.2f} {r['max_s'] * 1e3:>9.2f}")
        return "\n".join(lines)
