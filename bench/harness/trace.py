"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* the traced window is the host event named ``WINDOW`` (a
  ``jax.profiler.TraceAnnotation`` the harness holds open over the
  window);
* device busy time is the union of the intervals of the operations on
  each device plane (line ``XLA Ops``), clipped to the window and
  averaged over the devices that ran any;
* device time per program is the sum of each ``XLA Modules`` event's
  duration in the window, by module name;
* idle time (the window minus the busy union) is split over the host
  spans open during it, each instant to the innermost one - the program's
  own span names (``engine.apply``, ``macro.phase1``, ...), which
  ``obs="trace-xla"`` writes into the same trace - and summed by name.
  The device clock sits within about a millisecond of the host's in the
  v5e traces read so far, so the split is good to that.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside the program's spans"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def _events(line) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names, starts, durs = [], [], []
    for e in line.events:
        names.append(e.name)
        starts.append(e.start_ns)
        durs.append(e.duration_ns)
    return (names, np.asarray(starts, np.float64),
            np.asarray(durs, np.float64))


def union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Disjoint (K, 2) intervals covering the union of ``[start, end)``."""
    if starts.size == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return np.stack([s[first], reach[last]], axis=1)


def _label_segments(spans: List[Tuple[str, float, float]]):
    """Disjoint host-timeline segments, each labeled with the innermost
    span open there: (starts, ends, labels)."""
    bounds = sorted({b for _, s, e in spans for b in (s, e)})
    if len(bounds) < 2:
        return np.zeros(0), np.zeros(0), []
    starts = np.asarray(bounds[:-1])
    ends = np.asarray(bounds[1:])
    labels = [OUTSIDE] * len(starts)
    width = [np.inf] * len(starts)
    for name, s, e in spans:
        i0 = int(np.searchsorted(starts, s, "left"))
        i1 = int(np.searchsorted(starts, e, "left"))
        for i in range(i0, i1):
            if e - s < width[i]:
                width[i], labels[i] = e - s, name
    return starts, ends, labels


def _overlap(g0, g1, seg_s, seg_e, seg_l):
    """Time of the gaps ``[g0, g1)`` under each segment's label, summed
    by label (gap time under no segment goes to ``OUTSIDE``)."""
    edges = np.unique(np.concatenate([g0, g1, seg_s, seg_e]))
    a, b = edges[:-1], edges[1:]
    mid = 0.5 * (a + b)
    gi = np.searchsorted(g0, mid, "right") - 1
    in_gap = (gi >= 0) & (mid < g1[np.clip(gi, 0, None)])
    n = len(seg_l)
    if n:
        si = np.searchsorted(seg_s, mid, "right") - 1
        in_seg = (si >= 0) & (mid < seg_e[np.clip(si, 0, None)])
        lab = np.where(in_seg, si, n)
    else:
        lab = np.full(mid.shape, n)
    sums = np.bincount(lab[in_gap], weights=(b - a)[in_gap], minlength=n + 1)
    out: Dict[str, float] = {}
    for i in np.flatnonzero(sums):
        label = seg_l[i] if i < n else OUTSIDE
        out[label] = out.get(label, 0.0) + float(sums[i])
    return out


def reduce_trace(path: str, span_names, top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    window = None
    host_spans: List[Tuple[str, float, float]] = []
    device_lines = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            device_lines.append({line.name: line for line in plane.lines})
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            names, st, du = _events(line)
            for n, s, d in zip(names, st, du):
                if n == WINDOW:
                    window = (s, s + d)
                elif n in span_names and d > 0:
                    host_spans.append((n, s, s + d))
    if window is None:
        raise ValueError(f"no {WINDOW!r} event in {path}")
    w0, w1 = window
    busy_total = 0.0
    used = 0
    modules: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    seg_s, seg_e, seg_l = _label_segments(
        [(n, s, e) for n, s, e in host_spans if e > w0 and s < w1])
    for lines in device_lines:
        ops = lines.get(OPS_LINE)
        if ops is None:
            continue
        _, st, du = _events(ops)
        keep = (st < w1) & (st + du > w0)
        busy = union(np.maximum(st[keep], w0), np.minimum(st[keep] + du[keep],
                                                            w1))
        if busy.shape[0] == 0:
            continue
        used += 1
        busy_total += float((busy[:, 1] - busy[:, 0]).sum())
        mods = lines.get(MODULES_LINE)
        if mods is not None:
            names, ms, md = _events(mods)
            for n, s, d in zip(names, ms, md):
                c = min(s + d, w1) - max(s, w0)
                if c > 0:
                    modules[n] = modules.get(n, 0.0) + c
        edges = np.concatenate(([w0], busy.ravel(), [w1]))
        idle = _overlap(edges[0::2], edges[1::2], seg_s, seg_e, seg_l)
        for label, secs in idle.items():
            gaps[label] = gaps.get(label, 0.0) + secs
    n = max(used, 1)
    top_of = lambda d: sorted(((k, v / n / 1e9) for k, v in d.items()),
                              key=lambda kv: -kv[1])[:top]
    return TraceSummary(window_s=(w1 - w0) / 1e9, busy_s=busy_total / n / 1e9,
                        devices=used, device_ops=top_of(modules),
                        idle_gaps=top_of(gaps))
