"""One run of one cell: set-up, warm-up, the measured window, the metrics
and the output check, ending in the result line.

``run.py`` refuses to start without the chip; the tests call
``run_cell`` directly on the CPU at a small size.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

from harness import check, handover, program, world
from harness.manifest import Manifest
from harness.meters import CompileMeter


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader reads: the program's spans that
    opened inside the traced window as (name, start, seconds), the
    window's counter deltas, the slots completed in it, and the trace
    reduction (None when there is no trace)."""

    spans: List[tuple]
    counters: Dict[str, int]
    slots: int
    trace: object


def compile_cache_dir(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / "bench" / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def invalid_decisions(run, n_servers_in: np.ndarray) -> int:
    """Rows of the window's decisions that name no valid placement."""
    bad = 0
    r = len(n_servers_in)
    for c in run.calls:
        if c.t < run.s0:
            continue
        reg = np.asarray(c.region)
        srv = np.asarray(c.server)
        placed = reg >= 0
        size = np.where(placed, n_servers_in[np.clip(reg, 0, r - 1)], 0)
        bad += int(np.count_nonzero((reg < -1) | (reg >= r)
                                    | (placed & ((srv < 0) | (srv >= size)))))
    return bad


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             traced: bool, t_process: float, device) -> int:
    import jax

    manifest = Manifest(root)
    cell = manifest.cell(workload)
    cfg = manifest.config(cell["config"])
    spec = manifest.traffic(cell["traffic"])
    log(f"cell {workload}: config {cfg['name']}, traffic {spec['name']}, "
        f"seed {seed}, {seconds} s, trace {int(traced)}")
    log(f"compile cache: {compile_cache_dir(root)}")

    fleet = world.make_fleet(cfg)
    latency, graph = world.make_latency(cfg)
    traffic = world.Traffic(cfg, spec, fleet, seed)
    log(f"fleet: {fleet.n_regions} regions, {fleet.n_servers} servers; "
        f"{traffic.total_rate:.1f} tasks per slot expected")

    window_s = min(seconds, float(spec["trace_seconds"])) if traced \
        else seconds
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    marker = {}

    def on_open():
        if traced:
            # the Python function tracer would record every call of the
            # engine loop: off, the host annotations stay
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            marker["window"] = jax.profiler.TraceAnnotation("bench.window")
            marker["window"].__enter__()
        marker["compiles"] = meter.compiles

    with CompileMeter() as meter:
        try:
            run = program.run_program(
                cfg, traffic, fleet, latency, graph, seconds=window_s,
                obs_spec="trace-xla" if traced else None,
                on_open_extra=on_open, log=log)
        except handover.Refused as err:
            print(f"bench: {err}", file=sys.stderr, flush=True)
            return 5
        if traced:
            marker["window"].__exit__(None, None, None)
            jax.profiler.stop_trace()
    slots = run.end_slot - run.s0
    window = run.closed - run.opened
    setup_s = run.opened - t_process
    log(f"window: slots {run.s0}..{run.end_slot - 1} ({slots} slots) in "
        f"{window!r} s; set-up {setup_s!r} s")
    per_slot = np.diff(np.append(run.stamps[run.s0:], run.closed))
    half = per_slot.size // 2
    log(f"slot seconds: first half {per_slot[:half].mean()!r}, second half "
        f"{per_slot[half:].mean()!r}, max {per_slot.max()!r}")
    in_window = meter.compiles - marker["compiles"]
    log(f"compiles: {marker['compiles']} before the window, "
        f"{in_window} inside it; persistent cache "
        f"{meter.cache_hits} hits, {meter.cache_misses} misses")
    if in_window:
        print(f"bench: {in_window} program(s) compiled inside the measured "
              f"window; every shape has to be warm before it opens",
              file=sys.stderr, flush=True)
        return 4
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    attempted = int(sum(len(run.slots[t])
                        for t in range(run.s0, run.end_slot)))
    failed = invalid_decisions(run, np.diff(fleet.region_ptr))

    metrics: Dict[str, Dict] = {}
    breakdown = None
    trace_summary = None
    if traced:
        from harness import trace as tr
        files = list(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        names = {r.name for r in run.obs.tracer.records}
        t_reduce = time.perf_counter()
        trace_summary = tr.reduce_trace(str(files[0]), names)
        log(f"trace: {files[0].stat().st_size} bytes, reduced in "
            f"{time.perf_counter() - t_reduce:.1f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans = [(r.name, r.t_start, r.duration_s)
                 for r in run.obs.tracer.records
                 if run.opened <= r.t_start < run.closed]
        ctx = LayerContext(spans=spans, counters=run.window_counters,
                           slots=slots, trace=trace_summary)
        for m in manifest.metrics_for("per_layer", workload):
            value = manifest.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        breakdown = {"device_ops": [[n, s] for n, s in
                                    trace_summary.device_ops],
                     "idle_gaps": [[n, s] for n, s in
                                   trace_summary.idle_gaps]}
    else:
        decisions = np.asarray([c.seconds for c in run.calls
                                if c.t >= run.s0])
        e2e = {"slot_s": window / slots,
               "decision_p95_ms": 1000.0 * float(np.percentile(decisions,
                                                               95)),
               "setup_s": setup_s}
        for m in manifest.metrics_for("end_to_end", workload):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    log(f"counters in the window: {json.dumps(run.window_counters)}")

    t_check = time.perf_counter()
    readings = check.replay(cfg, fleet, latency, run, seed, log=log,
                            root=root)
    log(f"output check: {readings.checked_tasks} tasks scored on slots "
        f"{readings.checked_slots}, {time.perf_counter() - t_check:.1f} s")

    result = {"correct": readings.correct, "attempted": attempted,
              "failed": failed, "metrics": metrics,
              "device": {"platform": device.platform,
                         "kind": device.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak}}
    if traced:
        result["device"]["busy_s"] = trace_summary.busy_s
        result["device"]["window_s"] = trace_summary.window_s
        result["breakdown"] = breakdown
    result["check"] = readings.as_json()
    for line in readings.lines():
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
