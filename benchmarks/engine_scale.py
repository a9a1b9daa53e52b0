"""Slot-throughput scaling: array-native engine vs per-object reference,
plus workload-generation scaling: streaming TaskBatch vs legacy objects,
plus baseline-scheduler throughput: native ``schedule_batch`` vs the
``LegacySchedulerAdapter`` object path.

Measures slots/sec for the struct-of-arrays ``sim.engine.Engine`` against
the frozen object-per-server ``sim.reference.ReferenceEngine`` across
cluster sizes (5x50, 15x200, 25x500 region x server configs), both driving
the full TORTA scheduler at ~35% fleet utilization.  Emits
``BENCH_engine_scale.json`` at the repo root so the perf trajectory is
tracked across PRs.

The workload benchmark times demand generation separately — the legacy
per-object ``make_workload`` path against the array-native
``StreamingWorkload`` batches at 15x200 and 25x500, plus a 1000-slot
multi-day streaming row — and emits ``BENCH_workload_scale.json``.

The baseline benchmark runs all five baselines (RR, SkyLB, SDIB,
ReactiveOT, MILP) on a flash_crowd stream at 15x200 and 25x500, once
batch-native and once through the adapter (Task materialization +
``schedule()`` + decision-dict conversion each slot), and emits
``BENCH_baseline_batch.json``.

The micro benchmark A/Bs the phase-2 allocator backends — the numpy
greedy walk against the jit-compiled ``lax.scan`` pipeline
(``TortaScheduler(micro_backend="jax")``) — at 15x200 and 25x500, and
emits ``BENCH_micro_jit.json``.

The fused benchmark A/Bs the fused device-resident slot step — ONE
multi-region scan (``micro_backend="fused"``) + the jitted engine step
(``step_backend="jax"``) — against the numpy and per-region-jax
generations at 15x200 and 25x500, and emits ``BENCH_fused_step.json``.

Every emitted JSON embeds a ``"provenance"`` stamp (environment, git SHA,
wall-clock) from ``benchmarks.common.provenance``.  ``--obs`` runs the
fused config once more with phase tracing on, prints the span summary
table and fallback/retrace counters, and exports the full ``RunReport``
under ``benchmarks/results/``.  ``--toy`` shrinks every config to a
seconds-scale smoke (used by CI) and skips the ``BENCH_*.json`` writes so
toy numbers never clobber the tracked perf trajectory.

    PYTHONPATH=src python benchmarks/engine_scale.py [--quick]
    PYTHONPATH=src python benchmarks/engine_scale.py --workload-only
    PYTHONPATH=src python benchmarks/engine_scale.py --baselines-only
    PYTHONPATH=src python benchmarks/engine_scale.py --micro-only
    PYTHONPATH=src python benchmarks/engine_scale.py --fused-only [--obs]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import networkx as nx
import numpy as np

try:
    from benchmarks.common import provenance
except ImportError:          # run as a script: benchmarks/ is sys.path[0]
    from common import provenance

OUT_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_engine_scale.json"
WL_OUT_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_workload_scale.json"
BL_OUT_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_baseline_batch.json"
MJ_OUT_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_micro_jit.json"
FS_OUT_PATH = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_fused_step.json"

CONFIGS = [
    # (regions, servers/region, array slots, reference slots)
    (5, 50, 12, 4),
    (15, 200, 8, 2),
    (25, 500, 4, 1),
]

# --toy: every benchmark shrinks to a seconds-scale smoke and artifact
# writes are skipped (CI runs this; toy numbers must never overwrite the
# tracked BENCH_*.json perf trajectory)
TOY = False


def emit(path: pathlib.Path, out: dict) -> None:
    """Stamp provenance and write the benchmark artifact (skipped under
    ``--toy``, where the numbers are smoke-scale)."""
    out["provenance"] = provenance()
    if TOY:
        print(f"toy mode: skipping write of {path.name}")
        return
    path.write_text(json.dumps(out, indent=1))
    print(f"wrote {path}")

WL_CONFIGS = [
    # (regions, servers/region, legacy slots, streaming slots)
    (15, 200, 8, 64),
    (25, 500, 4, 32),
]


def synthetic_topology(r: int, seed: int = 0):
    from repro.sim.topology import Topology
    rng = np.random.default_rng(seed)
    lat = rng.uniform(10, 80, (r, r))
    lat = (lat + lat.T) / 2
    np.fill_diagonal(lat, 0.0)
    return Topology(name=f"synth{r}", n_regions=r, bandwidth_gbps=10,
                    latency=lat, graph=nx.cycle_graph(r))


def bench_config(r: int, spr: int, slots_new: int, slots_ref: int, *,
                 run_reference: bool = True, seed: int = 3) -> dict:
    from repro.core.torta import TortaScheduler
    from repro.sim import Engine, make_cluster_state, make_workload
    from repro.sim.cluster import throughput_per_slot
    from repro.sim.reference import ReferenceEngine, make_reference_torta

    topo = synthetic_topology(r)
    st = make_cluster_state(r, seed=seed, servers_per_region=(spr, spr + 1))
    rate = 0.35 * throughput_per_slot(st) / r
    wl = make_workload(max(slots_new, slots_ref), r, seed=2, base_rate=rate)
    n_tasks_slot = len(wl.tasks[0])

    t0 = time.time()
    Engine(topo, st.copy(), wl, TortaScheduler(r, seed=0)).run(slots_new)
    dt_new = (time.time() - t0) / slots_new

    row = {
        "regions": r, "servers_per_region": spr, "servers": st.n_servers,
        "tasks_per_slot": n_tasks_slot,
        "array_s_per_slot": dt_new,
        "array_slots_per_s": 1.0 / dt_new,
    }
    if run_reference:
        cl = st.to_cluster()
        t0 = time.time()
        ReferenceEngine(topo, cl, wl,
                        make_reference_torta(r, seed=0)).run(slots_ref)
        dt_ref = (time.time() - t0) / slots_ref
        row.update(reference_s_per_slot=dt_ref,
                   reference_slots_per_s=1.0 / dt_ref,
                   speedup=dt_ref / dt_new)
    else:
        # explicit nulls + reason, so downstream tooling never key-errors
        # on the rows where the per-object reference was not run
        row.update(reference_s_per_slot=None, reference_slots_per_s=None,
                   speedup=None,
                   reference_skipped="per-object reference impractical "
                                     "at this scale")
    return row


def bench_workload(r: int, spr: int, slots_legacy: int,
                   slots_stream: int, *, seed: int = 3) -> dict:
    """Per-slot workload-generation time: legacy object path vs the
    streaming TaskBatch path, at the same calibrated arrival rate."""
    from repro.sim import make_cluster_state, make_workload
    from repro.sim.cluster import throughput_per_slot
    from repro.workload import make_source

    st = make_cluster_state(r, seed=seed, servers_per_region=(spr, spr + 1))
    rate = 0.35 * throughput_per_slot(st) / r

    t0 = time.time()
    wl = make_workload(slots_legacy, r, seed=2, base_rate=rate)
    dt_legacy = (time.time() - t0) / slots_legacy
    n_legacy = sum(len(ts) for ts in wl.tasks)

    src = make_source("diurnal", slots_stream, r, seed=2, base_rate=rate)
    t0 = time.time()
    n_stream = sum(len(b) for b in src)
    dt_stream = (time.time() - t0) / slots_stream

    return {
        "regions": r, "servers_per_region": spr,
        "tasks_per_slot_legacy": n_legacy / slots_legacy,
        "tasks_per_slot_stream": n_stream / slots_stream,
        "legacy_s_per_slot": dt_legacy,
        "stream_s_per_slot": dt_stream,
        "speedup": dt_legacy / dt_stream,
    }


def bench_multiday_stream(n_slots: int = 1000, r: int = 25, *,
                          base_rate: float = 40.0) -> dict:
    """Streaming-only row: a 1000-slot multi-day horizon generated
    entirely as TaskBatch arrays (the per-object path would be minutes)."""
    from repro.workload import make_source

    src = make_source("multiday", n_slots, r, seed=2, base_rate=base_rate,
                      days=7)
    t0 = time.time()
    total = sum(len(b) for b in src)
    dt = time.time() - t0
    return {"scenario": "multiday", "slots": n_slots, "regions": r,
            "tasks_total": total, "s_per_slot": dt / n_slots,
            "tasks_per_s": total / max(dt, 1e-9)}


BL_CONFIGS = [
    # (regions, servers/region, slots, utilization)
    (15, 200, 3, 0.10),
    (25, 500, 2, 0.05),
]


def bench_baselines() -> None:
    """All five baselines, batch-native vs the adapter object path, on a
    flash_crowd stream — emits ``BENCH_baseline_batch.json``."""
    from repro.api import LegacyOnlyView, LegacySchedulerAdapter
    from repro.baselines import (MilpScheduler, ReactiveOTScheduler,
                                 RoundRobinScheduler, SDIBScheduler,
                                 SkyLBScheduler)
    from repro.sim import Engine, make_cluster_state
    from repro.sim.cluster import throughput_per_slot
    from repro.workload import make_source

    factories = {
        "RR": lambda r: RoundRobinScheduler(),
        "SkyLB": lambda r: SkyLBScheduler(),
        "SDIB": lambda r: SDIBScheduler(),
        "ReactiveOT": lambda r: ReactiveOTScheduler(r),
        "MILP": lambda r: MilpScheduler(r),
    }
    rows = []
    for r, spr, slots, util in BL_CONFIGS:
        st0 = make_cluster_state(r, seed=3,
                                 servers_per_region=(spr, spr + 1))
        rate = util * throughput_per_slot(st0) / r
        src = make_source("flash_crowd", slots, r, seed=2, base_rate=rate)
        n_tasks = int(src.arrivals_matrix().sum())
        print(f"[baseline_batch] {r} regions x ~{spr} servers "
              f"(~{n_tasks // slots} tasks/slot) ...", flush=True)
        def timed(mk_sched, check_native=False):
            # warm-up run first (numpy/scipy first-call costs), then the
            # best of two timed runs — the paths differ by only the
            # adapter's per-slot conversions, so noise matters
            best = float("inf")
            for rep in range(3):
                eng = Engine(synthetic_topology(r), st0.copy(), src,
                             mk_sched(), seed=4)
                if check_native:
                    assert eng.batch_native
                t0 = time.time()
                eng.run()
                if rep > 0:
                    best = min(best, (time.time() - t0) / slots)
            return best

        for name, mk in factories.items():
            dt_batch = timed(lambda: mk(r), check_native=True)
            dt_adapter = timed(
                lambda: LegacySchedulerAdapter(LegacyOnlyView(mk(r))))
            row = {"baseline": name, "regions": r,
                   "servers_per_region": spr,
                   "tasks_per_slot": n_tasks / slots,
                   "batch_s_per_slot": dt_batch,
                   "adapter_s_per_slot": dt_adapter,
                   "speedup": dt_adapter / dt_batch}
            print(f"  {name:10s} batch {dt_batch * 1e3:8.1f} ms/slot"
                  f"  adapter {dt_adapter * 1e3:8.1f} ms/slot"
                  f"  -> {row['speedup']:.2f}x", flush=True)
            rows.append(row)
    out = {"benchmark": "baseline_batch",
           "workload": "flash_crowd scenario (StreamingWorkload)",
           "paths": "native schedule_batch vs LegacySchedulerAdapter",
           "rows": rows}
    emit(BL_OUT_PATH, out)


MICRO_CONFIGS = [
    # (regions, servers/region, numpy slots, jax slots)
    (15, 200, 4, 6),
    (25, 500, 2, 3),
]


def bench_micro() -> None:
    """Phase-2 micro backends head to head: the numpy greedy walk vs the
    jit-compiled lax.scan pipeline, full-engine s/slot on the same
    calibrated workload as the engine bench — emits
    ``BENCH_micro_jit.json``."""
    from repro.core.torta import TortaScheduler
    from repro.sim import Engine, make_cluster_state, make_workload
    from repro.sim.cluster import throughput_per_slot

    rows = []
    for r, spr, s_np, s_jx in MICRO_CONFIGS:
        topo = synthetic_topology(r)
        st = make_cluster_state(r, seed=3,
                                servers_per_region=(spr, spr + 1))
        rate = 0.35 * throughput_per_slot(st) / r
        wl = make_workload(max(s_np, s_jx), r, seed=2, base_rate=rate)
        n_tasks_slot = len(wl.tasks[0])
        print(f"[micro_jit] {r} regions x ~{spr} servers "
              f"(~{n_tasks_slot} tasks/slot) ...", flush=True)

        t0 = time.time()
        Engine(topo, st.copy(), wl,
               TortaScheduler(r, seed=0)).run(s_np)
        dt_np = (time.time() - t0) / s_np

        # first jax run pays the per-shape jit compiles (pad-and-mask
        # keeps them to a handful); the timed run measures steady state
        Engine(topo, st.copy(), wl,
               TortaScheduler(r, seed=0, micro_backend="jax")).run(s_jx)
        t0 = time.time()
        Engine(topo, st.copy(), wl,
               TortaScheduler(r, seed=0, micro_backend="jax")).run(s_jx)
        dt_jx = (time.time() - t0) / s_jx

        row = {"regions": r, "servers_per_region": spr,
               "servers": st.n_servers, "tasks_per_slot": n_tasks_slot,
               "numpy_s_per_slot": dt_np, "jax_s_per_slot": dt_jx,
               "speedup": dt_np / dt_jx}
        print(f"  numpy {dt_np:7.2f} s/slot  jax {dt_jx:7.2f} s/slot"
              f"  -> {row['speedup']:.1f}x", flush=True)
        rows.append(row)

    out = {"benchmark": "micro_jit",
           "scheduler": "TORTA, micro_backend numpy vs jax (lax.scan)",
           "timing": "full engine s/slot; jax timed on a second run "
                     "(first run pays per-shape jit compiles)",
           "utilization": 0.35,
           "rows": rows}
    emit(MJ_OUT_PATH, out)


FUSED_CONFIGS = [
    # (regions, servers/region, numpy slots, jax slots, fused slots)
    (15, 200, 4, 6, 8),
    (25, 500, 2, 3, 4),
]


def bench_fused(obs: bool = False, retrace_budget: bool = False) -> None:
    """The fused device-resident slot step head to head with the two
    prior generations: numpy micro backend, per-region jitted scans
    (``micro_backend="jax"``), and the fused multi-region scan + jitted
    engine step (``micro_backend="fused"`` + ``step_backend="jax"``) —
    emits ``BENCH_fused_step.json``.  The default-on counters stay live
    during the timed runs (their overhead is part of the number) and each
    fused row carries its counter totals.  ``obs=True`` adds one traced
    fused run per config: span summary table on stdout + a full
    ``RunReport`` JSON under ``benchmarks/results/``."""
    from repro.core.torta import TortaScheduler
    from repro.sim import Engine, make_cluster_state, make_workload
    from repro.sim.cluster import throughput_per_slot

    rows = []
    for r, spr, s_np, s_jx, s_fu in FUSED_CONFIGS:
        topo = synthetic_topology(r)
        st = make_cluster_state(r, seed=3,
                                servers_per_region=(spr, spr + 1))
        rate = 0.35 * throughput_per_slot(st) / r
        wl = make_workload(max(s_np, s_jx, s_fu), r, seed=2,
                          base_rate=rate)
        n_tasks_slot = len(wl.tasks[0])
        print(f"[fused_step] {r} regions x ~{spr} servers "
              f"(~{n_tasks_slot} tasks/slot) ...", flush=True)

        def timed(mk_engine, slots, warmup=False):
            # jitted configs pay per-shape compiles on a first run; the
            # timed run measures steady state
            if warmup:
                mk_engine().run(slots)
            eng = mk_engine()
            t0 = time.time()
            eng.run(slots)
            return (time.time() - t0) / slots, eng

        def mk_fused(obs_spec=None):
            return Engine(topo, st.copy(), wl,
                          TortaScheduler(r, seed=0, micro_backend="fused"),
                          step_backend="jax", obs=obs_spec)

        dt_np, _ = timed(lambda: Engine(topo, st.copy(), wl,
                                        TortaScheduler(r, seed=0)), s_np)
        dt_jx, _ = timed(lambda: Engine(
            topo, st.copy(), wl,
            TortaScheduler(r, seed=0, micro_backend="jax")), s_jx,
            warmup=True)
        dt_fu, eng_fu = timed(mk_fused, s_fu, warmup=True)

        row = {"regions": r, "servers_per_region": spr,
               "servers": st.n_servers, "tasks_per_slot": n_tasks_slot,
               "numpy_s_per_slot": dt_np, "jax_s_per_slot": dt_jx,
               "fused_s_per_slot": dt_fu,
               "fused_speedup_vs_jax": dt_jx / dt_fu,
               "fused_speedup_vs_numpy": dt_np / dt_fu}
        if eng_fu.run_report is not None:
            row["fused_counters"] = eng_fu.run_report.counters
        if retrace_budget and eng_fu.run_report is not None:
            # hard-fail the run if the fused config compiled more bucket
            # shapes than analysis/retrace_budget.toml allows
            from repro.analysis import retrace
            from repro.analysis.basefile import load_budget
            budget = load_budget(pathlib.Path(__file__).resolve().parent
                                 .parent / "analysis"
                                 / "retrace_budget.toml")
            rep = retrace.enforce(eng_fu.run_report.counters, budget)
            row["retrace_shapes"] = rep.observed
            print(f"  retrace budget OK: {rep.observed}", flush=True)

        from repro.analysis import sanitize as sanitize_rt
        if sanitize_rt.enabled():
            # REPRO_SANITIZE=1: prove the checkify-instrumented kernels
            # change no metric bit vs the unguarded fused path
            with sanitize_rt.force(False):
                m_plain = mk_fused().run(s_fu).summary()
            m_san = mk_fused().run(s_fu).summary()
            diff = [k for k in m_plain
                    if not (m_plain[k] == m_san[k]
                            or (m_plain[k] != m_plain[k]
                                and m_san[k] != m_san[k]))]
            if diff:
                raise SystemExit(
                    f"sanitized fused run diverged on {diff}")
            row["sanitized_parity"] = "bitwise"
            print("  sanitized parity OK (REPRO_SANITIZE=1, "
                  "checkify user+float+index)", flush=True)
        print(f"  numpy {dt_np:7.2f}  per-region-jax {dt_jx:7.2f}  "
              f"fused {dt_fu:7.2f} s/slot  "
              f"-> {row['fused_speedup_vs_jax']:.1f}x vs jax, "
              f"{row['fused_speedup_vs_numpy']:.1f}x vs numpy", flush=True)
        rows.append(row)

        if obs:
            # one traced run: spans + counters + the full RunReport
            eng_t = mk_fused("trace")
            eng_t.run(s_fu)
            rep = eng_t.run_report
            print(f"  -- traced fused run ({r}x{spr}) span summary --")
            print(eng_t.obs.tracer.summary_table())
            for key in sorted(rep.counters):
                print(f"  {key} = {rep.counters[key]}")
            out_dir = pathlib.Path(__file__).resolve().parent / "results"
            out_dir.mkdir(parents=True, exist_ok=True)
            rep_path = out_dir / f"runreport_fused_{r}x{spr}.json"
            rep.save(rep_path)
            print(f"  run report -> {rep_path}", flush=True)

    out = {"benchmark": "fused_step",
           "scheduler": "TORTA; numpy vs per-region jax scans vs fused "
                        "multi-region scan + jitted engine step "
                        "(step_backend=jax)",
           "timing": "full engine s/slot; jitted configs timed on a "
                     "second run (first run pays per-shape compiles)",
           "utilization": 0.35,
           "rows": rows}
    emit(FS_OUT_PATH, out)


def run_workload_bench() -> None:
    rows = []
    for r, spr, s_leg, s_str in WL_CONFIGS:
        print(f"[workload_scale] {r} regions x ~{spr} servers ...",
              flush=True)
        row = bench_workload(r, spr, s_leg, s_str)
        print(f"  legacy {row['legacy_s_per_slot'] * 1e3:8.1f} ms/slot"
              f"  stream {row['stream_s_per_slot'] * 1e3:6.2f} ms/slot"
              f"  -> {row['speedup']:.1f}x"
              f"  (~{row['tasks_per_slot_stream']:.0f} tasks/slot)",
              flush=True)
        rows.append(row)
    md = bench_multiday_stream()
    print(f"[workload_scale] multiday 1000-slot stream: "
          f"{md['tasks_total']} tasks at {md['tasks_per_s']:.0f} tasks/s",
          flush=True)
    out = {"benchmark": "workload_scale",
           "generator": "diurnal scenario (StreamingWorkload TaskBatch)"
                        " vs legacy make_workload",
           "utilization": 0.35,
           "rows": rows,
           "multiday_stream": md}
    emit(WL_OUT_PATH, out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the reference run on the largest config")
    ap.add_argument("--workload-only", action="store_true",
                    help="only run the workload-generation benchmark")
    ap.add_argument("--baselines-only", action="store_true",
                    help="only run the baseline batch-vs-adapter benchmark")
    ap.add_argument("--micro-only", action="store_true",
                    help="only run the micro numpy-vs-jax backend benchmark")
    ap.add_argument("--fused-only", action="store_true",
                    help="only run the fused-slot-step benchmark "
                         "(numpy vs per-region-jax vs fused)")
    ap.add_argument("--obs", action="store_true",
                    help="add a traced fused run per config: span summary "
                         "table + RunReport JSON under benchmarks/results/")
    ap.add_argument("--retrace-budget", action="store_true",
                    help="enforce analysis/retrace_budget.toml against the "
                         "fused run's retrace counters (hard failure on "
                         "overrun or unbudgeted counter)")
    ap.add_argument("--toy", action="store_true",
                    help="shrink every config to a seconds-scale smoke "
                         "and skip BENCH_*.json writes (CI)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.toy:
        global TOY
        TOY = True
        CONFIGS[:] = [(3, 8, 3, 1)]
        WL_CONFIGS[:] = [(3, 8, 3, 8)]
        BL_CONFIGS[:] = [(3, 8, 2, 0.10)]
        MICRO_CONFIGS[:] = [(3, 8, 2, 2)]
        FUSED_CONFIGS[:] = [(3, 8, 2, 2, 3)]

    if args.baselines_only:
        bench_baselines()
        return
    if args.micro_only:
        bench_micro()
        return
    if args.fused_only:
        bench_fused(obs=args.obs, retrace_budget=args.retrace_budget)
        return

    if not args.workload_only:
        rows = []
        for i, (r, spr, s_new, s_ref) in enumerate(CONFIGS):
            run_ref = not (args.quick and i == len(CONFIGS) - 1)
            print(f"[engine_scale] {r} regions x ~{spr} servers ...",
                  flush=True)
            row = bench_config(r, spr, s_new, s_ref, run_reference=run_ref)
            spd = row.get("speedup")
            print(f"  array {row['array_s_per_slot']:.3f} s/slot"
                  + (f"  reference {row['reference_s_per_slot']:.2f} s/slot"
                     f"  -> {spd:.1f}x" if spd else ""), flush=True)
            rows.append(row)

        out = {"benchmark": "engine_scale",
               "scheduler": "TORTA (numpy micro backend)",
               "utilization": 0.35,
               "rows": rows}
        emit(OUT_PATH, out)

    run_workload_bench()
    if not args.workload_only:
        bench_baselines()


if __name__ == "__main__":
    main()
