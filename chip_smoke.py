"""Smoke run of the fused TORTA slot step on one TPU.

Drives the system's device hot path through the entry points a user
calls: ``Engine(..., TortaScheduler(micro_backend="fused"),
step_backend="jax")`` on a 25-region fleet of 500 servers per region
(12,500 servers, about 67k tasks per slot at 35% utilisation), then holds
its scheduling outcome against the numpy oracle engine on the same fleet,
traffic and seeds (phase 1's Sinkhorn runs on the TPU in both runs, so the
comparison isolates the fused micro scan and engine step).  Everything it
reads is generated from seeds.

    python chip_smoke.py

It needs a TPU: with no TPU (or run outside a checkout of this repo) it
prints why and exits non-zero without a result.  On success the last line
of stdout is one JSON object naming the device it ran on.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

REGIONS = 25
SERVERS_PER_REGION = 500
SLOTS = 4
UTILIZATION = 0.35

# The outcome the fused path must reproduce from the numpy oracle: the
# counts exactly, the seconds and cost within ``TPU_REL_TOL``.
COUNTS = ("completed", "dropped", "model_switches")
COMPARED = COUNTS + ("mean_response_s", "p95_response_s",
                     "power_cost_total")
# At 25x500 the fused micro scan places 17 of ~272k tasks on other servers
# than the numpy micro greedy, on the TPU and on a CPU backend alike, most
# likely because its float32 locality dots and norms round differently
# from numpy's (the TPU's emulated float64, see ``probe_f64``, moves only
# last bits).  Mean response and power cost then drift by ~3e-7 relative;
# the tolerance is ~35x that and far below the three to four significant
# digits the repo reports.
TPU_REL_TOL = 1e-5


def _repo_on_path() -> None:
    for path in (ROOT / "src", ROOT):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))


class SlotClock:
    """Demand source wrapper that stamps the host clock as the engine
    asks for each slot's arrivals, i.e. at the start of every slot."""

    def __init__(self, source):
        self.source = source
        self.stamps = []

    @property
    def n_slots(self) -> int:
        return self.source.n_slots

    def slot_batch(self, t: int):
        self.stamps.append(time.perf_counter())
        return self.source.slot_batch(t)


class CompileMeter:
    """Counts XLA backend compiles (or persistent-cache loads) and their
    seconds, plus persistent-cache hits and misses, while active."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def build_world(regions: int, servers_per_region: int, slots: int):
    """The fleet, topology and streaming diurnal traffic of the smoke."""
    _repo_on_path()
    from benchmarks.engine_scale import synthetic_topology

    from repro.sim import make_cluster_state
    from repro.sim.cluster import throughput_per_slot
    from repro.workload import make_source

    state = make_cluster_state(
        regions, seed=3,
        servers_per_region=(servers_per_region, servers_per_region + 1))
    rate = UTILIZATION * throughput_per_slot(state) / regions
    source = make_source("diurnal", slots, regions, seed=2, base_rate=rate)
    return synthetic_topology(regions), state, source


def run_engine(world, slots: int, *, fused: bool):
    """One engine run; returns (engine, per-slot host seconds).  A slot's
    time runs from the engine's request for its arrivals to the next
    slot's request (the last to the end of ``run``), so it ends at the
    host sync that closes each slot."""
    _repo_on_path()
    from repro.core.torta import TortaScheduler
    from repro.sim import Engine

    topo, state, source = world
    clock = SlotClock(source)
    if fused:
        sched = TortaScheduler(topo.n_regions, seed=0, micro_backend="fused")
        engine = Engine(topo, state.copy(), clock, sched, step_backend="jax")
    else:
        engine = Engine(topo, state.copy(), clock,
                        TortaScheduler(topo.n_regions, seed=0))
    engine.run(slots)
    stamps = clock.stamps + [time.perf_counter()]
    return engine, [b - a for a, b in zip(stamps, stamps[1:])]


def path_counters(counters: dict) -> dict:
    """The fallback, host-sync and retrace counters of a run report."""
    keep = ("engine.fallback.", "micro.host_sync.", ".retrace.")
    return {k: v for k, v in sorted(counters.items())
            if any(p in k for p in keep)}


def compare(got: dict, want: dict, rel: float = 0.0) -> dict:
    """Per compared metric, the (fused, oracle) pair where they differ:
    counts at all, the others by more than ``rel`` relative (``rel=0``:
    bitwise; nan equals nan)."""
    def close(k, a, b):
        tol = 0.0 if k in COUNTS else rel
        return a == b or (a != a and b != b) or abs(a - b) <= tol * abs(b)
    return {k: (got[k], want[k]) for k in COMPARED
            if not close(k, got[k], want[k])}


def response_agreement(got, want, rel: float = 1e-9) -> str:
    """How many per-task response times differ by more than ``rel``."""
    import numpy as np

    a = np.asarray(got.metrics.response_times)
    b = np.asarray(want.metrics.response_times)
    if a.shape != b.shape:
        return f"{a.size} vs {b.size} completions, not comparable per task"
    diff = np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
    return (f"{int(np.count_nonzero(diff > rel))} of {b.size} per-task "
            f"response times differ by more than {rel:g} relative, "
            f"{int(np.count_nonzero(diff))} differ at all "
            f"(max relative difference {float(diff.max())!r})")


def probe_f64(n: int = 1 << 16, seed: int = 0) -> dict:
    """How the default device's float64 differs from the host's IEEE
    float64: upload round trip, and the ops the fused path relies on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n) * 10.0 ** rng.uniform(-3, 3, n)
    y = rng.standard_normal(n) * 10.0 ** rng.uniform(-3, 3, n)
    e = -rng.uniform(0, 8, n)
    ops = {"add": (jnp.add, np.add, x, y),
           "mul": (jnp.multiply, np.multiply, x, y),
           "div": (jnp.divide, np.divide, x, y),
           "exp": (lambda a, _: jnp.exp(a), lambda a, _: np.exp(a), e, e)}
    out = {}
    with jax.enable_x64(True):
        out["roundtrip_mismatch"] = int(np.count_nonzero(
            np.asarray(jnp.asarray(x)) != x))
        for name, (dev_op, host_op, a, b) in ops.items():
            got = np.asarray(jax.jit(dev_op)(jnp.asarray(a), jnp.asarray(b)))
            want = host_op(a, b)
            rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
            out[f"{name}_mismatch"] = int(np.count_nonzero(got != want))
            out[f"{name}_max_rel"] = float(rel.max())
    return out


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU found (default device is "
              f"{device.platform}: {device.device_kind}); the smoke runs "
              f"only on a TPU")
        return 1
    try:
        _repo_on_path()
        from repro.compile_cache import enable_compile_cache
    except ImportError as err:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({err}); run it from a checkout of the repository")
        return 1
    print(f"device: {device.platform} {device.device_kind} "
          f"x{len(jax.devices())}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"float64 probe: {probe_f64()}", flush=True)

    world = build_world(REGIONS, SERVERS_PER_REGION, SLOTS)
    tasks = world[2].arrivals_matrix().sum(axis=1)
    print(f"fleet: {REGIONS} regions x {SERVERS_PER_REGION} servers = "
          f"{world[1].n_servers} servers; diurnal traffic at "
          f"{UTILIZATION:.0%} utilisation, tasks per slot "
          f"{tasks.astype(int).tolist()} (mean {tasks.mean():.0f})",
          flush=True)

    with CompileMeter() as cold:
        t0 = time.perf_counter()
        warmup = run_engine(world, SLOTS, fused=True)[0]
        warmup_s = time.perf_counter() - t0
    print(f"warm-up fused run ({SLOTS} slots): {warmup_s!r} s, "
          f"{cold.compiles} compiles in {cold.seconds!r} s, persistent "
          f"cache {cold.cache_hits} hits / {cold.cache_misses} misses",
          flush=True)

    engine, slot_s = run_engine(world, SLOTS, fused=True)
    fused = engine.metrics.summary()
    compiles = sum(v for k, v in engine.run_report.counters.items()
                   if k.startswith("device.compiles{"))
    print(f"steady fused run ({SLOTS} slots): s/slot {slot_s}, mean "
          f"{sum(slot_s) / len(slot_s)!r}, {compiles} compiles",
          flush=True)
    print(f"counters: {path_counters(engine.run_report.counters)}",
          flush=True)
    print(f"summary (fused, tpu): {fused}", flush=True)
    if not (fused["completed"] > 0 and math.isfinite(fused["mean_response_s"])
            and math.isfinite(fused["power_cost_total"])):
        print("the fused run completed nothing or its metrics are not finite")
        return 1
    if compare(warmup.metrics.summary(), fused):
        print("fused runs on the TPU do not repeat bitwise")
        return 1

    oracle_engine, oracle_slot_s = run_engine(world, SLOTS, fused=False)
    oracle = oracle_engine.metrics.summary()
    print(f"numpy oracle run ({SLOTS} slots): s/slot {oracle_slot_s}",
          flush=True)
    print(f"summary (oracle): {oracle}", flush=True)

    print(f"tpu vs oracle: {response_agreement(engine, oracle_engine)}",
          flush=True)
    bitwise = compare(fused, oracle)
    for key, (got, want) in bitwise.items():
        print(f"tpu vs oracle: {key}: fused {got!r} vs numpy {want!r} "
              f"(relative {abs(got - want) / abs(want)!r})")
    diff = compare(fused, oracle, rel=TPU_REL_TOL)
    if diff:
        print(f"oracle agreement: FAILED on {sorted(diff)} (counts must be "
              f"equal, the rest within relative {TPU_REL_TOL:g})")
        return 1
    print(f"oracle agreement: {', '.join(COUNTS)} equal; "
          + ("all compared metrics bitwise equal" if not bitwise else
             f"{', '.join(sorted(bitwise))} within relative "
             f"{TPU_REL_TOL:g}, the rest bitwise equal"), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
