"""Fused device-resident slot step: engine-level golden parity of the
jitted step backend, multi-region-scan assignment parity vs the per-region
scan, device-array ``BatchDecision`` round-trips, and the satellite
regressions (``make_dataset`` vectorization, ``prev_nu`` staleness,
arrivals-history buffering)."""

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # bare container: deterministic fallback shim
    from _hypofallback import given, settings, strategies as st

from repro.api import BatchDecision
from repro.core.macro import MacroAllocator
from repro.core.micro import MicroAllocator
from repro.core.predictor import K_HIST, make_dataset
from repro.core.torta import TortaScheduler
from repro.sim import (Engine, make_cluster_state, make_topology,
                       make_workload)
from repro.sim.cluster import SWITCH_POWER_FRAC, throughput_per_slot
from repro.sim.engine import FailureEvent, SlotObs
from repro.sim.engine_jax import (DYNAMIC_FIELDS, EngineStep, JaxStepper,
                                  close_step_impl, static_arrays)
from repro.sim.state import ACTIVE, MODEL_NAMES, OFF, WARMING
from repro.sim.topology import Topology
from repro.workload import make_source

N_MODELS = len(MODEL_NAMES)

METRIC_KEYS = ("completed", "dropped", "model_switches", "mean_response_s",
               "mean_wait_s", "mean_work_s", "power_cost_total",
               "switch_cost_total", "operational_overhead", "load_balance",
               "mean_queue_tasks")


def _topology(r: int, seed: int = 0) -> Topology:
    rng = np.random.default_rng(seed)
    lat = rng.uniform(10, 80, (r, r))
    lat = (lat + lat.T) / 2
    np.fill_diagonal(lat, 0.0)
    return Topology(name=f"synth{r}", n_regions=r, bandwidth_gbps=10,
                    latency=lat, graph=nx.cycle_graph(r))


def _world(r: int, spr: int, seed: int):
    """Randomized multi-region fleet state + obs builder."""
    rng = np.random.default_rng(seed)
    cs = make_cluster_state(r, seed=seed % 50,
                            servers_per_region=(spr, spr + 1))
    s = cs.n_servers
    cs.state[:] = np.where(rng.random(s) < 0.75, ACTIVE, OFF).astype(np.int8)
    cs.queue_s[:] = rng.exponential(30.0, s)
    cs.util[:] = rng.random(s)
    cs.current_model[:] = rng.integers(-1, N_MODELS, s).astype(np.int16)
    cs.warm_models[:] = rng.integers(
        -1, N_MODELS, cs.warm_models.shape).astype(np.int16)
    return cs, rng


def _obs(cs, t: int) -> SlotObs:
    r = cs.n_regions
    return SlotObs(t=t, latency=np.zeros((r, r)),
                   capacities=cs.capacities(),
                   total_capacities=cs.total_capacities(),
                   queue_s=cs.queue_by_region(),
                   queue_tasks=np.zeros(r), utilization=cs.utilizations(),
                   power_prices=cs.power_prices(),
                   prev_alloc=np.full((r, r), 1.0 / r),
                   arrivals_history=np.zeros((0, r)), state=cs,
                   slot_seconds=45.0)


# ---------------------------------------------------------------------------
# engine-level golden parity: Engine(step_backend="jax") vs the numpy engine
# ---------------------------------------------------------------------------


def _run_15x40(step_backend: str, scheduler=None, failures=None):
    topo = _topology(15, seed=1)
    cs = make_cluster_state(15, seed=3, servers_per_region=(40, 41))
    rate = 0.3 * throughput_per_slot(cs) / 15
    src = make_source("diurnal", 10, 15, seed=2, base_rate=rate)
    sched = scheduler or TortaScheduler(15, seed=0)
    return Engine(topo, cs.copy(), src, sched, seed=0, failures=failures,
                  step_backend=step_backend).run(10).summary()


def test_step_backend_golden_parity_15x40():
    """The jitted step backend reproduces the numpy engine's seeded 15x40
    trajectory EXACTLY (every summary metric bitwise equal)."""
    s_np = _run_15x40("numpy")
    s_jx = _run_15x40("jax")
    for k in METRIC_KEYS:
        assert s_np[k] == s_jx[k], k


def test_step_backend_golden_parity_under_failures():
    """Activation churn + a regional outage exercise the inactive-target
    sequential fallback mid-run; parity must survive it exactly."""
    fails = [FailureEvent(region=3, start_slot=3, duration=2)]
    s_np = _run_15x40("numpy", failures=fails)
    s_jx = _run_15x40("jax", failures=fails)
    for k in METRIC_KEYS:
        assert s_np[k] == s_jx[k], k


def test_fused_slot_end_to_end_exact():
    """The FULL fused slot — micro_backend="fused" + step_backend="jax" —
    reproduces the numpy TORTA trajectory exactly on a seeded run with a
    failure window (multi-region scan + jitted apply + drain/billing)."""
    topo = make_topology("abilene", seed=1)
    cs = make_cluster_state(topo.n_regions, seed=3)
    rate = 0.3 * throughput_per_slot(cs) / topo.n_regions
    wl = make_workload(8, topo.n_regions, seed=2, base_rate=rate)
    fails = [FailureEvent(region=1, start_slot=3, duration=2)]
    s_np = Engine(topo, cs.copy(), wl,
                  TortaScheduler(topo.n_regions, seed=0), seed=0,
                  failures=fails).run(8).summary()
    s_fu = Engine(topo, cs.copy(), wl,
                  TortaScheduler(topo.n_regions, seed=0,
                                 micro_backend="fused"),
                  seed=0, failures=fails,
                  step_backend="jax").run(8).summary()
    for k in METRIC_KEYS:
        assert s_np[k] == s_fu[k], k


def _numpy_warm(cs, slot_s):
    """``Engine._progress_warming``'s numpy block."""
    warming = cs.state == WARMING
    cs.warm_remaining_s[warming] -= slot_s
    done = warming & (cs.warm_remaining_s <= 0)
    cs.state[done] = ACTIVE
    cs.warm_remaining_s[done] = 0.0


def _numpy_single(cs, gs, mids, work_raw):
    """The numpy engine's grouped apply for single-task servers."""
    speed = np.maximum(cs.tflops[gs] / 112.0, 0.1)
    sw = cs.switch_cost_rows(gs, mids)
    energy = np.where(sw > 0, sw * cs.power_w[gs] * SWITCH_POWER_FRAC, 0.0)
    cs.note_model_rows(gs, mids)
    wk = work_raw / speed
    wait = cs.queue_s[gs] + sw
    cs.queue_s[gs] += sw + wk
    return sw, energy, wait, wk


def _numpy_close(cs, slot_s):
    """``Engine._finish_slot``'s numpy drain and power block."""
    act = cs.active_mask()
    busy = np.minimum(cs.queue_s, slot_s)
    cs.util = np.where(act, busy / slot_s, cs.util)
    cs.idle_slots = np.where(
        act, np.where(cs.util > 0.05, 0, cs.idle_slots + 1), cs.idle_slots)
    cs.queue_s = np.where(act, np.maximum(0.0, cs.queue_s - slot_s),
                          cs.queue_s)
    return np.where(act, (0.1 + 0.9 * cs.util) * cs.power_w * slot_s,
                    0.0), act


def _unpacked_close_power(cs, slot_s):
    """The close kernel's power draw, run on the mirror's columns as
    separate arrays.  Where the utilization is fractional it differs from
    the numpy block's by a few ulps: XLA's CPU backend contracts
    ``0.1 + 0.9 * util`` into one fused multiply-add."""
    with jax.enable_x64(True):
        step = EngineStep(
            **{name: jnp.asarray(getattr(cs, name))
               for name in DYNAMIC_FIELDS},
            **dict(zip(("speed", "power_w", "switch_scale"),
                       static_arrays(cs))))
        _, power, _ = jax.jit(close_step_impl)(step, slot_s)
        return np.asarray(power)


def _assert_mirrors_equal(a, b):
    for name in DYNAMIC_FIELDS:
        col_a, col_b = getattr(a, name), getattr(b, name)
        assert col_a.dtype == col_b.dtype, name
        np.testing.assert_array_equal(col_a, col_b, err_msg=name)


@pytest.mark.parametrize("n_rows", [11, 50, 200])
def test_packed_stepper_matches_numpy_blocks(n_rows):
    """Warming, the single-task apply and the close through the packed
    buffers leave the ``ClusterState`` mirror and return outputs bitwise
    equal to the numpy engine's blocks, with padded row buckets (16, 64,
    256) and warming servers; int8 states, int16 models and int64 idle
    counts round-trip through the int32 buffer.  The close's power draw
    is held bitwise to the kernel's on unpacked columns
    (``_unpacked_close_power`` says why not to numpy's)."""
    cs, rng = _world(5, 60, seed=n_rows)
    s = cs.n_servers
    warming = rng.random(s) < 0.2
    cs.state[warming] = WARMING
    cs.warm_remaining_s[:] = np.where(warming, rng.uniform(1, 90, s), 0.0)
    cs.idle_slots[:] = rng.integers(0, 40, s)
    ref = cs.copy()
    stepper = JaxStepper(cs)
    gs = rng.choice(s, n_rows, replace=False).astype(np.int64)
    mids = rng.integers(0, N_MODELS, n_rows).astype(np.int64)
    work = rng.exponential(20.0, n_rows)
    for slot_s in (45.0, 30.0):
        stepper.progress_warming(slot_s)
        _numpy_warm(ref, slot_s)
        _assert_mirrors_equal(cs, ref)
        got = stepper.apply_single_rows(gs, mids, work)
        want = _numpy_single(ref, gs, mids, work)
        for g, w in zip(got, want):
            assert g.shape == (n_rows,)
            np.testing.assert_array_equal(g, w)
        _assert_mirrors_equal(cs, ref)
        unpacked = _unpacked_close_power(ref, slot_s)
        power, act = stepper.close_slot(slot_s)
        _, want_act = _numpy_close(ref, slot_s)
        np.testing.assert_array_equal(act, want_act)
        np.testing.assert_array_equal(power, unpacked)
        _assert_mirrors_equal(cs, ref)
    assert (cs.state == WARMING).any() and (cs.warm_models != -1).any()


def test_step_backend_rejects_unknown():
    topo = _topology(2)
    cs = make_cluster_state(2, seed=0, servers_per_region=(3, 4))
    src = make_source("diurnal", 2, 2, seed=0, base_rate=2.0)
    with pytest.raises(ValueError, match="step backend"):
        Engine(topo, cs, src, TortaScheduler(2), step_backend="tpu")


# ---------------------------------------------------------------------------
# multi-region scan parity vs the per-region scan
# ---------------------------------------------------------------------------


def _random_tasks(rng, n: int, edim: int = 8):
    embeds = rng.standard_normal((n, edim)).astype(np.float32)
    has = rng.random(n) > 0.25
    embeds[~has] = 0.0
    return dict(
        mem_t=rng.uniform(1.0, 40.0, n),
        work=rng.uniform(1.0, 60.0, n),
        mids=rng.integers(0, N_MODELS, n).astype(np.int16),
        kind_ids=rng.integers(0, 3, n).astype(np.int8),
        embeds=embeds, has_embed=has,
        norms=np.linalg.norm(embeds, axis=1))


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=5),
       st.integers(min_value=0, max_value=2),
       st.integers(min_value=0, max_value=10_000))
def test_multi_region_scan_matches_per_region(r, size_class, seed):
    """ONE fused multi-region scan assigns identically to R separate
    per-region scans (the ``micro_backend="jax"`` path) across randomized
    region counts/sizes, multi-slot ring carry-over, zero-task regions
    and an all-inactive region."""
    spr = (3, 8, 17)[size_class]
    cs, rng = _world(r, spr, seed)
    if r > 1:
        cs.state[cs.region_slice(r - 1)] = OFF       # all-inactive region
    src = make_source("diurnal", 3, r, seed=seed % 97, base_rate=10.0)
    a_jx = MicroAllocator(backend="jax")
    a_fu = MicroAllocator(backend="fused")
    for t in range(3):
        batch = src.slot_batch(t)
        n = len(batch)
        region_of = rng.integers(0, r, n).astype(np.int32)
        if r > 2 and t == 1:
            region_of[region_of == 1] = 0            # zero-task region
        obs = _obs(cs, t)
        ref = np.full(n, -1, np.int32)
        for j in range(r):
            idx = np.flatnonzero(region_of == j)
            if idx.size:
                ref[idx] = a_jx.assign_batch(obs, j, batch, idx)
        got = a_fu.assign_batch_all(obs, batch, region_of)
        np.testing.assert_array_equal(got, ref, err_msg=f"slot {t}")
    # the carried rings agree region by region (uids are backend-local)
    for j in range(r):
        s_jx, s_fu = a_jx.locality_state(j), a_fu.locality_state(j)
        if s_jx is None:
            assert s_fu is None or (s_fu.count == 0).all()
            continue
        np.testing.assert_array_equal(s_jx.mids, s_fu.mids)
        np.testing.assert_array_equal(s_jx.slots, s_fu.slots)
        np.testing.assert_array_equal(s_jx.count, s_fu.count)
        np.testing.assert_allclose(s_jx.embeds, s_fu.embeds)


def test_fused_scan_zero_tasks_and_unrouted_rows():
    cs, rng = _world(2, 5, 11)
    alloc = MicroAllocator(backend="fused")
    src = make_source("diurnal", 1, 2, seed=3, base_rate=6.0)
    batch = src.slot_batch(0)
    out = alloc.assign_batch_all(_obs(cs, 0), batch.select(np.arange(0)),
                                 np.zeros(0, np.int32))
    assert out.shape == (0,)
    # unrouted rows (-1) stay buffered and never reach the scan
    region_of = np.full(len(batch), -1, np.int32)
    out = alloc.assign_batch_all(_obs(cs, 0), batch, region_of)
    assert (out == -1).all()


def test_fused_scan_all_inactive_everywhere():
    cs, rng = _world(3, 4, 7)
    cs.state[:] = OFF
    src = make_source("diurnal", 1, 3, seed=5, base_rate=8.0)
    batch = src.slot_batch(0)
    alloc = MicroAllocator(backend="fused")
    region_of = rng.integers(0, 3, len(batch)).astype(np.int32)
    out = alloc.assign_batch_all(_obs(cs, 0), batch, region_of)
    assert (out == -1).all()
    for j in range(3):
        lstate = alloc.locality_state(j)
        assert lstate is None or (lstate.count == 0).all()


def test_fused_assign_core_matches_numpy_single_region():
    """The per-region ``_assign_core`` API rides the same fused scan and
    still matches the numpy oracle exactly (rings carried across slots)."""
    cs, rng = _world(1, 9, 23)
    a_np = MicroAllocator(backend="numpy")
    a_fu = MicroAllocator(backend="fused")
    for t in range(3):
        arrs = _random_tasks(rng, 21)
        obs = _obs(cs, t)
        np.testing.assert_array_equal(a_np._assign_core(obs, 0, **arrs),
                                      a_fu._assign_core(obs, 0, **arrs),
                                      err_msg=f"slot {t}")
    s_np, s_fu = a_np.locality_state(0), a_fu.locality_state(0)
    np.testing.assert_array_equal(s_np.mids, s_fu.mids)
    np.testing.assert_allclose(s_np.embeds, s_fu.embeds)


# ---------------------------------------------------------------------------
# device-array BatchDecision
# ---------------------------------------------------------------------------


def test_batch_decision_device_array_roundtrip():
    """A decision built from jax device arrays is NOT synced to host at
    construction; ``validate()`` is the single sync point and the values
    round-trip exactly."""
    import jax.numpy as jnp
    cs = make_cluster_state(3, seed=1, servers_per_region=(4, 5))
    region = np.array([0, 2, -1, 1], np.int32)
    server = np.array([1, 0, -1, 2], np.int32)
    act = np.array([2, -1, 3], np.int64)
    dec = BatchDecision(region=jnp.asarray(region),
                        server=jnp.asarray(server),
                        activation=jnp.asarray(act))
    # construction kept the channels device-side (no forced host sync)
    assert callable(getattr(dec.region, "block_until_ready", None))
    assert callable(getattr(dec.server, "block_until_ready", None))
    assert dec.region.dtype == np.int32
    dec.validate(4, cs)
    assert isinstance(dec.region, np.ndarray)
    assert isinstance(dec.server, np.ndarray)
    np.testing.assert_array_equal(dec.region, region)
    np.testing.assert_array_equal(dec.server, server)
    assert dec.activation_targets(3) == {0: 2, 2: 3}


def test_batch_decision_device_array_validation_errors():
    import jax.numpy as jnp
    cs = make_cluster_state(2, seed=1, servers_per_region=(3, 4))
    dec = BatchDecision(region=jnp.asarray(np.array([0, 5], np.int32)),
                        server=jnp.asarray(np.array([0, 0], np.int32)))
    with pytest.raises(ValueError, match="region values"):
        dec.validate(2, cs)
    # int64 device input is normalized device-side to int32
    dec = BatchDecision(region=jnp.asarray(np.array([0], np.int64)),
                        server=jnp.asarray(np.array([0], np.int64)))
    assert dec.region.dtype == np.int32
    dec.validate(1, cs)


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------


def _make_dataset_loop(arrivals, util, queue):
    """The pre-vectorization window loop, kept as the regression oracle."""
    t_total, r = arrivals.shape
    h = arrivals / np.maximum(arrivals.sum(1, keepdims=True), 1e-9)
    feats = np.concatenate([util, queue / np.maximum(queue.max(), 1.0), h],
                           axis=1)
    xs, ys = [], []
    for t in range(K_HIST, t_total - 1):
        xs.append(feats[t - K_HIST:t])
        ys.append(h[t + 1])
    return np.asarray(xs, np.float32), np.asarray(ys, np.float32)


@pytest.mark.parametrize("t_total,r", [(4, 3), (K_HIST + 1, 2), (K_HIST + 2, 2),
                                       (24, 5), (61, 12)])
def test_make_dataset_matches_loop(t_total, r):
    rng = np.random.default_rng(t_total * 31 + r)
    arrivals = rng.poisson(20.0, (t_total, r)).astype(np.float64)
    util = rng.random((t_total, r))
    queue = rng.exponential(5.0, (t_total, r))
    want_x, want_y = _make_dataset_loop(arrivals, util, queue)
    got_x, got_y = make_dataset(arrivals, util, queue)
    np.testing.assert_array_equal(got_x, want_x)
    np.testing.assert_array_equal(got_y, want_y)
    assert got_x.dtype == np.float32 and got_y.dtype == np.float32


def test_prev_nu_tracks_supply_under_policy(monkeypatch):
    """Regression: with a trained policy driving allocation, prev_nu must
    keep tracking realized supply — toggling the policy off used to see a
    bogus 'supply shock' snap from the stale pre-policy nu."""
    import repro.core.policy as pol
    r = 3
    monkeypatch.setattr(pol, "mean_action",
                        lambda params, obs, n: np.full((n, n), 1.0 / n))
    macro = MacroAllocator(r, policy_params=object())
    kw = dict(demand=np.array([5.0, 3.0, 2.0]),
              predicted=np.full(r, 1 / 3), power_cost=np.ones(r),
              latency=np.ones((r, r)), queue=np.zeros(r),
              utilization=np.zeros(r), q_max=100.0)
    cap_a = np.array([10.0, 1.0, 1.0])
    macro.allocate(capacity=cap_a, **kw)
    np.testing.assert_allclose(macro.prev_nu, cap_a / cap_a.sum())
    # switch the policy off mid-experiment with UNCHANGED supply: the
    # smoothed path must not see a shock (eta stays at the default)
    macro.policy_params = None
    a_prev = macro.a_prev.copy()
    probs = macro.ot_plan(0.5 * kw["demand"] + 0.5 * kw["predicted"]
                          * kw["demand"].sum(), cap_a, kw["power_cost"],
                          kw["latency"])
    got = macro.allocate(capacity=cap_a, **kw)
    want = (1 - macro.eta) * a_prev + macro.eta * probs
    want = want / np.maximum(want.sum(1, keepdims=True), 1e-9)
    np.testing.assert_allclose(got, want)


def test_arrivals_history_buffer_semantics():
    """The preallocated (T, R) arrivals buffer preserves the legacy
    semantics: list-of-rows view, per-slot (t, R) obs slice, growth past
    the initial capacity, and read-only slices."""
    r = 3
    topo = _topology(r, seed=2)
    cs = make_cluster_state(r, seed=1, servers_per_region=(3, 4))
    n_slots = 70                                   # > initial 64 capacity
    src = make_source("diurnal", n_slots, r, seed=4, base_rate=3.0)
    seen = []

    class Probe:
        name = "probe"
        def reset(self): pass
        def schedule_batch(self, obs, batch):
            seen.append(obs.arrivals_history)
            # the engine records the slot's arrivals before building obs
            assert obs.arrivals_history.shape == (obs.t + 1, r)
            with pytest.raises(ValueError):
                obs.arrivals_history[:] = 0.0      # read-only view
            return BatchDecision(region=np.full(len(batch), -1, np.int32),
                                 server=np.full(len(batch), -1, np.int32))

    eng = Engine(topo, cs, src, Probe(), drop_after_slots=1)
    eng.run()
    hist = eng.arrivals_hist
    assert isinstance(hist, list) and len(hist) == n_slots
    expect = src.arrivals_matrix()
    np.testing.assert_array_equal(np.stack(hist), expect)
    # every slot's view matched the prefix of the realized matrix
    np.testing.assert_array_equal(seen[-1], expect[:n_slots])
