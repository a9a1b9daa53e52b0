"""JIT-native micro layer: scanned greedy parity vs the numpy oracle,
LocalityState ring-buffer equivalence, and fused-kernel interpret checks."""
import copy

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # bare container: deterministic fallback shim
    from _hypofallback import given, settings, strategies as st

from repro.core.micro import (LocalityTracker, MicroAllocator, RecentTask,
                              W_WARM, hw_load_matrix_np,
                              server_feature_matrix, task_feature_arrays)
from repro.core.micro_state import EMPTY, LocalityState
from repro.core.torta import TortaScheduler
from repro.kernels.compat_score import (compat_score, fused_score,
                                        fused_score_ref, score_matrix)
from repro.sim import (Engine, make_cluster, make_cluster_state,
                       make_topology, make_workload)
from repro.sim.cluster import throughput_per_slot
from repro.sim.engine import SlotObs
from repro.sim.state import ACTIVE, MODEL_NAMES, OFF

N_MODELS = len(MODEL_NAMES)


# ---------------------------------------------------------------------------
# randomized scan-vs-numpy parity sweep
# ---------------------------------------------------------------------------


def _random_world(spr: int, seed: int):
    """A one-region cluster with randomized dynamic state + a SlotObs."""
    rng = np.random.default_rng(seed)
    cs = make_cluster_state(1, seed=seed % 50,
                            servers_per_region=(spr, spr + 1))
    s = cs.n_servers
    cs.state[:] = np.where(rng.random(s) < 0.75, ACTIVE, OFF).astype(np.int8)
    cs.queue_s[:] = rng.exponential(30.0, s)
    cs.util[:] = rng.random(s)
    cs.current_model[:] = rng.integers(-1, N_MODELS, s).astype(np.int16)
    warm = rng.integers(-1, N_MODELS, cs.warm_models.shape)
    cs.warm_models[:] = warm.astype(np.int16)
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
@given(st.integers(min_value=0, max_value=40),
       st.integers(min_value=0, max_value=2),
       st.integers(min_value=0, max_value=10_000))
def test_scan_matches_numpy_assign_core(n_tasks, size_class, seed):
    """The lax.scan greedy returns IDENTICAL server choices to the numpy
    ``_assign_core`` across random region sizes and multi-slot history
    carry-over (the jit pipeline mirrors the oracle's float64 op order)."""
    spr = (4, 11, 23)[size_class]
    cs, rng = _random_world(spr, seed)
    a_np = MicroAllocator(backend="numpy")
    a_jx = MicroAllocator(backend="jax")
    for t in range(3):
        arrs = _random_tasks(rng, n_tasks)
        obs = _obs(cs, t)
        out_np = a_np._assign_core(obs, 0, **arrs)
        out_jx = a_jx._assign_core(obs, 0, **arrs)
        np.testing.assert_array_equal(out_np, out_jx,
                                      err_msg=f"slot {t} diverged")
    # the carried ring buffers agree too (uids are backend-local)
    s_np, s_jx = a_np.locality_state(0), a_jx.locality_state(0)
    if s_np is not None and s_jx is not None:
        np.testing.assert_array_equal(s_np.mids, s_jx.mids)
        np.testing.assert_array_equal(s_np.slots, s_jx.slots)
        np.testing.assert_array_equal(s_np.count, s_jx.count)
        np.testing.assert_allclose(s_np.embeds, s_jx.embeds)


def test_scan_narrow_embed_slot_after_wide_history():
    """Regression: a slot whose tasks carry no embeddings (the object
    path builds (N, 1) embeds then) must scan cleanly against a ring
    carrying 8-dim history, and still match the numpy walk."""
    cs, rng = _random_world(8, 17)
    a_np = MicroAllocator(backend="numpy")
    a_jx = MicroAllocator(backend="jax")
    wide = _random_tasks(rng, 10, edim=8)
    narrow = _random_tasks(rng, 7, edim=1)
    narrow["embeds"][:] = 0.0
    narrow["has_embed"][:] = False
    narrow["norms"][:] = 0.0
    for t, arrs in enumerate((wide, narrow, wide)):
        obs = _obs(cs, t)
        np.testing.assert_array_equal(a_np._assign_core(obs, 0, **arrs),
                                      a_jx._assign_core(obs, 0, **arrs),
                                      err_msg=f"slot {t}")


def test_scan_zero_tasks():
    cs, rng = _random_world(6, 3)
    a = MicroAllocator(backend="jax")
    arrs = _random_tasks(rng, 0)
    out = a._assign_core(_obs(cs, 0), 0, **arrs)
    assert out.shape == (0,)


def test_scan_all_inactive():
    cs, rng = _random_world(6, 4)
    cs.state[:] = OFF
    arrs = _random_tasks(rng, 9)
    for backend in ("numpy", "jax"):
        out = MicroAllocator(backend=backend)._assign_core(
            _obs(cs, 0), 0, **arrs)
        assert (out == -1).all(), backend


def test_scan_all_buffered():
    """Saturated queues (> 16 slots of backlog) buffer every task in both
    backends and leave the locality history untouched."""
    cs, rng = _random_world(6, 5)
    cs.state[:] = ACTIVE
    cs.queue_s[:] = 1e7
    arrs = _random_tasks(rng, 12)
    for backend in ("numpy", "jax"):
        alloc = MicroAllocator(backend=backend)
        out = alloc._assign_core(_obs(cs, 0), 0, **arrs)
        assert (out == -1).all(), backend
        lstate = alloc.locality_state(0)
        assert lstate is None or (lstate.count == 0).all()


def test_scan_engine_end_to_end_exact():
    """TORTA with micro_backend="jax" reproduces the numpy backend's full
    engine trajectory on a seeded multi-slot run."""
    topo = make_topology("abilene", seed=1)
    cluster = make_cluster(topo.n_regions, seed=3)
    rate = 0.3 * throughput_per_slot(cluster) / topo.n_regions
    wl = make_workload(8, topo.n_regions, seed=2, base_rate=rate)
    s_np = Engine(topo, copy.deepcopy(cluster), wl,
                  TortaScheduler(topo.n_regions, seed=0),
                  seed=0).run(8).summary()
    s_jx = Engine(topo, copy.deepcopy(cluster), wl,
                  TortaScheduler(topo.n_regions, seed=0,
                                 micro_backend="jax"),
                  seed=0).run(8).summary()
    for k in ("completed", "dropped", "model_switches"):
        assert s_np[k] == s_jx[k], k
    for k in ("power_cost_total", "mean_response_s", "mean_wait_s"):
        assert s_jx[k] == pytest.approx(s_np[k], rel=1e-9), k


def test_scan_fused_kernel_end_to_end():
    """The float32 fused-kernel static path stays within fp-noise of the
    float64 scan on a short horizon (same contract as the existing
    numpy-vs-pallas end-to-end check)."""
    topo = make_topology("abilene", seed=1)
    cluster = make_cluster(topo.n_regions, seed=3)
    rate = 0.3 * throughput_per_slot(cluster) / topo.n_regions
    wl = make_workload(5, topo.n_regions, seed=2, base_rate=rate)
    s_jx = Engine(topo, copy.deepcopy(cluster), wl,
                  TortaScheduler(topo.n_regions, seed=0,
                                 micro_backend="jax"),
                  seed=0).run(5).summary()
    s_fu = Engine(topo, copy.deepcopy(cluster), wl,
                  TortaScheduler(topo.n_regions, seed=0,
                                 micro_backend="jax",
                                 micro_fused_kernel=True,
                                 kernel_interpret=True),
                  seed=0).run(5).summary()
    assert s_fu["completed"] == pytest.approx(s_jx["completed"], rel=0.02)
    assert s_fu["mean_response_s"] == pytest.approx(
        s_jx["mean_response_s"], rel=0.1)


# ---------------------------------------------------------------------------
# fused kernel (interpret mode) vs oracles
# ---------------------------------------------------------------------------


def _fused_operands(seed=0, n=37, spr=21):
    cs = make_cluster_state(1, seed=seed, servers_per_region=(spr, spr + 1))
    rng = np.random.default_rng(seed)
    s = cs.n_servers
    cs.current_model[:] = rng.integers(-1, N_MODELS, s).astype(np.int16)
    cs.warm_models[:] = rng.integers(-1, N_MODELS,
                                     cs.warm_models.shape).astype(np.int16)
    arrs = _random_tasks(rng, n)
    tf = task_feature_arrays(arrs["kind_ids"], arrs["mem_t"])
    sf = server_feature_matrix(cs, cs.region_slice(0), 45.0)
    server_models = np.concatenate(
        [cs.current_model[:, None], cs.warm_models], axis=1)
    return cs, arrs, tf, sf, server_models


def test_fused_kernel_matches_ref():
    cs, arrs, tf, sf, server_models = _fused_operands()
    loc = np.random.default_rng(1).random((len(arrs["mids"]),
                                           cs.n_servers)).astype(np.float32)
    for locality in (None, loc):
        got = fused_score(tf.astype(np.float32), sf.astype(np.float32),
                          arrs["mids"].astype(np.float32),
                          server_models.astype(np.float32),
                          locality, interpret=True)
        want = fused_score_ref(tf.astype(np.float32),
                               sf.astype(np.float32),
                               arrs["mids"].astype(np.float32),
                               server_models.astype(np.float32), locality)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)


def test_fused_kernel_matches_numpy_composition():
    """fused kernel == hw_load_matrix_np + W_WARM * warm-matrix (the
    allocator's numpy static score), to float32 tolerance."""
    cs, arrs, tf, sf, server_models = _fused_operands(seed=7)
    mids = arrs["mids"]
    sl = cs.region_slice(0)
    warm_hit = cs.warm_hit_matrix(mids, sl)
    warm = np.where(cs.current_model[sl][None, :] == mids[:, None], 1.0,
                    np.where(warm_hit, 0.4, 0.0))
    want = hw_load_matrix_np(tf, sf) + W_WARM * warm
    got = np.asarray(fused_score(
        tf.astype(np.float32), sf.astype(np.float32),
        mids.astype(np.float32), server_models.astype(np.float32),
        interpret=True))
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)


def test_score_matrix_optional_locality():
    """locality=None equals an explicit zeros locality operand (the
    allocation the optional form avoids)."""
    _, arrs, tf, sf, _ = _fused_operands(seed=5, n=19, spr=9)
    tf32, sf32 = tf.astype(np.float32), sf.astype(np.float32)
    zeros = np.zeros((tf.shape[0], sf.shape[0]), np.float32)
    a = np.asarray(score_matrix(tf32, sf32, use_pallas=True,
                                interpret=True))
    b = np.asarray(score_matrix(tf32, sf32, zeros, use_pallas=True,
                                interpret=True))
    np.testing.assert_allclose(a, b, atol=1e-6)
    c = np.asarray(compat_score(tf32, sf32, interpret=True))
    np.testing.assert_allclose(a, c, atol=1e-6)


# ---------------------------------------------------------------------------
# LocalityState ring buffer vs legacy tracker
# ---------------------------------------------------------------------------


def _seed_tracker(rng, n_servers=5, edim=8, notes=30):
    tracker = LocalityTracker()
    for _ in range(notes):
        srv = int(rng.integers(0, n_servers))
        mid = int(rng.integers(-1, N_MODELS))
        embed = (rng.standard_normal(edim).astype(np.float32)
                 if rng.random() > 0.3 else None)
        tracker.note_fields((0, srv), mid, embed, int(rng.integers(0, 6)))
    return tracker


def test_locality_state_tracker_adapters_exact():
    """from_tracker/to_tracker are exact-equivalence: every server column
    matches ``LocalityTracker.locality_column`` bitwise, both ways."""
    rng = np.random.default_rng(11)
    tracker = _seed_tracker(rng)
    lstate = LocalityState.from_tracker(tracker, 0, 5)
    arrs = _random_tasks(rng, 17)
    t = 7
    for s in range(5):
        want = tracker.locality_column((0, s), arrs["mids"],
                                       arrs["embeds"], arrs["norms"],
                                       arrs["has_embed"], t)
        got = lstate.column(s, arrs["mids"], arrs["embeds"],
                            arrs["norms"], arrs["has_embed"], t)
        np.testing.assert_array_equal(got, want, err_msg=f"server {s}")
    back = lstate.to_tracker(0)
    for s in range(5):
        want = tracker.locality_column((0, s), arrs["mids"],
                                       arrs["embeds"], arrs["norms"],
                                       arrs["has_embed"], t)
        got = back.locality_column((0, s), arrs["mids"], arrs["embeds"],
                                   arrs["norms"], arrs["has_embed"], t)
        np.testing.assert_array_equal(got, want, err_msg=f"server {s}")


def test_locality_state_note_matches_tracker():
    """Interleaved notes keep the ring bitwise-equal to the tracker list
    (newest-first order, keep-truncation, norm recompute)."""
    rng = np.random.default_rng(23)
    tracker = LocalityTracker()
    lstate = LocalityState.empty(3, 4, 8)
    uid = 0
    for i in range(20):
        srv = int(rng.integers(0, 3))
        mid = int(rng.integers(0, N_MODELS))
        embed = (rng.standard_normal(8).astype(np.float32)
                 if rng.random() > 0.4 else None)
        tracker.note_fields((0, srv), mid, embed, i)
        uid += 1
        lstate.note(srv, mid, embed, i, uid)
    arrs = _random_tasks(rng, 9)
    for s in range(3):
        want = tracker.locality_column((0, s), arrs["mids"],
                                       arrs["embeds"], arrs["norms"],
                                       arrs["has_embed"], 21)
        got = lstate.column(s, arrs["mids"], arrs["embeds"],
                            arrs["norms"], arrs["has_embed"], 21)
        np.testing.assert_array_equal(got, want)
        assert int(lstate.count[s]) == len(tracker.recent.get((0, s), ()))


def test_recent_task_negative_mid():
    """Regression: history entries noted with mid < 0 store model=None
    (the field is Optional[str]) and score a zero model-match term."""
    tracker = LocalityTracker()
    tracker.note_fields((0, 0), -1, None, 0)
    rt = tracker.recent[(0, 0)][0]
    assert rt.model is None and rt.mid == -1
    assert "Optional" in str(RecentTask.__dataclass_fields__["model"].type)
    mids = np.array([0, 1], np.int16)
    col = tracker.locality_column((0, 0), mids, np.zeros((2, 8),
                                                         np.float32),
                                  np.zeros(2), np.zeros(2, bool), 1)
    np.testing.assert_array_equal(col, 0.0)
    # the array state represents the same entry distinctly from EMPTY pads
    lstate = LocalityState.from_tracker(tracker, 0, 1)
    assert lstate.mids[0, 0] == -1 and lstate.mids[0, 1] == EMPTY
    assert int(lstate.count[0]) == 1


def test_locality_state_grow_embed_dim():
    lstate = LocalityState.empty(2, 4, 1)
    lstate.note(0, 3, np.ones(1, np.float32), 0, 1)
    grown = lstate.grown(8)
    assert grown.embed_dim == 8
    assert grown.mids[0, 0] == 3
    np.testing.assert_array_equal(grown.embeds[0, 0],
                                  [1, 0, 0, 0, 0, 0, 0, 0])
