"""Golden parity: array-native engine vs the frozen per-object reference,
plus numpy-vs-Pallas equivalence of the batched compat score."""
import copy

import numpy as np
import pytest

from repro.api import LegacySchedulerAdapter
from repro.core.micro import (LocalityTracker, batched_score_matrix, score,
                              server_feature_matrix, task_feature_matrix)
from repro.core.torta import TortaScheduler
from repro.sim import (Engine, make_cluster, make_cluster_state,
                       make_topology, make_workload)
from repro.sim.cluster import throughput_per_slot
from repro.sim.reference import (ReferenceEngine,
                                 ReferenceRoundRobinScheduler,
                                 make_reference_torta)
from repro.sim.state import ClusterState, model_id

PARITY_KEYS = ("completed", "dropped", "model_switches",
               "power_cost_total", "switch_cost_total",
               "mean_response_s", "mean_wait_s", "operational_overhead")


@pytest.fixture(scope="module")
def parity_world():
    topo = make_topology("abilene", seed=1)
    cluster = make_cluster(topo.n_regions, seed=3)
    rate = 0.3 * throughput_per_slot(cluster) / topo.n_regions
    wl = make_workload(20, topo.n_regions, seed=2, base_rate=rate)
    return topo, cluster, wl


@pytest.mark.parametrize("which", ["rr", "torta"])
def test_golden_parity(parity_world, which):
    """Same seeds -> same completions, drops, power cost, switch counts
    (fp tolerance) between the old-shape semantics and the array engine.

    The "rr" case drives the FROZEN reference RR through the unified
    engine via ``LegacySchedulerAdapter(obs_mode="cluster")``, so both
    sides run identical scheduler logic and any divergence isolates the
    engine's grouped whole-array apply.  The "torta" case additionally
    pins TORTA's native ``schedule_batch`` to the per-object oracle."""
    topo, cluster, wl = parity_world
    if which == "rr":
        ref_sched = ReferenceRoundRobinScheduler()
        new_sched = LegacySchedulerAdapter(ReferenceRoundRobinScheduler(),
                                           obs_mode="cluster")
    else:
        ref_sched = make_reference_torta(topo.n_regions, seed=0)
        new_sched = TortaScheduler(topo.n_regions, seed=0)
    s_ref = ReferenceEngine(topo, copy.deepcopy(cluster), wl, ref_sched,
                            seed=0).run().summary()
    s_new = Engine(topo, copy.deepcopy(cluster), wl, new_sched,
                   seed=0).run().summary()
    for k in PARITY_KEYS:
        assert s_new[k] == pytest.approx(s_ref[k], rel=1e-6), k


def test_state_roundtrip():
    cluster = make_cluster(5, seed=7)
    st = ClusterState.from_cluster(cluster)
    assert st.n_regions == 5
    assert st.n_servers == sum(len(r.servers) for r in cluster.regions)
    # region reductions match the object properties
    np.testing.assert_allclose(st.capacities(), cluster.capacities())
    np.testing.assert_allclose(st.power_prices(), cluster.power_prices())
    back = st.to_cluster()
    for reg_a, reg_b in zip(cluster.regions, back.regions):
        assert len(reg_a.servers) == len(reg_b.servers)
        for sa, sb in zip(reg_a.servers, reg_b.servers):
            assert sa.gpu == sb.gpu
            assert sa.capacity == pytest.approx(sb.capacity)
            assert sa.state == sb.state


def test_state_switch_cost_matches_server():
    st = make_cluster_state(3, seed=11)
    cluster = st.to_cluster()
    g = 0
    srv = cluster.regions[0].servers[0]
    for model in ("llama3-8b", "tinyllama-1.1b", "llama3-8b",
                  "qwen2.5-3b", "mixtral-8x7b", "llama3-8b"):
        assert st.switch_cost(g, model_id(model)) == pytest.approx(
            srv.switch_cost_s(model))
        vec = st.switch_cost_vec(model_id(model))
        assert vec[g] == pytest.approx(srv.switch_cost_s(model))
        st.note_model(g, model_id(model))
        srv.note_model(model)
    assert st.current_model[g] == model_id("llama3-8b")


def test_batched_score_matches_scalar():
    """The batched (N x S) matrix equals the scalar Eq 7-10 reference."""
    st = make_cluster_state(2, seed=5)
    cluster = st.to_cluster()
    wl = make_workload(2, 2, seed=6, base_rate=8.0)
    tasks = wl.tasks[0][:12]
    sl = st.region_slice(0)
    slot_s = 45.0
    tf = task_feature_matrix(tasks)
    sf = server_feature_matrix(st, sl, slot_s)
    loc = LocalityTracker()
    loc.note((0, 1), tasks[0], 0)
    loc.note((0, 1), tasks[-1], 0)
    embeds = np.stack([t.embed for t in tasks])
    norms = np.linalg.norm(embeds, axis=1)
    has = np.ones(len(tasks), bool)
    task_mids = np.array([model_id(t.model) for t in tasks], np.int16)
    loc_mat = np.stack([loc.locality_column((0, i), task_mids, embeds,
                                            norms, has, t=1)
                        for i in range(sl.stop - sl.start)], axis=1)
    got = batched_score_matrix(tf, sf, loc_mat, backend="numpy")
    for i, task in enumerate(tasks):
        for j, srv in enumerate(cluster.regions[0].servers):
            # scalar `score` adds the warm bonus on top of Eq 7-10; a fresh
            # cluster has no current/warm models, so it is 0 here and the
            # static matrix must match the scalar form (hw/load are exact in
            # float64; the locality embedding dot is float32-limited)
            want = score(task, srv, (0, j), 1, slot_s, loc)
            assert got[i, j] == pytest.approx(want, abs=1e-6), (i, j)


def test_compat_kernel_equivalence_scheduler_shapes():
    """numpy oracle vs Pallas compat_score at scheduler-realistic shapes."""
    st = make_cluster_state(4, seed=9, servers_per_region=(60, 61))
    wl = make_workload(1, 4, seed=10, base_rate=70.0)
    tasks = wl.tasks[0]
    assert len(tasks) >= 64
    rng = np.random.default_rng(0)
    for ridx in range(2):
        sl = st.region_slice(ridx)
        tf = task_feature_matrix(tasks)
        sf = server_feature_matrix(st, sl, 45.0)
        loc = rng.random((len(tasks), sl.stop - sl.start))
        a = batched_score_matrix(tf, sf, loc, backend="numpy")
        b = batched_score_matrix(tf, sf, loc, backend="pallas",
                                 interpret=True)
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)


def test_micro_backends_agree_end_to_end(parity_world):
    """numpy- and kernel-backed TORTA runs stay within fp-noise of each
    other on a short horizon (scores agree to ~1e-7, so trajectories can
    only diverge on near-exact ties)."""
    topo, cluster, wl = parity_world
    s_np = Engine(topo, copy.deepcopy(cluster), wl,
                  TortaScheduler(topo.n_regions, seed=0),
                  seed=0).run(6).summary()
    s_pl = Engine(topo, copy.deepcopy(cluster), wl,
                  TortaScheduler(topo.n_regions, seed=0,
                                 use_compat_kernel=True,
                                 kernel_interpret=True),
                  seed=0).run(6).summary()
    assert s_pl["completed"] == pytest.approx(s_np["completed"], rel=0.02)
    assert s_pl["mean_response_s"] == pytest.approx(
        s_np["mean_response_s"], rel=0.1)


def test_torta_reset_clears_run_state(parity_world):
    """reset() must not leak _sticky routing or prediction_log entries
    across repeated runs (repeated-run benchmarks depend on it)."""
    topo, cluster, wl = parity_world
    sched = TortaScheduler(topo.n_regions, seed=0, distribution="sticky")
    s1 = Engine(topo, copy.deepcopy(cluster), wl, sched, seed=0).run(8).summary()
    n_log = len(sched.prediction_log)
    assert n_log == 8 and sched._sticky
    s2 = Engine(topo, copy.deepcopy(cluster), wl, sched, seed=0).run(8).summary()
    assert len(sched.prediction_log) == 8          # not 16: reset cleared it
    for k in ("completed", "power_cost_total", "model_switches"):
        assert s1[k] == pytest.approx(s2[k], rel=1e-9), k
