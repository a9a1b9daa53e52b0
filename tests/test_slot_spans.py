"""Span and counter coverage of the fused slot step: every phase of an
engine slot runs inside a span, each under its parent and opened once per
slot; the apply paths' row counts add up to the assigned rows; the fused
scan's upload and the engine step's packed buffers are counted in
transfers and bytes; and compiles carry the jitted programs' names."""
import dataclasses
import inspect

import jax
import numpy as np
import pytest

from repro.core import micro_jax
from repro.core.torta import TortaScheduler
from repro.obs import Tracer
from repro.obs import runtime as obs_rt
from repro.sim import Engine, engine_jax, make_cluster_state
from repro.sim.cluster import throughput_per_slot
from repro.sim.engine_jax import JaxStepper
from repro.workload import make_source
from test_obs import _topology

R = 5
SLOTS = 8
REPLAY_SLOT = 3

# every span of the fused slot, with the span it opens under (None = top)
PARENT = {
    "engine.intake": None,
    "schedule.batch": None,
    "engine.activate": None,
    "engine.apply": None,
    "engine.buffer": None,
    "engine.slot_close": None,
    "engine.observe": None,
    "macro.phase1": "schedule.batch",
    "macro.predict": "macro.phase1",
    "macro.ot": "macro.phase1",
    "macro.sample": "schedule.batch",
    "micro.activation": "schedule.batch",
    "micro.assign": "schedule.batch",
    "micro.pack": "micro.assign",
    "micro.upload": "micro.assign",
    "micro.host_sync": "micro.assign",
    "engine.apply.single": "engine.apply",
    "engine.apply.conflict": "engine.apply",
    "engine.apply.replay": "engine.apply",
    "engine.close_step": "engine.slot_close",
}
# spans whose path does not run in every slot
SOMETIMES = {"engine.apply.single", "engine.apply.conflict",
             "engine.apply.replay"}


class ReplayAt:
    """TORTA, except that at one slot its Eq 6 targets ask every region
    down to one server: servers it just targeted go inactive before the
    apply, which then replays the slot per task."""

    name = "TORTA"
    supports_batch = True

    def __init__(self, slot):
        self.inner = TortaScheduler(R, seed=0, micro_backend="fused")
        self.slot = slot

    def reset(self):
        self.inner.reset()

    def schedule_batch(self, obs, batch):
        decision = self.inner.schedule_batch(obs, batch)
        if obs.t == self.slot:
            decision = dataclasses.replace(
                decision, activation=np.ones(R, np.int64))
        return decision


def _engine(obs_spec, scheduler=None):
    topo = _topology(R, seed=1)
    cs = make_cluster_state(R, seed=3, servers_per_region=(10, 11))
    rate = 0.4 * throughput_per_slot(cs) / R
    src = make_source("diurnal", SLOTS, R, seed=2, base_rate=rate)
    return Engine(topo, cs, src, scheduler or ReplayAt(REPLAY_SLOT), seed=4,
                  step_backend="jax", obs=obs_spec)


@pytest.fixture(scope="module")
def traced():
    eng = _engine("trace")
    eng.run(SLOTS)
    return eng


def test_each_phase_opens_once_per_slot_under_its_parent(traced):
    records = traced.obs.tracer.records
    assert {r.name for r in records} == set(PARENT)
    for rec in records:
        parent = records[rec.parent].name if rec.parent >= 0 else None
        assert parent == PARENT[rec.name], rec.name
        if parent is not None:
            assert records[rec.parent].slot == rec.slot
    for t in range(SLOTS):
        names = [r.name for r in records if r.slot == t]
        for name in PARENT:
            n = names.count(name)
            assert n <= 1 if name in SOMETIMES else n == 1, (t, name, n)
    replayed = {r.slot for r in records if r.name == "engine.apply.replay"}
    assert replayed == {REPLAY_SLOT}


def test_top_level_spans_tile_the_slot(traced):
    top = [r for r in traced.obs.tracer.records if r.parent < 0]
    starts = [min(r.t_start for r in top if r.slot == t)
              for t in range(SLOTS)]
    for t in range(SLOTS - 1):
        inside = sum(r.duration_s for r in top if r.slot == t)
        assert inside >= 0.98 * (starts[t + 1] - starts[t]), t


def test_apply_rows_add_up_to_the_assigned_rows(monkeypatch):
    """The rows of the three apply paths add up to the assigned rows: the
    jitted single-task apply's (counted where it is called), the conflict
    walk's (``engine.fallback.same_server_conflict``) and the replay's
    (``engine.fallback.inactive_target_rows``)."""
    single = []
    apply = JaxStepper.apply_single_rows

    def spy(self, gs, mids, work_raw):
        single.append(len(gs))
        return apply(self, gs, mids, work_raw)

    monkeypatch.setattr(JaxStepper, "apply_single_rows", spy)
    eng = _engine(None)
    eng.run(SLOTS)
    c = eng.obs.counters
    rows = {"single": sum(single),
            "conflict": c.get("engine.fallback.same_server_conflict"),
            "replay": c.get("engine.fallback.inactive_target_rows")}
    assert all(n > 0 for n in rows.values()), rows
    assert sum(rows.values()) == c.get("engine.tasks.assigned")
    assert c.get("engine.fallback.inactive_target_slot") == 1


def test_self_time_and_slot_tags():
    clock = iter(np.arange(0.0, 100.0)).__next__
    tr = Tracer(clock=clock)
    tr.slot = 7
    with tr.span("outer"):          # t 0 .. 5
        with tr.span("inner"):      # t 1 .. 2
            pass
        with tr.span("inner"):      # t 3 .. 4
            pass
    rows = {r["name"]: r for r in tr.summary()}
    assert rows["outer"]["total_s"] == 5.0
    assert rows["outer"]["self_s"] == 3.0
    assert rows["inner"]["self_s"] == rows["inner"]["total_s"] == 2.0
    assert {r.slot for r in tr.records} == {7}


def test_micro_upload_bytes_are_the_scan_operands(monkeypatch):
    """Per dispatch, ``device.h2d_bytes{layer=micro}`` grows by the
    ``nbytes`` of every operand the scan takes from the host (all but the
    device-resident rings) and ``device.transfers{dir=h2d,layer=micro}``
    by their number; ``dir=d2h`` by the one read-back of the
    assignments."""
    scan = micro_jax._scan_assign_multi
    names = list(inspect.signature(
        micro_jax._scan_assign_multi_impl).parameters)
    rings = {names.index(n) for n in ("l_mids", "l_slots", "l_emb",
                                      "l_nrm")}
    seen = []

    def spy(*operands):
        host = [a for i, a in enumerate(operands) if i not in rings]
        out = scan(*operands)
        c = obs_rt.active().counters
        seen.append((sum(a.nbytes for a in host), len(host),
                     c.get("device.h2d_bytes", layer="micro"),
                     c.get("device.transfers", dir="h2d", layer="micro"),
                     c.get("device.transfers", dir="d2h", layer="micro")))
        return out

    monkeypatch.setattr(micro_jax, "_scan_assign_multi", spy)
    eng = _engine(None)
    eng.run(3)
    assert len(seen) == 3
    assert [s[2] for s in seen] == np.cumsum([s[0] for s in seen]).tolist()
    assert [s[3] for s in seen] == np.cumsum([s[1] for s in seen]).tolist()
    # the read-back is counted after the dispatch returns
    assert [s[4] for s in seen] == [0, 1, 2]
    c = eng.obs.counters
    assert c.get("device.transfers", dir="d2h", layer="micro") == 3
    for layer in ("macro", "engine"):
        assert c.get("device.h2d_bytes", layer=layer) > 0
        assert c.get("device.transfers", dir="h2d", layer=layer) > 0
        assert c.get("device.transfers", dir="d2h", layer=layer) > 0


def test_engine_dispatches_move_two_packed_buffers_each_way(monkeypatch):
    """Per engine dispatch, ``device.transfers{layer=engine}`` grows by 2
    up and 2 down (one float64 and one int32 buffer each way) and
    ``device.h2d_bytes{layer=engine}`` by the packed buffers' ``nbytes``;
    the static triple goes up once, before the run's first dispatch."""
    seen = []

    def spy_on(name):
        kernel = getattr(engine_jax, name)

        def spy(statics, floats, ints):
            c = obs_rt.active().counters
            seen.append((name, floats.nbytes + ints.nbytes,
                         sum(a.nbytes for a in statics),
                         c.get("device.transfers", dir="h2d", layer="engine"),
                         c.get("device.transfers", dir="d2h", layer="engine"),
                         c.get("device.h2d_bytes", layer="engine")))
            return kernel(statics, floats, ints)

        monkeypatch.setattr(engine_jax, name, spy)

    for name in ("warm_step", "apply_single", "close_step"):
        spy_on(name)
    eng = _engine(None)
    eng.run(SLOTS)
    names = [s[0] for s in seen]
    assert names.count("close_step") == SLOTS and "apply_single" in names
    # the uploads are counted before the dispatch, the read-back after it
    n = np.arange(1, len(seen) + 1)
    assert [s[3] for s in seen] == (3 + 2 * n).tolist()
    assert [s[4] for s in seen] == (2 * (n - 1)).tolist()
    assert [s[5] for s in seen] == (
        seen[0][2] + np.cumsum([s[1] for s in seen])).tolist()
    c = eng.obs.counters
    assert c.get("device.transfers", dir="d2h", layer="engine") == 2 * n[-1]


def test_cold_run_counts_compiles_by_program_name():
    jax.clear_caches()
    eng = _engine(None)
    eng.run(3)
    compiled = {k: v for k, v in eng.obs.counters.as_dict().items()
                if k.startswith("device.compiles")}
    programs = {k[len("device.compiles{program="):-1] for k in compiled}
    assert {"micro_scan_all", "engine_apply_single",
            "engine_close_step"} <= programs, programs
    assert not [p for p in programs if "_unknown" in p], programs
    # compiles outside an active run are not counted anywhere
    assert obs_rt.active() is None
    jax.jit(lambda x: x + 1.0)(np.arange(3.0))
    assert eng.obs.counters.as_dict() == eng.run_report.counters


def test_program_names():
    assert obs_rt.program_name("jit(micro_scan_all)") == "micro_scan_all"
    assert obs_rt.program_name("jit(<unknown>)") == "_unknown"
