"""The output check on the CPU at a tiny size: the accepted program
passes, and the control (the reference one step below the stated
precision, in the program's place) and each fault planted under the
timed path fail.

Faults (each patches the program for its extent):

* ``state_unchanged`` - the slot close returns the fleet's queues,
  utilisation and idle counters unchanged;
* ``half_batch`` - the scheduler's decision leaves the second half of
  every batch unplaced;
* ``answer_altered`` - in every slot one placed task's server is moved
  to the next server of its region.

The cells run on one chip, so "the exchange between chips left out" is
not a fault they can have.
"""
import contextlib

import bench_testkit as kit
import numpy as np
import pytest

from harness import check, control

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kit.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def fewer_scored_tasks(monkeypatch):
    # the tiny cell offers ~100 tasks per slot: score a few thousand
    monkeypatch.setattr(check, "CHECK_TASKS", 3000)


def test_program_passes(root):
    cfg, fleet, latency, run = kit.run_tiny(root, seed=2 ** 31 + 11)
    assert run.end_slot > run.s0
    readings = check.replay(cfg, fleet, latency, run, seed=5)
    assert readings.correct, readings.lines()
    assert readings.checked_tasks > 0
    assert readings.values["route_gap"] < 1e-5


def test_control_fails(root):
    cfg, fleet, latency, _, traffic = kit.world_of(root, kit.TINY, 9)
    run = control.run_control(cfg, traffic, fleet, latency)
    readings = check.replay(cfg, fleet, latency, run, seed=9)
    assert not readings.correct
    v, lim = readings.values, check.LIMITS
    assert v["route_gap"] > 3 * lim["route_gap"]
    assert v["outcome_rel"] > 3 * lim["outcome_rel"]


def test_a_fault_outside_the_scored_slots_is_caught(root):
    """Half of one unscored window slot left unplaced shows in
    ``eligibility_faults``, which reads every slot."""
    cfg, fleet, latency, run = kit.run_tiny(root, seed=2 ** 31 + 17)
    sizes = {t: len(run.slots[t]) for t in range(run.s0, run.end_slot)}
    scored = set(check.sample_slots(17, run.s0, run.end_slot, sizes))
    t = next(t for t in range(run.s0, run.end_slot) if t not in scored)
    call = next(c for c in run.calls if c.t == t)
    half = len(call.region) // 2
    call.region = np.array(call.region, copy=True)
    call.server = np.array(call.server, copy=True)
    call.region[half:] = -1
    call.server[half:] = -1
    readings = check.replay(cfg, fleet, latency, run, seed=17)
    assert readings.values["eligibility_faults"] > 0, readings.lines()
    assert t not in readings.checked_slots


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_fails(root, fault):
    with planted(fault):
        cfg, fleet, latency, run = kit.run_tiny(root, seed=13, seconds=0.5)
    readings = check.replay(cfg, fleet, latency, run, seed=13)
    assert not readings.correct, readings.lines()


@contextlib.contextmanager
def _patched(owner, name: str, make):
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def _altered_decision(orig, alter):
    from repro.api import BatchDecision

    def schedule_batch(self, obs, batch):
        d = orig(self, obs, batch)
        region = np.array(d.region, copy=True)
        server = np.array(d.server, copy=True)
        alter(obs, region, server)
        return BatchDecision(region=region, server=server,
                             activation=d.activation)
    return schedule_batch


def _half(obs, region, server):
    h = len(region) // 2
    region[h:] = -1
    server[h:] = -1


def _one_moved(obs, region, server):
    placed = np.flatnonzero(region >= 0)
    if placed.size:
        i = placed[0]
        size = int(np.diff(obs.state.region_ptr)[region[i]])
        server[i] = (server[i] + 1) % size


def _frozen_close(orig):
    def close_slot(self, slot_s):
        keep = {k: getattr(self.state, k).copy()
                for k in ("queue_s", "util", "idle_slots")}
        out = orig(self, slot_s)
        for k, v in keep.items():
            getattr(self.state, k)[...] = v
        return out
    return close_slot


@contextlib.contextmanager
def planted(name: str):
    from repro.core.torta import TortaScheduler
    from repro.sim.engine_jax import JaxStepper

    if name == "state_unchanged":
        ctx = _patched(JaxStepper, "close_slot", _frozen_close)
    elif name == "half_batch":
        ctx = _patched(TortaScheduler, "schedule_batch",
                       lambda o: _altered_decision(o, _half))
    elif name == "answer_altered":
        ctx = _patched(TortaScheduler, "schedule_batch",
                       lambda o: _altered_decision(o, _one_moved))
    else:
        raise KeyError(f"unknown fault {name!r}; faults: {FAULTS}")
    with ctx:
        yield
