"""The system under test, driven the way a user drives it.

The window runs the engine's slot loop, ``Engine.run`` -> ``_run_loop``,
with ``TortaScheduler(R, micro_backend="fused")`` and
``Engine(..., step_backend="jax")``.  The program receives only the
fleet, topology and traffic that ``harness.world`` generates, and the
configuration's numbers as ``harness.handover`` hands them over; the
benchmark wraps two of its seams:

* the demand source (``SlotSource``): it stamps the host clock at each
  request for a slot's arrivals, opens the window at slot ``s0`` and
  closes it at the first request past ``--seconds`` by raising
  ``WindowClosed`` out of the loop;
* the scheduler (``TimedScheduler``): it times each ``schedule_batch``
  call and keeps what the output check needs (the batch, the returned
  decision and the routing matrix ``A_t`` the call left behind).

Before the engine is built, ``warm_shapes`` runs throwaway engines over
a sweep of loads and of single-task servers, so that the window compiles
nothing.  Traffic is
generated before the window for ``horizon_factor`` times as many slots as
the fastest warm-up slot predicts; a run that reaches the end of it
fails.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from harness import handover, world


class WindowClosed(Exception):
    """Raised out of the engine loop when the measured window ends."""


class OutOfTraffic(RuntimeError):
    """The window reached the last generated slot."""


# ------------------------------------------------------ program's types


def program_world(cfg: dict, fleet: world.Fleet, latency, graph):
    """The benchmark's fleet and topology as the program's types.  Raises
    ``handover.Refused`` where the configuration states what the program
    would not run (see ``harness.handover``)."""
    from repro.sim.state import ClusterState
    from repro.sim.topology import Topology

    handover.refuse(cfg)
    state = ClusterState(
        **{f.name: getattr(fleet, f.name).copy()
           for f in dataclasses.fields(fleet)},
        **fleet.dynamic_columns(world.warm_slots(cfg)))
    topo = Topology(cfg["topology"]["name"], fleet.n_regions,
                    float(cfg["topology"]["bandwidth_gbps"]),
                    latency.copy(), graph)
    return topo, state


def task_batch(slot: world.Slot):
    from repro.workload.batch import TaskBatch
    return TaskBatch(ids=slot.ids, origin=slot.origin,
                     model_idx=slot.model_idx, kind_id=slot.kind_id,
                     work_s=slot.work_s, mem_gb=slot.mem_gb,
                     deadline_slot=slot.deadline_slot,
                     arrival_slot=slot.arrival_slot, embeds=slot.embeds)


def make_scheduler(cfg: dict):
    from repro.core.torta import TortaScheduler
    tc = cfg["torta"]
    return TortaScheduler(cfg["topology"]["nodes"], seed=tc["scheduler_seed"],
                          eta=tc["eta"], sigma=tc["sigma"],
                          headroom=tc["headroom"],
                          micro_backend=tc["micro_backend"])


# --------------------------------------------------------- the two seams


@dataclasses.dataclass
class Call:
    """One ``schedule_batch`` call as the output check reads it."""

    t: int
    seconds: float
    batch: object
    region: np.ndarray
    server: np.ndarray
    activation: np.ndarray
    routing: np.ndarray


class TimedScheduler:
    """The benchmark's wrapper around the scheduler: host time of each
    ``schedule_batch`` call, and what the output check reads."""

    def __init__(self, inner):
        self.inner = inner
        self.name = getattr(inner, "name", type(inner).__name__)
        self.supports_batch = True
        self.calls: List[Call] = []

    def reset(self) -> None:
        self.inner.reset()

    def schedule_batch(self, obs, batch):
        t0 = time.perf_counter()
        decision = self.inner.schedule_batch(obs, batch)
        seconds = time.perf_counter() - t0
        self.calls.append(Call(
            t=int(obs.t), seconds=seconds, batch=batch,
            region=decision.region, server=decision.server,
            activation=decision.activation,
            routing=self.inner.macro.a_prev.copy()))
        return decision


class SlotSource:
    """Demand source with the window's clock.

    ``stamps[t]`` is the host time of the engine's request for slot
    ``t``'s arrivals.  Slots ``[0, s0)`` are the warm-up.  At the request
    for slot ``s0`` it calls ``on_open`` (traffic generation, shape
    warm-up, profiler start) and then opens the window; the first later
    request ``seconds`` or more after the opening raises
    ``WindowClosed``."""

    def __init__(self, n_regions: int, s0: int, seconds: float,
                 batches: List, on_open: Callable[[], None]):
        self.n_regions = n_regions
        self.s0 = s0
        self.seconds = seconds
        self.batches = batches
        self.on_open = on_open
        self.stamps: List[float] = []
        self.opened: Optional[float] = None
        self.closed: Optional[float] = None
        self.end_slot: Optional[int] = None

    @property
    def n_slots(self) -> int:
        return 1 << 30

    def slot_batch(self, t: int):
        if t == self.s0:
            self.on_open()
        now = time.perf_counter()
        if t == self.s0:
            self.opened = now
        elif t > self.s0 and now - self.opened >= self.seconds:
            self.closed, self.end_slot = now, t
            raise WindowClosed()
        if t >= len(self.batches):
            raise OutOfTraffic(
                f"the window reached slot {t}, the last generated one")
        self.stamps.append(now)
        return self.batches[t]


# ------------------------------------------------------------- warm-up


class ListSource:
    """Demand source over a fixed list of batches."""

    def __init__(self, n_regions: int, batches: List):
        self.n_regions = n_regions
        self.batches = batches

    @property
    def n_slots(self) -> int:
        return len(self.batches)

    def slot_batch(self, t: int):
        return self.batches[t]


def load_sweep(traffic: world.Traffic) -> List[world.Slot]:
    """One slot at each of the traffic file's ``shape_warmup_loads`` times
    the mean slot, its tasks taken in turn from the warm-up slots (which
    every seed shares), rows grouped by origin."""
    pool = [traffic.slot(t) for t in range(traffic.s0)]
    cols = ("origin", "model_idx", "kind_id", "work_s", "mem_gb", "embeds")
    rows = {k: np.concatenate([getattr(s, k) for s in pool]) for k in cols}
    ahead = np.concatenate([s.deadline_slot - s.arrival_slot for s in pool])
    out = []
    for t, load in enumerate(traffic.spec["shape_warmup_loads"]):
        n = max(1, int(round(load * traffic.total_rate)))
        take = np.arange(n) % ahead.size
        take = take[np.argsort(rows["origin"][take], kind="stable")]
        out.append(world.Slot(
            t=t, ids=(np.int64(t) << np.int64(32)) + np.arange(n),
            deadline_slot=t + ahead[take],
            arrival_slot=np.full(n, t, np.int64),
            **{k: v[take] for k, v in rows.items()}))
    return out


class SpreadScheduler:
    """Places a slot's ``k`` tasks on ``k`` distinct servers (server
    ``i`` of the fleet, region-major, takes row ``i``), so that the
    engine's apply sees exactly ``k`` single-task servers."""

    name = "spread"
    supports_batch = True

    def __init__(self, region_ptr: np.ndarray):
        self.ptr = region_ptr

    def reset(self) -> None:
        pass

    def schedule_batch(self, obs, batch):
        from repro.api import BatchDecision
        g = np.arange(len(batch))
        region = np.searchsorted(self.ptr, g, side="right") - 1
        return BatchDecision(region=region, server=g - self.ptr[region])


def warm_shapes(cfg: dict, traffic: world.Traffic, fleet: world.Fleet,
                latency, graph) -> int:
    """Compile every shape the window can reach before it opens, through
    two throwaway engines built as the window's engine is: one runs the
    scheduler over ``load_sweep``'s slots, from a few tasks to several
    times the mean slot; the other places 1, 2, ... tasks, up to one per
    server, on distinct servers, so that the apply meets every count of
    single-task servers.  Only the program's public entry points are
    used; the window's engine and scheduler are built afresh afterwards.
    Returns the slots run."""
    from repro.sim import Engine

    sweep = load_sweep(traffic)
    pool = sweep[-1]
    spread = [world.Slot(t=k, **{
        f.name: getattr(pool, f.name)[:k + 1]
        for f in dataclasses.fields(pool) if f.name != "t"})
        for k in range(min(fleet.n_servers, len(pool)))]
    for k, s in enumerate(spread):
        s.ids = (np.int64(k) << np.int64(32)) + np.arange(k + 1)
        s.deadline_slot = s.deadline_slot - s.arrival_slot + k
        s.arrival_slot = np.full(k + 1, k, np.int64)
    ran = 0
    for slots, scheduler in ((sweep, make_scheduler(cfg)),
                             (spread, SpreadScheduler(fleet.region_ptr))):
        topo, state = program_world(cfg, fleet, latency, graph)
        batches = [task_batch(s) for s in slots]
        engine = Engine(topo, state, ListSource(fleet.n_regions, batches),
                        scheduler, slot_seconds=cfg["slot_seconds"],
                        drop_after_slots=cfg["drop_after_slots"],
                        step_backend=cfg["torta"]["step_backend"],
                        obs=False)
        engine.run(len(batches))
        ran += len(batches)
    return ran


# ---------------------------------------------------------------- result


@dataclasses.dataclass
class ProgramRun:
    """What one run of the program leaves for the metrics and the check."""

    s0: int
    end_slot: int
    stamps: List[float]
    opened: float
    closed: float
    calls: List[Call]
    slots: List[world.Slot]
    metrics: object
    final_state: object
    obs: object
    window_counters: Dict[str, int]


def counters_dict(obs) -> Dict[str, int]:
    if obs is None or obs.counters is None:
        return {}
    return dict(obs.counters.as_dict())


def run_program(cfg: dict, traffic: world.Traffic, fleet: world.Fleet,
                latency, graph, *, seconds: float, obs_spec,
                on_open_extra: Callable[[], None],
                log: Callable[[str], None]) -> ProgramRun:
    """Build the engine as a user builds it, run the warm-up slots, open
    the window and run until it closes."""
    from repro.sim import Engine

    spec = traffic.spec
    s0 = int(cfg["warmup_slots"])
    t_sweep = time.perf_counter()
    swept = warm_shapes(cfg, traffic, fleet, latency, graph)
    log(f"shape warm-up: {swept} slots of throwaway engines in "
        f"{time.perf_counter() - t_sweep:.3f} s")
    topo, state = program_world(cfg, fleet, latency, graph)
    slots: List[world.Slot] = [traffic.slot(t) for t in range(s0 + 1)]
    batches = [task_batch(s) for s in slots]
    sched = TimedScheduler(make_scheduler(cfg))
    base: Dict[str, int] = {}

    def on_open():
        # the fastest warm-up slot: later slots compile nothing
        steps = np.diff(src.stamps[1:])
        pace = float(steps.min()) if steps.size else 1.0
        horizon = s0 + 16 + int(np.ceil(spec["horizon_factor"] * seconds
                                        / max(pace, 1e-4)))
        t_gen = time.perf_counter()
        for t in range(len(slots), horizon):
            slots.append(traffic.slot(t))
            batches.append(task_batch(slots[-1]))
        log(f"warm-up: {s0} slots, the fastest took {pace!r} s; traffic "
            f"generated to slot {horizon} in "
            f"{time.perf_counter() - t_gen:.3f} s")
        on_open_extra()
        base.update(counters_dict(engine.obs))

    src = SlotSource(fleet.n_regions, s0, seconds, batches, on_open)
    engine = Engine(topo, state, src, sched, slot_seconds=cfg["slot_seconds"],
                    drop_after_slots=cfg["drop_after_slots"],
                    step_backend=cfg["torta"]["step_backend"], obs=obs_spec)
    try:
        engine.run(1 << 30)
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the engine loop ended before the window closed")
    after = counters_dict(engine.obs)
    window_counters = {k: v - base.get(k, 0) for k, v in after.items()
                       if v - base.get(k, 0)}
    return ProgramRun(
        s0=s0, end_slot=src.end_slot, stamps=src.stamps, opened=src.opened,
        closed=src.closed, calls=sched.calls, slots=slots,
        metrics=engine.metrics, final_state=engine.state, obs=engine.obs,
        window_counters=window_counters)
