"""The control: the plain reference (the one the configuration names, see
``manifest.reference_class``) put in the program's place, computed
one step below the configuration's stated precision (float32 for its
float64 layers, bfloat16 for its float32 Sinkhorn and locality dots).

``run_control`` drives the same slots a run drives and leaves the same
record (``harness.program.ProgramRun``), so ``check.replay`` judges it as
it judges the program.  It must come out not correct.
"""
from __future__ import annotations

import dataclasses
import math
import types
from typing import Dict, List

import numpy as np

from harness import check, program, world
from harness.manifest import reference_class
from harness.reference import Precision


@dataclasses.dataclass
class _Metrics:
    response_times: List[float] = dataclasses.field(default_factory=list)
    completion_slots: List[int] = dataclasses.field(default_factory=list)
    switch_count_by_slot: List[int] = dataclasses.field(default_factory=list)
    power_cost_by_slot: List[float] = dataclasses.field(default_factory=list)
    drops_by_slot: Dict[int, int] = dataclasses.field(default_factory=dict)


def window_slots(traffic: world.Traffic) -> int:
    """Enough window slots for the check to score ``CHECK_TASKS``."""
    return int(math.ceil(check.CHECK_TASKS / traffic.total_rate)) + 1


def run_control(cfg: dict, traffic: world.Traffic, fleet: world.Fleet,
                latency, precision: Precision = None,
                root=None) -> program.ProgramRun:
    precision = precision or Precision.below_stated()
    ref = reference_class(cfg, root)(cfg, fleet, latency, precision)
    s0 = int(cfg["warmup_slots"])
    end = s0 + window_slots(traffic)
    slots = [traffic.slot(t) for t in range(end + 1)]
    calls: List[program.Call] = []
    m = _Metrics()
    for t in range(end):
        batch = ref.start_slot(t, slots[t])
        obs = ref.observe(batch)
        demand = np.bincount(batch.origin, minlength=ref.r).astype(float)
        a, predicted = ref.route(obs, demand, ref.a_prev)
        ref.a_prev = a
        region = ref.sample_regions(a, batch, obs)
        act = ref.activation(a, demand, predicted, obs)
        server = ref.phase2(t, batch, region, forced=None,
                            score=True)["server"]
        decided = np.where(server >= 0, region, -1)
        calls.append(program.Call(
            t=t, seconds=0.0, batch=batch, region=decided, server=server,
            activation=act, routing=a))
        ref.apply_activation(act)
        resp, n_sw, energy, assigned = ref.apply(t, batch, decided,
                                                    server)
        drops = ref.settle(t, batch, assigned)
        m.response_times.extend(resp)
        m.completion_slots.extend([t] * len(resp))
        m.switch_count_by_slot.append(n_sw)
        if drops:
            m.drops_by_slot[t] = drops
        m.power_cost_by_slot.append(ref.close(energy))
    ref.start_slot(end, slots[end])
    final = types.SimpleNamespace(queue_s=np.asarray(ref.queue, np.float64),
                                  state=ref.state.copy(),
                                  current_model=np.asarray(ref.current))
    return program.ProgramRun(
        s0=s0, end_slot=end, stamps=[], opened=0.0, closed=0.0, calls=calls,
        slots=slots, metrics=m, final_state=final, obs=None,
        window_counters={})
