"""The output check: the program's answers against the plain reference.

After the window has closed, ``replay`` walks every slot the run made,
warm-up and window alike, through the plain reference in check mode
(``harness.reference``, or the copy the configuration names):
for each slot the reference computes each layer's answer from the state
the program's earlier answers left, compares it with the program's, and
then applies the program's answer.  Numbers compared, each against its
limit (see ``LIMITS``; ``PERF.md`` gives the readings and the reasoning):

* ``route_gap`` - phase 1: the largest absolute difference between the
  program's routing matrix ``A_t`` and one smoothing step of the
  reference (float64 Sinkhorn) from the program's ``A_{t-1}``;
* ``region_mismatch`` - phase 1: placed tasks whose region differs from
  the reference's draw from the program's ``A_t`` (exact);
* ``activation_mismatch`` - phase 2: regions whose Eq 6 target differs
  from the reference's, given the program's ``A_t`` (exact);
* ``eligibility_faults`` - phase 2, on every slot: tasks placed on a
  server that is inactive, too small or over the queue cap at slot
  start, or buffered while a server of their region was active, fit them
  and stayed under the cap whatever the slot placed on it (exact);
* ``place_gap`` - phase 2: on a sample of window slots drawn from the
  seed, the largest amount by which the reference's Eq 7-10 score of the
  server the program chose lies below the reference's best (``inf``
  where the program buffers a task that has an eligible server, or
  places one where no server or not that one is eligible);
* ``outcome_rel`` - engine step: the largest relative difference of a
  task's response time, a slot's power cost, or a server's queue after
  the last slot;
* ``count_mismatch`` - engine step: slots whose batch of task ids,
  number of completions, model switches or drops differ, plus servers
  whose state code or current model differ after the last slot (exact).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np

from harness import world
from harness.manifest import reference_class
from harness.reference import Precision

# name: limit.  The reasons and the readings they were set from are in
# PERF.md ("Output check"); exact comparisons have the limit 0.
LIMITS: Dict[str, float] = {
    "route_gap": 2e-4,
    "region_mismatch": 0,
    "activation_mismatch": 0,
    "eligibility_faults": 0,
    "place_gap": 1e-5,
    "outcome_rel": 1e-9,
    "count_mismatch": 0,
}

# the sample of window slots whose phase 2 is scored: slots drawn from
# the seed until they hold this many tasks
CHECK_TASKS = 150_000


@dataclasses.dataclass
class Readings:
    values: Dict[str, float]
    checked_tasks: int
    checked_slots: List[int]

    @property
    def correct(self) -> bool:
        return all(self.values[k] <= LIMITS[k] for k in LIMITS)

    def lines(self) -> List[str]:
        return [f"{k} {self.values[k]!r} limit {LIMITS[k]!r}"
                for k in LIMITS]

    def as_json(self) -> Dict[str, Dict[str, float]]:
        return {k: {"value": _num(self.values[k]), "limit": LIMITS[k]}
                for k in LIMITS}


def _num(x: float):
    return x if math.isfinite(x) else str(x)


def sample_slots(seed: int, first: int, end: int,
                 sizes: Dict[int, int]) -> List[int]:
    """Window slots drawn from the seed until they hold ``CHECK_TASKS``
    tasks (all of them if the window holds fewer)."""
    rng = np.random.default_rng([int(seed), 17])
    out, tasks = [], 0
    for t in rng.permutation(np.arange(first, end)).tolist():
        if tasks >= CHECK_TASKS:
            break
        out.append(int(t))
        tasks += sizes[t]
    return sorted(out)


def _rel(a: np.ndarray, b: np.ndarray, floor: float) -> float:
    if a.size == 0:
        return 0.0
    d = np.abs(a - b) / np.maximum(np.abs(b), floor)
    d = np.where(np.isnan(d), np.inf, d)
    return float(d.max())


def per_slot_responses(metrics, n_slots: int) -> List[np.ndarray]:
    resp = np.asarray(metrics.response_times, np.float64)
    slots = np.asarray(metrics.completion_slots, np.int64)
    order = np.argsort(slots, kind="stable")
    bounds = np.searchsorted(slots[order], np.arange(n_slots + 1))
    return [resp[order[bounds[t]:bounds[t + 1]]] for t in range(n_slots)]


def replay(cfg: dict, fleet: world.Fleet, latency: np.ndarray, run,
           seed: int, log=print, precision: Precision = Precision(),
           root=None) -> Readings:
    """Replay the run through the reference the configuration names (see
    ``manifest.reference_class``; ``root`` is the checkout's) and read
    every number."""
    ref = reference_class(cfg, root)(cfg, fleet, latency, precision)
    end = run.end_slot
    calls = {c.t: c for c in run.calls}
    sizes = {t: len(run.slots[t]) for t in range(run.s0, end)}
    scored = set(sample_slots(seed, run.s0, end, sizes))
    m = run.metrics
    responses = per_slot_responses(m, end)
    drops_by_slot = m.drops_by_slot
    v = dict.fromkeys(LIMITS, 0.0)
    checked = 0
    a_prev = np.full((ref.r, ref.r), 1.0 / ref.r)
    for t in range(end):
        batch = ref.start_slot(t, run.slots[t])
        call = calls.get(t)
        if (call is None or len(call.batch) != len(batch)
                or not np.array_equal(call.batch.ids, batch.ids)):
            v["count_mismatch"] += 1
            log(f"check: slot {t}: the program's batch is not the "
                f"reference's; the replay stops here")
            v["outcome_rel"] = math.inf
            break
        obs = ref.observe(batch)
        demand = np.bincount(batch.origin, minlength=ref.r).astype(float)
        a_prog = np.asarray(call.routing, np.float64)
        a_ref, predicted = ref.route(obs, demand, a_prev)
        v["route_gap"] = max(v["route_gap"],
                             float(np.max(np.abs(a_prog - a_ref))))
        a_prev = a_prog
        region = ref.sample_regions(a_prog, batch, obs)
        placed = np.asarray(call.region) >= 0
        v["region_mismatch"] += int(np.count_nonzero(
            np.asarray(call.region)[placed] != region[placed]))
        act = ref.activation(a_prog, demand, predicted, obs)
        v["activation_mismatch"] += int(np.count_nonzero(
            act != np.asarray(call.activation)))
        server = np.asarray(call.server, np.int64)
        v["eligibility_faults"] += ref.eligibility_faults(
            batch, np.where(placed, np.asarray(call.region), region),
            np.where(placed, server, -1))
        p2 = ref.phase2(t, batch, region, forced=server,
                        score=t in scored)
        if t in scored:
            checked += p2["gaps"].size
            if p2["gaps"].size:
                v["place_gap"] = max(v["place_gap"],
                                     float(p2["gaps"].max()))
        ref.apply_activation(np.asarray(call.activation))
        resp, n_sw, energy, assigned = ref.apply(
            t, batch, np.asarray(call.region, np.int64), server)
        drops = ref.settle(t, batch, assigned)
        cost = ref.close(energy)
        got = responses[t]
        if (got.size != len(resp) or n_sw != m.switch_count_by_slot[t]
                or drops != drops_by_slot.get(t, 0)):
            v["count_mismatch"] += 1
        else:
            v["outcome_rel"] = max(v["outcome_rel"],
                                   _rel(got, np.asarray(resp), 1e-9))
        v["outcome_rel"] = max(v["outcome_rel"], _rel(
            np.asarray([m.power_cost_by_slot[t]]), np.asarray([cost]),
            1e-12))
    else:
        ref.start_slot(end, run.slots[end] if end < len(run.slots)
                       else run.slots[-1])
        st = run.final_state
        v["outcome_rel"] = max(v["outcome_rel"], _rel(
            np.asarray(st.queue_s, np.float64),
            np.asarray(ref.queue, np.float64), 1.0))
        v["count_mismatch"] += int(np.count_nonzero(
            np.asarray(st.state) != ref.state))
        v["count_mismatch"] += int(np.count_nonzero(
            np.asarray(st.current_model, np.int64)
            != np.asarray(ref.current)))
    return Readings(values={k: float(x) for k, x in v.items()},
                    checked_tasks=checked, checked_slots=sorted(scored))
