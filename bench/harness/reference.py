"""Plain reference of one TORTA slot, written from the algorithm.

It imports nothing of the program.  Its semantics are those of the
program's numpy oracle (``core/micro.py`` with ``backend="numpy"``,
``core/macro.py``, ``sim/engine.py``'s numpy step, ``sim/reference.py``),
written out plainly: phase 1 (EMA forecast, log-domain Sinkhorn, smoothed
routing matrix ``A_t``, one region drawn per task), Eq 6 activation
targets, the Eq 7-10 greedy in each region's urgency order, and the engine
step (activation, per-task apply, buffering and drops, queue drain and
power billing).

Every constant and size comes from the configuration file.  ``Precision``
says in which dtypes it computes: the output check runs it in the stated
precision of each layer or above (float64, with float64 Sinkhorn and
dots); the control runs it one step below what the configuration states.

Two modes share the code.  In *check* mode the reference follows the
program's decisions (teacher forcing): it computes its own answer for each
layer from the state the program's earlier answers produced, compares,
and then applies the program's answer, so one disagreement does not carry
into the slots after it.  In *free* mode (the control) it applies its own
answers.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import numpy as np

from harness import world

EMPTY = -2                      # unused ring entry


@dataclasses.dataclass(frozen=True)
class Precision:
    """Dtypes of the three parts: ``host`` (phase-1 host math, scores,
    engine step), ``sinkhorn`` and ``dots`` (locality dots and norms).
    bfloat16 is computed in float32 with every result rounded to
    bfloat16, as the chip's bfloat16 units round."""

    host: str = "float64"
    sinkhorn: str = "float64"
    dots: str = "float64"

    @classmethod
    def below_stated(cls) -> "Precision":
        """One step below the configuration's stated precision: float32
        for its float64 layers, bfloat16 for its float32 ones."""
        return cls(host="float32", sinkhorn="bfloat16", dots="bfloat16")


def storage(name: str):
    return np.float64 if name == "float64" else np.float32


def rounder(name: str):
    """``x -> x`` rounded to the named precision, in its storage dtype."""
    if name == "bfloat16":
        import ml_dtypes
        bf16 = ml_dtypes.bfloat16
        return lambda x: np.asarray(x, np.float32).astype(bf16).astype(
            np.float32)
    dt = storage(name)
    return lambda x: np.asarray(x, dt)


def logsumexp(x: np.ndarray, axis: int, q) -> np.ndarray:
    m = np.max(x, axis=axis, keepdims=True)
    out = q(np.log(q(np.sum(q(np.exp(q(x - m))), axis=axis,
                            keepdims=True)))) + m
    return q(np.squeeze(out, axis=axis))


def sinkhorn_probs(mu, nu, cost, reg: float, iters: int,
                   prec: str) -> np.ndarray:
    """Row-normalised entropic OT plan, log-domain (``core/ot.sinkhorn``
    followed by ``routing_probs``), computed in precision ``prec``."""
    q = rounder(prec)
    mu, nu, cost = (q(np.asarray(a, np.float64)) for a in (mu, nu, cost))
    reg = q(reg)
    logmu = q(np.log(np.maximum(mu, q(1e-30))))
    lognu = q(np.log(np.maximum(nu, q(1e-30))))
    mk = q(-cost / reg)
    mkr = q(mk * reg)
    f = np.zeros_like(mu)
    g = np.zeros_like(nu)
    for _ in range(iters):
        f = q(reg * q(logmu - logsumexp(q(q(mkr + g[None, :]) / reg), 1, q)))
        g = q(reg * q(lognu - logsumexp(q(q(mkr + f[:, None]) / reg), 0, q)))
    plan = q(np.exp(q(q(q(mkr + f[:, None]) + g[None, :]) / reg)))
    plan = plan.astype(np.float64)
    return plan / np.maximum(plan.sum(1, keepdims=True), 1e-12)


class Reference:
    """State and step of the plain reference (see module docstring)."""

    def __init__(self, cfg: dict, fleet: world.Fleet, latency: np.ndarray,
                 precision: Precision = Precision()):
        self.cfg = cfg
        self.p = precision
        self.f = storage(precision.host)
        self.qd = rounder(precision.dots)
        tc = cfg["torta"]
        self.tc = tc
        self.fleet = fleet
        self.lat = np.asarray(latency, np.float64)
        self.r = fleet.n_regions
        self.ptr = fleet.region_ptr
        self.sizes = np.diff(fleet.region_ptr)
        self.slot_s = float(cfg["slot_seconds"])
        dyn = fleet.dynamic_columns(world.warm_slots(cfg))
        self.state = dyn["state"].copy()
        self.warm_rem = dyn["warm_remaining_s"].astype(self.f)
        self.queue = dyn["queue_s"].astype(self.f)
        self.util = dyn["util"].astype(self.f)
        self.idle = dyn["idle_slots"].copy()
        self.current: List[int] = dyn["current_model"].tolist()
        self.warm_lists: List[List[int]] = [[] for _ in
                                            range(fleet.n_servers)]
        self.speed = np.maximum(fleet.tflops / cfg["reference_speed_tflops"],
                                0.1)
        self.switch_s, self.warm_hit_s = world.switch_seconds(cfg)
        names, rows = world.model_table(cfg)
        kinds = cfg["kinds"]
        self.demand_by_kind = np.asarray(
            [cfg["kind_demand_tflops"][k] for k in kinds], np.float64)
        rank = np.empty(len(names), np.int64)
        rank[np.argsort(np.asarray(names))] = np.arange(len(names))
        self.model_rank = rank
        # phase-1 state
        self.a_prev = np.full((self.r, self.r), 1.0 / self.r)
        self.ema = np.full(self.r, 1.0 / self.r)
        self.prev_nu = np.full(self.r, 1.0 / self.r)
        self.rng = np.random.default_rng(tc["scheduler_seed"])
        self.hist: List[np.ndarray] = []
        # locality rings per (region, server in region), newest first
        k = tc["history_keep"]
        self.s_pad = int(self.sizes.max())
        shape = (self.r, self.s_pad, k)
        self.ring_mid = np.full(shape, EMPTY, np.int64)
        self.ring_slot = np.zeros(shape, np.int64)
        self.ring_norm = np.zeros(shape, storage(precision.dots))
        self.ring_emb = np.zeros(shape + (0,), storage(precision.dots))
        self.pending: Optional[world.Slot] = None
        self.valid = np.arange(self.s_pad)[None, :] < self.sizes[:, None]
        self.gmap = np.where(self.valid,
                             self.ptr[:-1, None] + np.arange(self.s_pad), 0)

    # ------------------------------------------------------ slot start

    def start_slot(self, t: int, new: world.Slot) -> world.Slot:
        """Warming progress, arrivals history; returns the slot's batch:
        buffered rows first, then the new arrivals."""
        warming = self.state == world.WARMING
        if warming.any():
            rem = self.warm_rem.copy()
            rem[warming] = rem[warming] - self.f(self.slot_s)
            done = warming & (rem <= 0)
            self.state[done] = world.ACTIVE
            rem[done] = 0.0
            self.warm_rem = rem
        self.hist.append(np.bincount(new.origin, minlength=self.r)
                         .astype(np.float64))
        if self.ring_emb.shape[3] < new.embeds.shape[1]:
            e = new.embeds.shape[1]
            grown = np.zeros(self.ring_emb.shape[:3] + (e,),
                             self.ring_emb.dtype)
            grown[..., :self.ring_emb.shape[3]] = self.ring_emb
            self.ring_emb = grown
        if self.pending is None or len(self.pending) == 0:
            return new
        return world.Slot(t=t, **{
            k: np.concatenate([getattr(self.pending, k), getattr(new, k)])
            for k in ("ids", "origin", "model_idx", "kind_id", "work_s",
                      "mem_gb", "deadline_slot", "arrival_slot", "embeds")})

    def segsum(self, x: np.ndarray) -> np.ndarray:
        out = np.zeros(self.r, x.dtype)
        for j in range(self.r):
            out[j] = x[self.ptr[j]:self.ptr[j + 1]].sum()
        return out

    def observe(self, batch: world.Slot) -> Dict[str, np.ndarray]:
        """The slot's observation, from the reference's own state.  The
        queue in tasks counts backlog seconds only: the buffered rows have
        already moved into the slot's batch when the scheduler looks (as
        in ``sim/reference.py``, whose buffers are emptied first)."""
        act = self.state == world.ACTIVE
        cap = self.segsum(np.where(act, self.fleet.capacity, 0.0))
        q_s = self.segsum(np.where(act, self.queue, 0.0).astype(self.f))
        return {"capacities": cap,
                "queue_tasks": q_s / self.f(max(self.slot_s, 1.0))}

    # --------------------------------------------------------- phase 1

    def route(self, obs, demand: np.ndarray, a_prev: np.ndarray):
        """Phase 1: returns (A_t, forecast).  ``a_prev`` is the routing
        matrix the smoothing starts from."""
        f = self.f
        tc = self.tc
        tot = demand.sum()
        if tot > 0:
            self.ema = ((1 - tc["ema_alpha"]) * self.ema
                        + tc["ema_alpha"] * demand / tot).astype(f)
        predicted = self.ema / self.ema.sum()
        caps = obs["capacities"].astype(f)
        cap = np.maximum(caps - obs["queue_tasks"],
                         f(0.05) * np.maximum(caps, f(1e-6)))
        blended = 0.5 * demand + 0.5 * predicted * max(float(tot), 1.0)
        mu = blended / max(float(blended.sum()), 1e-9)
        nu = cap / max(float(cap.sum()), 1e-9)
        price = self.fleet.power_price
        cost = (tc["cost_w_power"] * np.broadcast_to(
            (price / max(price.max(), 1e-9))[None, :], (self.r, self.r))
            + tc["cost_w_latency"] * (self.lat / max(self.lat.max(), 1e-9)))
        probs = sinkhorn_probs(mu, nu, cost, tc["sinkhorn_reg"],
                               tc["sinkhorn_iters"], self.p.sinkhorn)
        shock = float(np.abs(nu - self.prev_nu).sum()) > tc["shock_l1"]
        self.prev_nu = nu
        eta = 1.0 if shock else tc["eta"]
        a = ((1 - eta) * a_prev + eta * probs).astype(f)
        a = a / np.maximum(a.sum(1, keepdims=True), f(1e-9))
        return a.astype(np.float64), predicted.astype(np.float64)

    def sample_regions(self, a: np.ndarray, batch: world.Slot,
                       obs) -> np.ndarray:
        """One region per task from its origin's row of ``A_t``, over
        regions with active capacity (per origin, ascending)."""
        region = np.full(len(batch), -1, np.int64)
        mask = obs["capacities"] > 0
        for origin in np.unique(batch.origin):
            idx = np.flatnonzero(batch.origin == origin)
            pm = a[int(origin)] * mask
            if pm.sum() <= 0:
                pm = mask.astype(float)
            if pm.sum() <= 0:
                pm = np.ones(self.r)
            region[idx] = self.rng.choice(self.r, size=idx.size,
                                          p=pm / pm.sum())
        return region

    def activation(self, a: np.ndarray, demand: np.ndarray,
                   predicted: np.ndarray, obs) -> np.ndarray:
        """Eq 6 targets per region from the trend-adjusted inbound
        forecast ``A_t^T (forecast * total)``."""
        tc = self.tc
        total = max(float(demand.sum()), 1.0)
        inbound = a.T @ (predicted * total)
        if len(self.hist) >= 2:
            prev = max(float(self.hist[-2].sum()), 1.0)
            inbound = inbound * float(np.clip(total / prev, 1.0, 1.6))
        out = np.empty(self.r, np.int64)
        for j in range(self.r):
            caps = self.fleet.capacity[self.ptr[j]:self.ptr[j + 1]]
            avg = float(np.mean(caps)) if caps.size else 1.0
            f = max(float(inbound[j]), 0.0)
            need = ((float(obs["queue_tasks"][j]) + f
                     + tc["sigma"] * math.sqrt(f)) / max(avg, 1e-9))
            out[j] = int(min(self.sizes[j],
                             max(1, math.ceil(tc["headroom"] * need))))
        return out

    # --------------------------------------------------------- phase 2

    def greedy_order(self, batch: world.Slot, region: np.ndarray):
        """Rows with a region, region-major, each region urgency-first:
        (deadline, model name, -work), ties in row order."""
        rows = np.flatnonzero(region >= 0)
        order = np.lexsort((-batch.work_s[rows],
                            self.model_rank[batch.model_idx[rows]],
                            batch.deadline_slot[rows], region[rows]))
        return rows[order]

    def switch_cost(self, g: int, mid: int) -> float:
        if self.current[g] == mid:
            return 0.0
        scale = float(self.fleet.switch_scale[g])
        if mid in self.warm_lists[g]:
            return scale * self.warm_hit_s
        return scale * self.switch_s

    def _push_many(self, j, s, mid, t: int, emb) -> None:
        """Placed tasks become the newest entries of their servers' rings
        (``history_keep`` kept), in the given order."""
        if len(j) == 0:
            return
        k_keep = self.ring_mid.shape[2]
        key = j * self.s_pad + s
        order = np.argsort(key, kind="stable")
        key_o = key[order]
        uniq, start, count = np.unique(key_o, return_index=True,
                                       return_counts=True)
        pos = np.arange(len(key_o)) - np.repeat(start, count)
        newest = np.repeat(count, count) - 1 - pos     # 0 = newest
        qd = self.qd
        e = np.zeros((len(j), self.ring_emb.shape[3]), self.ring_emb.dtype)
        e[:, :emb.shape[1]] = qd(emb)
        norm = qd(np.sqrt(qd(np.sum(qd(e * e), axis=1))))
        flat = [a.reshape((-1,) + a.shape[2:]) for a in
                (self.ring_mid, self.ring_slot, self.ring_norm,
                 self.ring_emb)]
        old = [a[uniq].copy() for a in flat]
        for k in range(k_keep):
            shift = k - count                              # old entry k-m
            from_old = shift >= 0
            for a, o in zip(flat, old):
                a[uniq[from_old], k] = o[from_old, shift[from_old]]
        sel = newest < k_keep
        src = order[sel]
        tgt = (key_o[sel], newest[sel])
        flat[0][tgt] = mid[src]
        flat[1][tgt] = t
        flat[2][tgt] = norm[src]
        flat[3][tgt] = e[src]

    def _push_distinct(self, j, s, mid, t: int, emb) -> None:
        """``_push_many`` for distinct (region, server) pairs."""
        for a in (self.ring_mid, self.ring_slot, self.ring_norm,
                  self.ring_emb):
            a[j, s, 1:] = a[j, s, :-1]
        qd = self.qd
        e = np.zeros((len(j), self.ring_emb.shape[3]), self.ring_emb.dtype)
        e[:, :emb.shape[1]] = qd(emb)
        self.ring_mid[j, s, 0] = mid
        self.ring_slot[j, s, 0] = t
        self.ring_emb[j, s, 0] = e
        self.ring_norm[j, s, 0] = qd(np.sqrt(qd(np.sum(qd(e * e), axis=1))))

    def _ring_terms(self, t: int, j, s):
        """Eq 10 is linear in the task: for server ``s`` of region ``j``,
        ``loc(task) = w_model * C[mid] + w_embed * (e / |e|) . V`` with
        ``C[m] = sum_k [mid_k == m] / d_k`` and
        ``V = sum_k e_k / (|e_k| d_k)`` over the ring's entries, where
        ``d_k = exp(loc_decay * min(max(t - slot_k, 0), loc_max_age))``.
        (The program's guard ``|e| |e_k| > 1e-9`` holds for every
        non-zero float32 embedding of the traffic.)  Returns (C, V)."""
        f = self.f
        tc = self.tc
        mids = self.ring_mid[j, s]                       # (..., K)
        age = np.clip(t - self.ring_slot[j, s], 0, tc["loc_max_age"])
        inv_d = np.where(mids != EMPTY,
                         f(1) / np.exp(f(tc["loc_decay"]) * age.astype(f)),
                         f(0))
        one_hot = mids[..., None] == np.arange(len(self.model_rank))
        c = np.sum(np.where(one_hot, inv_d[..., None], f(0)), axis=-2)
        qd = self.qd
        norm = self.ring_norm[j, s]
        w = np.where(norm > 0, qd(qd(inv_d) / np.where(norm > 0, norm, 1)),
                     0).astype(norm.dtype)
        v = qd(np.sum(qd(self.ring_emb[j, s] * w[..., None]), axis=-2))
        return c.astype(f), v

    def phase2(self, t: int, batch: world.Slot, region: np.ndarray, *,
               forced: Optional[np.ndarray], score: bool) -> Dict:
        """Phase 2 for every region of the slot.

        ``forced`` (check mode) is the program's server per row: the
        projected queues and the rings follow it.  With ``score`` the
        Eq 7-10 greedy runs over every region at once, one task of each
        region per step in urgency order; in check mode each step then
        measures by how much the reference's score of the program's
        server lies below the reference's best (``inf`` where the program
        buffers a task that has an eligible server, places one that has
        none, or picks an ineligible server), and in free mode (no
        ``forced``) the reference places the task itself.  Returns
        ``server`` per row and the per-task ``gaps``."""
        order = self.greedy_order(batch, region)
        out = np.full(len(batch), -1, np.int64)
        if not score:
            rows = order[(forced[order] >= 0)
                         & (forced[order] < self.sizes[region[order]])]
            out[rows] = forced[rows]
            self._push_many(region[rows], forced[rows],
                            batch.model_idx[rows], t, batch.embeds[rows])
            return {"server": out, "gaps": np.zeros(0)}
        f = self.f
        tc = self.tc
        fl = self.fleet
        gmap, valid = self.gmap, self.valid
        r_all = np.arange(self.r)
        # slot-start terms per model m (M, R, S): Eq 8 hardware fit, the
        # warm bonus, memory fit and the switch seconds of a placement
        _, rows_m = world.model_table(self.cfg)
        kinds = self.cfg["kinds"]
        active = (self.state[gmap] == world.ACTIVE) & valid
        cur = np.asarray(self.current)[gmap]
        n_warm = world.warm_slots(self.cfg)
        warm = np.full(gmap.shape + (n_warm,), -9, np.int64)
        for j, s in zip(*np.nonzero(valid)):
            lst = self.warm_lists[gmap[j, s]]
            warm[j, s, :len(lst)] = lst
        load = np.exp(-(self.util[gmap].astype(f)
                        + self.queue[gmap].astype(f) / f(self.slot_s)))
        scale = fl.switch_scale[gmap]
        base, mem_ok, sw = [], [], []
        for m, (_, mem_m, kind_m) in enumerate(rows_m):
            k = kinds.index(kind_m)
            hw = (np.minimum(1.0, fl.tflops[gmap]
                             / self.demand_by_kind[k])
                  * np.minimum(1.0, fl.mem_gb[gmap] / max(mem_m, 1e-9))
                  * np.where(fl.kind_id[gmap] == k, 1.0, 0.5))
            hit = (warm == m).any(-1)
            bonus = np.where(cur == m, 1.0,
                             np.where(hit, tc["warm_cache_bonus"], 0.0))
            base.append(f(tc["w_hw"]) * hw.astype(f)
                        + f(tc["w_load"]) * load
                        + f(tc["w_warm"]) * bonus.astype(f))
            mem_ok.append(fl.mem_gb[gmap] >= mem_m)
            sw.append(np.where(cur == m, 0.0, np.where(
                hit, scale * self.warm_hit_s, scale * self.switch_s)))
        base, mem_ok = np.stack(base), np.stack(mem_ok)
        sw = np.stack(sw).astype(f)
        speed = self.speed[gmap].astype(f)
        c_loc, v_loc = self._ring_terms(t, slice(None), slice(None))
        proj = np.where(valid, self.queue[gmap], 0.0).astype(f)
        per_region = [order[region[order] == j] for j in range(self.r)]
        gaps: List[np.ndarray] = []
        cap_s = f(tc["queue_cap_slots"] * self.slot_s)
        e_dim = self.ring_emb.shape[3]
        for k in range(max(len(x) for x in per_region)):
            live = np.array([k < len(x) for x in per_region])
            rows = np.array([x[k] if k < len(x) else 0 for x in per_region])
            mid = batch.model_idx[rows].astype(np.int64)
            work = batch.work_s[rows].astype(f)
            qd = self.qd
            te = np.zeros((self.r, e_dim), v_loc.dtype)
            te[:, :batch.embeds.shape[1]] = qd(batch.embeds[rows])
            t_norm = qd(np.sqrt(qd(np.sum(qd(te * te), axis=-1))))
            unit = np.where(t_norm[:, None] > 0,
                            qd(te / np.where(t_norm > 0, t_norm, 1)[:, None]),
                            0).astype(v_loc.dtype)
            emb_term = qd(np.sum(qd(v_loc * unit[:, None, :]),
                                 axis=-1)).astype(f)
            loc = (f(tc["w_model"]) * c_loc[r_all, :, mid]
                   + f(tc["w_embed"]) * emb_term)
            static = base[mid, r_all] + f(tc["w_loc"]) * loc
            eligible = (active & mem_ok[mid, r_all] & (proj <= cap_s)
                        & live[:, None])
            q = proj / f(self.slot_s)
            sc = (static - (f(tc["wait_lin"]) * q
                            + f(tc["wait_quad"]) * q * q)
                  - f(tc["exec_penalty"]) * (work[:, None] / speed)
                  / f(self.slot_s))
            sc = np.where(eligible, sc, -np.inf)
            any_e = eligible.any(1)
            best = np.argmax(sc, 1)
            if forced is None:
                pick = np.where(any_e, best, -1)
            else:
                pick = np.where(live, forced[rows], -1)
                inr = (pick >= 0) & (pick < self.s_pad)
                ok = inr & eligible[r_all, np.clip(pick, 0, self.s_pad - 1)]
                with np.errstate(invalid="ignore"):
                    diff = sc[r_all, best] - sc[r_all, np.clip(pick, 0, None)]
                gap = np.where(any_e, np.where(ok, diff, np.inf),
                               np.where(pick >= 0, np.inf, 0.0))
                gaps.append(gap[live])
            j = np.flatnonzero(live & (pick >= 0) & (pick < self.sizes))
            if j.size == 0:
                continue
            s = pick[j]
            out[rows[j]] = s
            proj[j, s] += work[j] / speed[j, s] + sw[mid[j], j, s]
            self._push_distinct(j, s, mid[j], t, batch.embeds[rows[j]])
            c_loc[j, s], v_loc[j, s] = self._ring_terms(t, j, s)
        return {"server": out,
                "gaps": np.concatenate(gaps) if gaps else np.zeros(0)}

    def eligibility_faults(self, batch: world.Slot, region: np.ndarray,
                           server: np.ndarray) -> int:
        """Rows of the slot whose decision breaks Eq 7-10's eligibility,
        read from the slot-start state without scoring, so it runs on
        every slot: a task placed on a server that is not active, lacks
        its memory or starts the slot over the queue cap, or a task
        buffered while a server of its region (``region``: the
        reference's draw) was active, held its memory and stays under the
        cap even if every task placed on it this slot paid a full model
        switch."""
        fl = self.fleet
        cap = self.tc["queue_cap_slots"] * self.slot_s
        active = self.state == world.ACTIVE
        placed = (server >= 0) & (server < self.sizes[region])
        rows = np.flatnonzero(placed)
        g = self.ptr[region[rows]] + server[rows]
        bad = int(np.count_nonzero(
            ~active[g] | (fl.mem_gb[g] < batch.mem_gb[rows])
            | (self.queue[g] > cap * (1 + 1e-9))))
        bad += int(np.count_nonzero((server >= 0) & ~placed))
        most = self.queue.astype(np.float64) + np.bincount(
            g, weights=(batch.work_s[rows] / self.speed[g]
                        + fl.switch_scale[g] * self.switch_s),
            minlength=fl.n_servers)
        roomy = active & (most <= cap * (1 - 1e-9))
        left = np.flatnonzero(server < 0)
        for mem in np.unique(batch.mem_gb[left]):
            fits = self.segsum((roomy & (fl.mem_gb >= mem)).astype(np.int64))
            mine = left[batch.mem_gb[left] == mem]
            bad += int(np.count_nonzero(fits[region[mine]] > 0))
        return bad

    # ------------------------------------------------------ engine step

    def apply_activation(self, targets: np.ndarray) -> None:
        """Wake idle servers up to each region's target, or switch off
        the least used, longest idle servers with an empty queue."""
        for j in range(self.r):
            if targets[j] < 0:
                continue
            lo, hi = int(self.ptr[j]), int(self.ptr[j + 1])
            n_target = int(np.clip(targets[j], 1, hi - lo))
            codes = self.state[lo:hi]
            active = np.flatnonzero(codes == world.ACTIVE)
            off = np.flatnonzero(codes == world.OFF)
            n_now = len(active) + int(np.count_nonzero(codes
                                                       == world.WARMING))
            if n_target > n_now:
                wake = off[:n_target - n_now] + lo
                self.state[wake] = world.WARMING
                self.warm_rem[wake] = self.cfg["cold_start_s"]
            elif n_target < len(active):
                g = active + lo
                order = g[np.lexsort((-self.idle[g], self.util[g]))]
                victims = order[:len(active) - n_target]
                victims = victims[self.queue[victims] <= 0]
                self.state[victims] = world.OFF
                self.util[victims] = 0.0

    def apply(self, t: int, batch: world.Slot, region: np.ndarray,
              server: np.ndarray) -> tuple:
        """Place every decided task on its server in row order.  Where a
        target is not active, every task of the slot resolves to its
        target if active, else to its region's least-backlogged active
        server.  Returns (responses, switches, switch energy J, the
        assigned mask)."""
        # Python floats are IEEE doubles: the float64 loop runs on them
        f = float if self.f is np.float64 else self.f
        n = len(batch)
        assigned = np.zeros(n, bool)
        cand = np.flatnonzero(region >= 0)
        g0 = self.ptr[region[cand]] + server[cand]
        resolve = bool(np.any(self.state[g0] != world.ACTIVE))
        state = self.state.tolist()
        queue = self.queue.tolist()
        speed = self.speed.tolist()
        power = self.fleet.power_w.tolist()
        frac = self.cfg["switch_power_frac"]
        keep = world.warm_slots(self.cfg)
        responses: List[float] = []
        energy = f(0.0)
        n_sw = 0
        work_l = batch.work_s.tolist()
        mid_l = batch.model_idx.tolist()
        origin_l = batch.origin.tolist()
        lat = self.lat.tolist()
        for i, g in zip(cand.tolist(), g0.tolist()):
            j = int(region[i])
            if resolve and state[g] != world.ACTIVE:
                lo, hi = int(self.ptr[j]), int(self.ptr[j + 1])
                act = np.flatnonzero(self.state[lo:hi] == world.ACTIVE)
                if act.size == 0:
                    continue
                g = lo + int(act[np.argmin(np.asarray(queue[lo:hi])[act])])
            mid = mid_l[i]
            sw = f(self.switch_cost(g, mid))
            if sw > 0:
                n_sw += 1
                energy = f(energy + f(f(sw * f(power[g])) * f(frac)))
            self.current[g] = mid
            lst = self.warm_lists[g]
            if mid in lst:
                lst.remove(mid)
            lst.insert(0, mid)
            del lst[keep:]
            work = f(f(work_l[i]) / f(speed[g]))
            wait = f(f(queue[g]) + sw)
            net = f(lat[origin_l[i]][j] / 1000.0)
            queue[g] = f(f(queue[g]) + f(sw + work))
            responses.append(float(f(f(wait + work) + net)))
            assigned[i] = True
        self.queue = np.asarray(queue, self.f)
        return responses, n_sw, float(energy), assigned

    def settle(self, t: int, batch: world.Slot, assigned: np.ndarray) -> int:
        """Buffer what was not placed (grouped by origin) and drop rows
        ``drop_after_slots`` or more slots old; returns the drops."""
        left = np.flatnonzero(~assigned)
        too_old = (t - batch.arrival_slot[left]) >= self.cfg["drop_after_slots"]
        keep = left[~too_old]
        keep = keep[np.argsort(batch.origin[keep], kind="stable")]
        self.pending = world.Slot(t=t, **{
            k: getattr(batch, k)[keep]
            for k in ("ids", "origin", "model_idx", "kind_id", "work_s",
                      "mem_gb", "deadline_slot", "arrival_slot", "embeds")})
        return int(np.count_nonzero(too_old))

    def close(self, energy_j: float) -> float:
        """Drain every active server by one slot, update utilisation and
        idle counters, and bill power at regional prices plus the switch
        energy at the mean price.  Returns the slot's power cost."""
        f = self.f
        slot = f(self.slot_s)
        act = self.state == world.ACTIVE
        busy = np.minimum(self.queue, slot)
        self.util = np.where(act, busy / slot, self.util).astype(f)
        self.idle = np.where(act, np.where(self.util > 0.05, 0,
                                           self.idle + 1), self.idle)
        self.queue = np.where(act, np.maximum(f(0), self.queue - slot),
                              self.queue).astype(f)
        power = np.where(act, (f(0.1) + f(0.9) * self.util)
                         * self.fleet.power_w.astype(f) * slot,
                         f(0)).astype(f)
        reg_j = self.segsum(power)
        price = self.fleet.power_price
        cost = 0.0
        for j in range(self.r):
            cost += float(reg_j[j]) / 3.6e6 * float(price[j])
        cost += energy_j / 3.6e6 * float(np.mean(price))
        return cost
