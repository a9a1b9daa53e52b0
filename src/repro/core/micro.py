"""Micro-level allocation (§V-C): dynamic server activation (Eq 6) + greedy
task-server matching by compatibility score (Eqs 7-10) + task buffering.

The scoring hot path builds the full (N tasks x S servers) Eq 7-10 score
matrix in ONE batched call per region-slot, with a pluggable backend:

* ``backend="numpy"`` — float64 oracle, exact op-for-op port of the scalar
  reference functions below (kept for tests and ``sim/reference.py``);
* ``backend="jax"`` — the whole greedy pass is a jit-compiled ``lax.scan``
  over the pre-sorted task axis (``core/micro_jax.py``), with the
  locality history carried as fixed-shape ``LocalityState`` arrays and an
  optional fused Pallas static-score kernel (``fused=True``);
* ``backend="pallas"`` — numpy greedy walk, but the static hw+load part
  of the score matrix comes from the ``kernels/compat_score`` Pallas op
  (enable via ``TortaScheduler(use_compat_kernel=True)``).

Locality history lives in ``core/micro_state.py``'s ``LocalityState`` — a
fixed-shape per-region ring buffer scoring identically to the legacy
``LocalityTracker`` (which survives below as the per-object reference's
API, with exact-equivalence adapters between the two).

The numpy greedy pass walks tasks urgency-first, applying the dynamic
terms (projected-wait penalty, warm bonus, execution-time term) as
whole-row vector updates; the jax pass expresses the same updates inside
the scan body, so no per-task Python loop remains at all.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.micro_state import LocalityState
from repro.obs import runtime as obs_rt
from repro.sim.engine import SlotObs
from repro.sim.state import ACTIVE, MODEL_NAMES, ClusterState, model_id
from repro.sim.workload import Task

W_HW, W_LOAD, W_LOC = 0.4, 0.4, 0.2      # Eq 7 weights
W_WARM = 2.0                             # same-model (no-switch) bonus
W_MODEL, W_EMBED = 0.7, 0.3              # Eq 10 similarity weights
LOC_DECAY = 0.5                          # lambda in Eq 10

# compute requirement proxy: task kind maps to a tflops demand (Eq 8)
DEMAND_TFLOPS = {"compute": 200.0, "memory": 100.0, "lightweight": 60.0}
KIND_ORDER = ("compute", "memory", "lightweight")
_KIND_IDX = {k: i for i, k in enumerate(KIND_ORDER)}
_DEMAND_BY_KIND = np.array([DEMAND_TFLOPS[k] for k in KIND_ORDER])

# model-id -> lexicographic rank of the model name, so the batch path's
# np.lexsort reproduces the legacy `sorted(..., key=(deadline, model,
# -work))` ordering exactly (both sorts are stable)
_MODEL_RANK = np.empty(len(MODEL_NAMES), np.int64)
_MODEL_RANK[np.argsort(np.array(MODEL_NAMES))] = np.arange(len(MODEL_NAMES))

# server-feature "capacity" channel fed to the compat_score kernel: the
# kernel computes load = exp(-4*(util+queue)/cap), so cap=4 reduces it to
# this module's Eq 9 form exp(-(util+queue)).
KERNEL_LOAD_CAP = 4.0


def target_active_servers(queue_tasks: float, predicted: float,
                          avg_capacity: float, n_servers: int, *,
                          sigma: float = 1.0, headroom: float = 2.0) -> int:
    """Eq 6: N_target = min(S_r, ceil((Q + F + sigma*sqrt(F)) / C_avg)).

    ``headroom`` scales the target to keep utilization off the knee of the
    queueing curve (the paper trades a mild power increase for latency —
    its cost win comes from cheap-region routing + fewer switches, not from
    starving capacity)."""
    f = max(predicted, 0.0)
    need = (queue_tasks + f + sigma * math.sqrt(f)) / max(avg_capacity, 1e-9)
    return int(min(n_servers, max(1, math.ceil(headroom * need))))


# ---------------------------------------------------------------------------
# scalar Eq 7-10 reference (oracle for the batched path; used by
# sim/reference.py and the parity tests)
# ---------------------------------------------------------------------------


def hw_compatibility(task: Task, srv) -> float:
    """Eq 8: min(1, compute ratio) * min(1, memory ratio) * type match."""
    demand = DEMAND_TFLOPS[task.kind]
    c = min(1.0, srv.tflops / demand)
    m = min(1.0, srv.mem_gb / max(task.mem_gb, 1e-9))
    type_match = 1.0 if srv.kind == task.kind else 0.5
    return c * m * type_match


def load_compatibility(srv, slot_s: float) -> float:
    """Eq 9: exp(-(util + queue)/capacity), with the queue expressed as
    slot-time occupancy so slow/small GPUs aren't permanently discriminated
    (they must fill with lightweight tasks for the fleet to balance)."""
    q_norm = srv.queue_s / max(slot_s, 1e-9)
    return math.exp(-(srv.util + q_norm))


@dataclasses.dataclass
class RecentTask:
    model: Optional[str]         # None for history entries with mid < 0
    embed: Optional[np.ndarray]
    slot: int
    # cached derived facts for the vectorized path (identical values to
    # what the scalar path recomputes per call)
    mid: int = -1
    norm: float = 0.0
    uid: int = -1                # tracker-unique id (stable cache key)


class LocalityTracker:
    """Recent-task history per server for Eq 10."""

    def __init__(self, keep: int = 4):
        self.keep = keep
        self.recent: Dict[Tuple[int, int], List[RecentTask]] = {}
        self._uid = 0

    def note(self, key: Tuple[int, int], task: Task, t: int) -> None:
        self.note_fields(key, model_id(task.model), task.embed, t)

    def note_fields(self, key: Tuple[int, int], mid: int,
                    embed: Optional[np.ndarray], t: int) -> None:
        """Array-native ``note``: record by model id + embedding row."""
        lst = self.recent.setdefault(key, [])
        norm = np.linalg.norm(embed) if embed is not None else 0.0
        self._uid += 1
        lst.insert(0, RecentTask(MODEL_NAMES[mid] if mid >= 0 else None,
                                 embed, t, mid=mid, norm=norm,
                                 uid=self._uid))
        del lst[self.keep:]

    def locality(self, key: Tuple[int, int], task: Task, t: int) -> float:
        total = 0.0
        for rt in self.recent.get(key, ()):
            sim = W_MODEL * (1.0 if rt.model == task.model else 0.0)
            if task.embed is not None and rt.embed is not None:
                denom = (np.linalg.norm(task.embed) * np.linalg.norm(rt.embed))
                if denom > 1e-9:
                    sim += W_EMBED * float(task.embed @ rt.embed) / denom
            total += sim / math.exp(LOC_DECAY * min(max(t - rt.slot, 0), 40))
        return total

    def locality_column(self, key: Tuple[int, int], mids: np.ndarray,
                        embeds: np.ndarray, norms: np.ndarray,
                        has_embed: np.ndarray, t: int,
                        cache: Optional[dict] = None) -> np.ndarray:
        """Eq-10 locality of every task vs one server's history — the
        column-vectorized form of :meth:`locality` (same accumulation
        order).  ``cache`` memoizes per-history-entry contribution vectors
        across calls within one slot (entries are immutable once noted, so
        only the newest entry is ever computed fresh)."""
        recent = self.recent.get(key)
        n = len(mids)
        if not recent:
            return np.zeros(n)
        col = np.zeros(n)
        for rt in recent:
            contrib = cache.get(rt.uid) if cache is not None else None
            if contrib is None:
                sim = W_MODEL * (mids == rt.mid).astype(np.float64)
                if rt.embed is not None and has_embed.any():
                    denom = norms * rt.norm
                    ok = has_embed & (denom > 1e-9)
                    dots = embeds @ rt.embed
                    safe = np.where(ok, denom, 1.0)
                    sim = sim + np.where(
                        ok, W_EMBED * dots.astype(np.float64) / safe, 0.0)
                contrib = sim / math.exp(
                    LOC_DECAY * min(max(t - rt.slot, 0), 40))
                if cache is not None:
                    cache[rt.uid] = contrib
            col += contrib
        return col


def score(task: Task, srv, key: Tuple[int, int], t: int,
          slot_s: float, loc: LocalityTracker) -> float:
    """Eq 7 (+ explicit warm-model bonus: a same-model hit skips the entire
    Fig-3 switch pipeline, the single largest latency term)."""
    warm = 1.0 if srv.current_model == task.model else (
        0.4 if task.model in srv.warm_models else 0.0)
    return (W_HW * hw_compatibility(task, srv)
            + W_LOAD * load_compatibility(srv, slot_s)
            + W_LOC * loc.locality(key, task, t)
            + W_WARM * warm)


# ---------------------------------------------------------------------------
# batched scoring (the hot path)
# ---------------------------------------------------------------------------


def task_feature_matrix(tasks: Sequence[Task]) -> np.ndarray:
    """(N, 8) float64: [demand_tflops, mem_gb, kind-onehot x3, 0, 0, 0]."""
    n = len(tasks)
    f = np.zeros((n, 8))
    for i, t in enumerate(tasks):
        f[i, 0] = DEMAND_TFLOPS[t.kind]
        f[i, 1] = t.mem_gb
        f[i, 2 + _KIND_IDX[t.kind]] = 1.0
    return f


def task_feature_arrays(kind_id: np.ndarray,
                        mem_gb: np.ndarray) -> np.ndarray:
    """``task_feature_matrix`` from parallel arrays (no Task objects)."""
    n = len(kind_id)
    f = np.zeros((n, 8))
    kid = kind_id.astype(np.int64)
    f[:, 0] = _DEMAND_BY_KIND[kid]
    f[:, 1] = mem_gb
    f[np.arange(n), 2 + kid] = 1.0
    return f


def server_feature_matrix(state: ClusterState, sl: slice,
                          slot_s: float) -> np.ndarray:
    """(S, 8) float64: [tflops, mem_gb, kind-onehot x3, util, queue_norm,
    KERNEL_LOAD_CAP]."""
    s = sl.stop - sl.start
    f = np.zeros((s, 8))
    f[:, 0] = state.tflops[sl]
    f[:, 1] = state.mem_gb[sl]
    f[np.arange(s), 2 + state.kind_id[sl].astype(np.int64)] = 1.0
    f[:, 5] = state.util[sl]
    f[:, 6] = state.queue_s[sl] / max(slot_s, 1e-9)
    f[:, 7] = KERNEL_LOAD_CAP
    return f


def hw_load_matrix_np(task_feats: np.ndarray,
                      server_feats: np.ndarray) -> np.ndarray:
    """(N, S) float64 W_HW*hw + W_LOAD*load — numpy oracle of the
    ``compat_score`` kernel (zero locality), op-ordered to match the scalar
    reference bitwise."""
    demand = task_feats[:, 0][:, None]
    mem_t = task_feats[:, 1][:, None]
    tflops = server_feats[:, 0][None, :]
    mem_s = server_feats[:, 1][None, :]
    c = np.minimum(1.0, tflops / demand)
    m = np.minimum(1.0, mem_s / np.maximum(mem_t, 1e-9))
    kind_t = np.argmax(task_feats[:, 2:5], axis=1)
    kind_s = np.argmax(server_feats[:, 2:5], axis=1)
    type_match = np.where(kind_t[:, None] == kind_s[None, :], 1.0, 0.5)
    hw = c * m * type_match
    load = np.exp(-(server_feats[:, 5] + server_feats[:, 6]))[None, :]
    return W_HW * hw + W_LOAD * load


def hw_load_matrix(task_feats: np.ndarray, server_feats: np.ndarray, *,
                   backend: str = "numpy",
                   interpret: bool = False) -> np.ndarray:
    """(N, S) W_HW*hw + W_LOAD*load via the selected backend.
    ``backend="pallas"`` runs it through the ``compat_score`` kernel
    (float32, no locality operand — the Eq-10 term is folded in on the
    host, so no (N, S) zeros matrix is allocated per call)."""
    if backend == "pallas":
        from repro.kernels.compat_score import score_matrix
        return np.asarray(score_matrix(
            task_feats.astype(np.float32), server_feats.astype(np.float32),
            use_pallas=True, interpret=interpret)).astype(np.float64)
    if backend == "numpy":
        return hw_load_matrix_np(task_feats, server_feats)
    raise ValueError(f"unknown micro backend: {backend!r}")


def batched_score_matrix(task_feats: np.ndarray, server_feats: np.ndarray,
                         locality: np.ndarray, *, backend: str = "numpy",
                         interpret: bool = False) -> np.ndarray:
    """One (N, S) Eq 7-10 static score matrix: W_HW*hw + W_LOAD*load +
    W_LOC*locality.  Locality is added on the host so the allocator can
    apply within-slot locality updates as column deltas."""
    return hw_load_matrix(task_feats, server_feats, backend=backend,
                          interpret=interpret) + W_LOC * locality


class MicroAllocator:
    """Greedy matching within a region, urgency-first (Algorithm 1,
    Phase 2), scored via one batched (N x S) matrix per region-slot.

    Locality history is held per region as fixed-shape ``LocalityState``
    arrays; ``backend="jax"`` hands state + score matrix to the jitted
    ``lax.scan`` greedy (``core/micro_jax.py``), while the numpy/pallas
    backends run the (oracle) Python walk over the same state."""

    KEEP = 4                      # history depth (legacy tracker default)

    def __init__(self, sigma: float = 1.0, headroom: float = 2.0, *,
                 backend: str = "numpy", interpret: bool = False,
                 fused: bool = False):
        if backend not in ("numpy", "pallas", "jax", "fused"):
            raise ValueError(f"unknown micro backend: {backend!r}")
        self.sigma = sigma
        self.headroom = headroom
        self.backend = backend
        self.interpret = interpret
        self.fused = fused
        self._loc: Dict[int, LocalityState] = {}
        self._dev_rings = None        # backend="fused": device-side rings
        self._uid = 0

    def reset(self) -> None:
        self._loc = {}
        self._dev_rings = None
        self._uid = 0

    def locality_state(self, ridx: int) -> Optional[LocalityState]:
        """The region's ring-buffer history (None before first use).  For
        ``backend="fused"`` this is a lazy device->host materialization of
        the stacked rings (uids are backend-local)."""
        if self._dev_rings is not None:
            n_servers = self._dev_region_sizes[ridx]
            return self._dev_rings.region_state(ridx, n_servers)
        return self._loc.get(ridx)

    def _ensure_dev_rings(self, n_regions: int, s_pad: int, edim: int):
        """Device-resident stacked rings for the fused backend (grown in
        the embed channel on demand, reset when the fleet shape moves)."""
        from repro.core.micro_jax import DeviceRings
        rings = self._dev_rings
        if rings is None or rings.mids.shape[0] != n_regions \
                or rings.mids.shape[1] != s_pad:
            rings = DeviceRings.empty(n_regions, s_pad, self.KEEP,
                                      max(edim, 1))
        elif rings.embed_dim < edim:
            rings = rings.grown(edim)
        self._dev_rings = rings
        return rings

    def locality_tracker(self) -> LocalityTracker:
        """All regions' history exported as one legacy tracker
        (debug/interop; scores are exactly equivalent)."""
        tracker = LocalityTracker(keep=self.KEEP)
        if self._dev_rings is not None:
            for ridx in range(self._dev_rings.mids.shape[0]):
                self.locality_state(ridx).to_tracker(ridx, tracker)
            return tracker
        for ridx, lstate in sorted(self._loc.items()):
            lstate.to_tracker(ridx, tracker)
        return tracker

    def _state_for(self, ridx: int, n_servers: int,
                   edim: int) -> LocalityState:
        lstate = self._loc.get(ridx)
        if lstate is None or lstate.n_servers != n_servers:
            lstate = LocalityState.empty(n_servers, self.KEEP,
                                         max(edim, 1))
        elif lstate.embed_dim < edim:
            lstate = lstate.grown(edim)
        self._loc[ridx] = lstate
        return lstate

    def activation_target(self, obs: SlotObs, ridx: int,
                          predicted: float) -> int:
        st = obs.state
        sl = st.region_slice(ridx)
        caps = st.capacity[sl]
        avg_cap = float(np.mean(caps)) if caps.size else 1.0
        return target_active_servers(
            float(obs.queue_tasks[ridx]), predicted, avg_cap,
            sl.stop - sl.start, sigma=self.sigma, headroom=self.headroom)

    def activation_targets(self, obs: SlotObs,
                           pred_inbound: np.ndarray) -> np.ndarray:
        """All regions' Eq-6 targets as one ``(R,)`` array — the api
        activation form, consumed whole by the fused slot step (exact
        per-region parity with :meth:`activation_target`)."""
        r = obs.state.n_regions
        out = np.empty(r, np.int64)
        for j in range(r):
            out[j] = self.activation_target(obs, j, float(pred_inbound[j]))
        return out

    def assign_region(self, obs: SlotObs, ridx: int, tasks: List[Task]
                      ) -> Dict[int, Optional[Tuple[int, int]]]:
        """Object-path entry: sorts ``Task`` objects, packs them into
        arrays, and runs the shared array core."""
        if not tasks:
            return {}
        with obs_rt.span("micro.assign"):
            # urgency (deadline) first, then resource-intensive first
            ordered = sorted(tasks,
                             key=lambda tk: (tk.deadline_slot, tk.model,
                                             -tk.work_s))
            edim = next((tk.embed.shape[0] for tk in ordered
                         if tk.embed is not None), 1)
            embeds = np.stack([tk.embed if tk.embed is not None
                               else np.zeros(edim, np.float32)
                               for tk in ordered])
            servers = self._assign_core(
                obs, ridx,
                mem_t=np.array([tk.mem_gb for tk in ordered]),
                work=np.array([tk.work_s for tk in ordered]),
                mids=np.array([model_id(tk.model) for tk in ordered],
                              np.int16),
                kind_ids=np.array([_KIND_IDX[tk.kind] for tk in ordered],
                                  np.int8),
                embeds=embeds,
                has_embed=np.array([tk.embed is not None
                                    for tk in ordered]),
                norms=np.linalg.norm(embeds, axis=1))
        return {tk.id: ((ridx, int(s)) if s >= 0 else None)
                for tk, s in zip(ordered, servers)}

    def assign_batch_all(self, obs: SlotObs, batch,
                         region_of: np.ndarray) -> np.ndarray:
        """Fused whole-slot entry (``backend="fused"``): assign EVERY
        routed row of the slot's ``TaskBatch`` in one multi-region scan
        dispatch (``core/micro_jax.assign_scan_all``).  ``region_of`` is
        the phase-1 target region per row (-1 = unrouted); returns the
        server-in-region per row (-1 = buffer)."""
        from repro.core.micro_jax import assign_scan_all
        region_of = np.asarray(region_of)
        n = len(batch)
        out = np.full(n, -1, np.int32)
        rows = np.flatnonzero(region_of >= 0)
        if rows.size == 0:
            return out
        self._dev_region_sizes = obs.state.region_sizes()
        with obs_rt.span("micro.assign"):
            # one global sort: region-major, then each region's greedy
            # order (deadline, model name, -work) — stable-chain equal to
            # the per-region lexsort of assign_batch
            work = batch.work_s[rows]
            order = np.lexsort((-work, _MODEL_RANK[batch.model_idx[rows]],
                                batch.deadline_slot[rows],
                                region_of[rows]))
            sidx = rows[order]
            embeds = batch.embeds[sidx]
            norms = np.linalg.norm(embeds, axis=1)
            out[sidx] = assign_scan_all(
                self, obs, region_of[sidx],
                mem_t=batch.mem_gb[sidx], work=work[order],
                mids=batch.model_idx[sidx].astype(np.int16),
                kind_ids=batch.kind_id[sidx], embeds=embeds,
                has_embed=norms > 0.0, norms=norms)
        return out

    def assign_batch(self, obs: SlotObs, ridx: int, batch,
                     idx: np.ndarray) -> np.ndarray:
        """Batch-native entry: assign rows ``idx`` of a ``TaskBatch`` to
        region ``ridx``; returns server-in-region per row of ``idx``
        (-1 = buffer).  No Task objects are materialized."""
        idx = np.asarray(idx)
        if idx.size == 0:
            return np.zeros(0, np.int32)
        with obs_rt.span("micro.assign"):
            work = batch.work_s[idx]
            # same ordering as the object path:
            # (deadline, model name, -work)
            order = np.lexsort((-work, _MODEL_RANK[batch.model_idx[idx]],
                                batch.deadline_slot[idx]))
            sidx = idx[order]
            embeds = batch.embeds[sidx]
            norms = np.linalg.norm(embeds, axis=1)
            servers = self._assign_core(
                obs, ridx,
                mem_t=batch.mem_gb[sidx], work=work[order],
                mids=batch.model_idx[sidx].astype(np.int16),
                kind_ids=batch.kind_id[sidx], embeds=embeds,
                # a zero row is TaskBatch's encoding of "no embedding"
                # (from_tasks of embed=None tasks) — match the object path
                has_embed=norms > 0.0, norms=norms)
            out = np.full(idx.size, -1, np.int32)
            out[order] = servers
        return out

    def _assign_core(self, obs: SlotObs, ridx: int, *, mem_t: np.ndarray,
                     work: np.ndarray, mids: np.ndarray,
                     kind_ids: np.ndarray, embeds: np.ndarray,
                     has_embed: np.ndarray,
                     norms: np.ndarray) -> np.ndarray:
        """Greedy walk over pre-sorted task arrays; returns per-task
        server index within the region (-1 = buffer)."""
        st = obs.state
        sl = st.region_slice(ridx)
        active = st.state[sl] == ACTIVE
        n = len(work)
        out = np.full(n, -1, np.int32)
        if n == 0 or not active.any():
            return out
        slot_s = obs.slot_seconds
        if self.backend == "fused":
            # single-region call through the multi-region scan (the
            # whole-slot path is assign_batch_all; this keeps the
            # per-region API — tests, legacy/sticky callers — on the
            # same device-resident rings)
            from repro.core.micro_jax import assign_scan_all
            self._dev_region_sizes = st.region_sizes()
            return assign_scan_all(
                self, obs, np.full(n, ridx, np.int64), mem_t=mem_t,
                work=work, mids=mids, kind_ids=kind_ids, embeds=embeds,
                has_embed=has_embed, norms=norms)
        lstate = self._state_for(ridx, sl.stop - sl.start,
                                 embeds.shape[1])

        if self.backend == "jax":
            from repro.core.micro_jax import assign_scan
            return assign_scan(self, obs, ridx, lstate, mem_t=mem_t,
                               work=work, mids=mids, kind_ids=kind_ids,
                               embeds=embeds, has_embed=has_embed,
                               norms=norms)

        # per-server arrays (region slice)
        mem_s = st.mem_gb[sl]
        speed = np.maximum(st.tflops[sl] / 112.0, 0.1)
        cur = st.current_model[sl]

        # ---- the single batched (N x S) score-matrix call ----
        tf = task_feature_arrays(kind_ids, mem_t)
        sf = server_feature_matrix(st, sl, slot_s)
        loc_cache: dict = {}
        loc0 = np.stack([lstate.column(
            i, mids, embeds, norms, has_embed, obs.t, cache=loc_cache)
            for i in range(sl.stop - sl.start)], axis=1)
        hwl = hw_load_matrix(tf, sf, backend=self.backend,
                             interpret=self.interpret)
        base = hwl + W_LOC * loc0

        warm_hit = st.warm_hit_matrix(mids, sl)
        warm = np.where(cur[None, :] == mids[:, None], 1.0,
                        np.where(warm_hit, 0.4, 0.0))
        static = base + W_WARM * warm
        exec_pen = 0.3 * (work[:, None] / speed[None, :]) / slot_s

        mem_ok = mem_s[None, :] >= mem_t[:, None]
        proj = st.queue_s[sl].astype(np.float64)
        for i in range(n):
            eligible = active & mem_ok[i] & (proj <= 16.0 * slot_s)
            if not eligible.any():
                continue                       # buffer (§V-C2 buffering)
            # projected wait penalty — superlinear so warm-model stickiness
            # can never hold a backlogged server (a switch costs ~0.5 slot;
            # waiting >1.5 slots must dominate it)
            q_slots = proj / slot_s
            sc = (static[i] - (0.8 * q_slots + 0.4 * q_slots * q_slots)
                  ) - exec_pen[i]
            sc = np.where(eligible, sc, -np.inf)
            best = int(np.argmax(sc))
            g = sl.start + best
            proj[best] += work[i] / speed[best] \
                + st.switch_cost(g, int(mids[i]))
            self._uid += 1
            lstate.note(best, int(mids[i]),
                        embeds[i] if has_embed[i] else None,
                        obs.t, self._uid)
            # within-slot locality update: refresh this server's column so
            # later tasks see the just-placed history (linear term)
            new_col = lstate.column(best, mids, embeds, norms, has_embed,
                                    obs.t, cache=loc_cache)
            static[:, best] = (hwl[:, best] + W_LOC * new_col) \
                + W_WARM * warm[:, best]
            out[i] = best
        return out
