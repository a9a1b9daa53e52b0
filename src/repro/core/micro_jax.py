"""JIT-native micro greedy matching — ``lax.scan`` over the task axis.

This is the ``backend="jax"`` implementation of
``MicroAllocator._assign_core``: one jit-compiled pipeline that builds the
full (N, S) Eq 7-10 score matrix, then scans the pre-sorted task axis with
the warm bonus, projected-wait penalty, exec-time term and within-slot
locality column refresh expressed as whole-array updates inside the scan
body.  The per-task Python loop of the numpy oracle disappears entirely;
locality history is carried through the scan as the fixed-shape
``LocalityState`` arrays (``core/micro_state.py``).

Numerics mirror the numpy oracle op for op (float64 math under a local
``enable_x64`` scope, float32 embedding dots cast to float64, identical
accumulation order, first-index argmax tie-breaking), so assignments are
identical to ``backend="numpy"`` up to BLAS-vs-XLA last-ulp dot rounding —
pinned by the randomized parity sweep in ``tests/test_micro_jit.py``.

Pad-and-mask retrace policy: the task axis is padded to a shape bucket
(powers of two below 256, multiples of 256 above) and padded rows are
masked out of eligibility, so each run compiles only a handful of
distinct ``(N_pad, S)`` scan shapes instead of retracing per slot.  The
static score base can optionally come from the fused
``kernels/compat_score`` Pallas kernel (float32; compiled for the TPU,
interpreted only when the allocator is built with ``interpret=True``)
via ``fused=True``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize
from repro.core.micro_state import EMPTY, LocalityState
from repro.obs import runtime as obs_rt

_F64 = jnp.float64


def bucket(n: int) -> int:
    """Pad size for the task axis: powers of two below 256, multiples of
    256 above — a handful of distinct compiled shapes per run."""
    if n <= 16:
        return 16
    if n < 256:
        return 1 << (n - 1).bit_length()
    return 256 * (-(-n // 256))


def _loc_consts():
    from repro.core.micro import LOC_DECAY, W_EMBED, W_LOC, W_MODEL, W_WARM
    return W_MODEL, W_EMBED, W_LOC, W_WARM, LOC_DECAY


def _entry_contrib_tail(model_eq, dots, denom, ok, e_slots, e_mids, t):
    """The parity-critical per-entry Eq-10 op/dtype sequence of
    ``LocalityState.column``, shared by both scan bodies and applied to
    pre-broadcast operands (per-region scan: (N, K) with the entry axis
    broadcast; fused multi-region scan: (R, S, K))."""
    w_model, w_embed, _, _, loc_decay = _loc_consts()
    sim = w_model * model_eq.astype(_F64)
    safe = jnp.where(ok, denom.astype(_F64), 1.0)
    sim = sim + jnp.where(ok, w_embed * dots.astype(_F64) / safe, 0.0)
    age = jnp.clip(t - e_slots, 0, 40).astype(_F64)
    contrib = sim / jnp.exp(loc_decay * age)
    return jnp.where(e_mids != EMPTY, contrib, 0.0)


def _entry_contribs(task_mids, task_embeds, task_norms, task_has,
                    e_mids, e_slots, e_embeds, e_norms, t):
    """(N, K) per-history-entry Eq-10 contributions of one server's ring
    vs every task (same ops/dtypes as ``LocalityState.column``)."""
    model_eq = task_mids[:, None] == e_mids[None, :]
    dots = task_embeds @ e_embeds.T                       # (N, K) float32
    denom = task_norms[:, None] * e_norms[None, :]        # float32
    ok = task_has[:, None] & (denom > 1e-9)
    return _entry_contrib_tail(model_eq, dots, denom, ok,
                               e_slots[None, :], e_mids[None, :], t)


def _sum_newest_first(contrib):
    """Sum the keep axis in ring order (matches the numpy accumulation)."""
    col = contrib[..., 0]
    for k in range(1, contrib.shape[-1]):
        col = col + contrib[..., k]
    return col


@jax.jit
def _scan_assign(base, warmterm, loc_mids, loc_slots, loc_embeds,
                 loc_norms, proj0, active, mem_ok, exec_pen, add_cost,
                 task_mids, task_embeds, task_norms, task_has, note_norms,
                 t, slot_s, n_real):
    """Jitted greedy walk.  ``base`` is the hw+load static part (N, S);
    the locality term and warm bonus are layered on inside, and the
    within-slot locality refresh is a whole-column update per step."""
    _, _, w_loc, _, _ = _loc_consts()
    n_pad = base.shape[0]

    # initial locality matrix: the per-server entry contributions vmapped
    # over the server axis -> (N, S, K), summed in ring order
    loc0 = _sum_newest_first(jax.vmap(
        _entry_contribs,
        in_axes=(None, None, None, None, 0, 0, 0, 0, None),
        out_axes=1)(task_mids, task_embeds, task_norms, task_has,
                    loc_mids, loc_slots, loc_embeds, loc_norms, t))

    static0 = (base + w_loc * loc0) + warmterm

    def body(carry, i):
        proj, static, l_mids, l_slots, l_emb, l_nrm = carry
        eligible = (active & mem_ok[i] & (proj <= 16.0 * slot_s)
                    & (i < n_real))
        any_e = eligible.any()
        q = proj / slot_s
        sc = (static[i] - (0.8 * q + 0.4 * q * q)) - exec_pen[i]
        sc = jnp.where(eligible, sc, -jnp.inf)
        best = jnp.argmax(sc)

        proj = proj.at[best].add(jnp.where(any_e, add_cost[i, best], 0.0))

        # ring push on the chosen server (newest-first shift)
        nm = jnp.concatenate([task_mids[i][None], l_mids[best, :-1]])
        ns = jnp.concatenate([t[None], l_slots[best, :-1]])
        ne = jnp.concatenate([jnp.where(task_has[i], task_embeds[i],
                                        0.0)[None], l_emb[best, :-1]])
        nn = jnp.concatenate([jnp.where(task_has[i], note_norms[i],
                                        0.0)[None], l_nrm[best, :-1]])

        # within-slot locality refresh of the chosen server's column
        col = _sum_newest_first(_entry_contribs(
            task_mids, task_embeds, task_norms, task_has, nm, ns, ne, nn,
            t))
        new_col = (base[:, best] + w_loc * col) + warmterm[:, best]

        keep_row = ~any_e
        l_mids = l_mids.at[best].set(jnp.where(keep_row, l_mids[best], nm))
        l_slots = l_slots.at[best].set(
            jnp.where(keep_row, l_slots[best], ns))
        l_emb = l_emb.at[best].set(jnp.where(keep_row, l_emb[best], ne))
        l_nrm = l_nrm.at[best].set(jnp.where(keep_row, l_nrm[best], nn))
        static = static.at[:, best].set(
            jnp.where(any_e, new_col, static[:, best]))

        out_i = jnp.where(any_e, best.astype(jnp.int32), -1)
        return (proj, static, l_mids, l_slots, l_emb, l_nrm), out_i

    carry0 = (proj0, static0, loc_mids, loc_slots, loc_embeds, loc_norms)
    (_, _, l_mids, l_slots, l_emb, l_nrm), out = jax.lax.scan(
        body, carry0, jnp.arange(n_pad))
    return out, l_mids, l_slots, l_emb, l_nrm


def assign_scan(alloc, obs, ridx: int, lstate: LocalityState, *,
                mem_t: np.ndarray, work: np.ndarray, mids: np.ndarray,
                kind_ids: np.ndarray, embeds: np.ndarray,
                has_embed: np.ndarray, norms: np.ndarray) -> np.ndarray:
    """Host-side wrapper: builds the padded operand set, runs the jitted
    scan under a local float64 scope, and writes the scanned locality
    rings back into ``lstate``.  Returns per-task server index (-1 =
    buffer), identical to the numpy ``_assign_core``."""
    from repro.core import micro
    st = obs.state
    sl = st.region_slice(ridx)
    n = len(work)
    slot_s = obs.slot_seconds
    active = st.state[sl] == micro.ACTIVE

    # reconcile embed widths: a slot whose tasks carry no/narrower
    # embeddings still scans against a wider carried ring — zero-pad the
    # task side (exact: the extra dot terms are 0.0, matching the numpy
    # path's history slice to the task width)
    if embeds.shape[1] < lstate.embed_dim:
        embeds = np.pad(embeds,
                        ((0, 0), (0, lstate.embed_dim - embeds.shape[1])))

    speed = np.maximum(st.tflops[sl] / 112.0, 0.1)
    cur = st.current_model[sl]
    tf = micro.task_feature_arrays(kind_ids, mem_t)
    sf = micro.server_feature_matrix(st, sl, slot_s)
    warm_hit = st.warm_hit_matrix(mids, sl)
    warm = np.where(cur[None, :] == mids[:, None], 1.0,
                    np.where(warm_hit, 0.4, 0.0))

    if alloc.fused:
        # fused Pallas kernel computes hw+load+warm in one pass (float32);
        # the warm term is inside `base`, so warmterm stays zero
        from repro.kernels.compat_score import fused_score
        server_models = np.concatenate(
            [cur[:, None], st.warm_models[sl]], axis=1)
        base = np.asarray(fused_score(
            jnp.asarray(tf, jnp.float32), jnp.asarray(sf, jnp.float32),
            jnp.asarray(mids, jnp.float32),
            jnp.asarray(server_models, jnp.float32),
            interpret=alloc.interpret)).astype(np.float64)
        warmterm = np.zeros_like(base)
    else:
        base = micro.hw_load_matrix_np(tf, sf)
        warmterm = micro.W_WARM * warm

    exec_pen = 0.3 * (work[:, None] / speed[None, :]) / slot_s
    mem_ok = st.mem_gb[sl][None, :] >= mem_t[:, None]
    add_cost = (work[:, None] / speed[None, :]
                + st.switch_cost_matrix(mids, sl))
    # legacy `note_fields` recomputes each entry's norm from its own row
    # (BLAS 1-D norm), which can differ in the last ulp from the axis norm
    note_norms = np.array([np.linalg.norm(embeds[i]) if has_embed[i]
                           else 0.0 for i in range(n)], np.float32)

    n_pad = bucket(n)
    pad = n_pad - n
    s_total = sl.stop - sl.start
    # the jit cache is keyed by operand shapes: first sighting of a
    # (N_pad, S) bucket this run is the trace/compile
    obs_rt.count_new_shape("micro.retrace.scan", f"{n_pad}x{s_total}")
    obs_rt.count("micro.host_sync.scan")

    def padf(a, fill=0.0):
        width = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
        return np.pad(a, width, constant_values=fill)

    with jax.enable_x64(True):
        out, l_mids, l_slots, l_emb, l_nrm = _scan_assign(
            jnp.asarray(padf(base)), jnp.asarray(padf(warmterm)),
            jnp.asarray(lstate.mids), jnp.asarray(lstate.slots),
            jnp.asarray(lstate.embeds), jnp.asarray(lstate.norms),
            jnp.asarray(st.queue_s[sl].astype(np.float64)),
            jnp.asarray(active), jnp.asarray(padf(mem_ok, False)),
            jnp.asarray(padf(exec_pen)), jnp.asarray(padf(add_cost)),
            jnp.asarray(padf(mids.astype(np.int32))),
            jnp.asarray(padf(embeds.astype(np.float32))),
            jnp.asarray(padf(norms.astype(np.float32))),
            jnp.asarray(padf(has_embed, False)),
            jnp.asarray(padf(note_norms)),
            jnp.asarray(np.int32(obs.t)),
            jnp.asarray(np.float64(slot_s)),
            jnp.asarray(np.int32(n)))
        out = np.asarray(out)[:n]
        new_rings = (np.asarray(l_mids), np.asarray(l_slots),
                     np.asarray(l_emb), np.asarray(l_nrm))
    _writeback(alloc, lstate, new_rings)
    return out.astype(np.int32)


def _writeback(alloc, lstate: LocalityState,
               rings: Tuple[np.ndarray, ...]) -> None:
    """Copy the scanned rings back into the region's ``LocalityState``,
    refreshing uids (cache keys must be unique, not stable) and counts."""
    l_mids, l_slots, l_emb, l_nrm = rings
    lstate.mids[...] = l_mids
    lstate.slots[...] = l_slots
    lstate.embeds[...] = l_emb
    lstate.norms[...] = l_nrm
    lstate.count[...] = (l_mids != EMPTY).sum(axis=1).astype(np.int32)
    n_entries = lstate.uid.size
    lstate.uid[...] = np.arange(alloc._uid + 1, alloc._uid + 1 + n_entries,
                                dtype=np.int64).reshape(lstate.uid.shape)
    alloc._uid += n_entries


# ---------------------------------------------------------------------------
# fused multi-region scan (backend="fused")
# ---------------------------------------------------------------------------
#
# ONE jitted scan covers every region of the slot at once: tasks are padded
# to an (R, N_pad) bucket, servers to (R, S_pad), and the greedy body is
# expressed as whole-(R, S) array work per task step — the per-region
# dispatch loop, the host-built (N, S) feature/switch/warm matrices, and
# the per-slot LocalityState host round-trip all disappear.  Two structural
# differences from the per-region scan above (same math, fewer bytes):
#
# * the static Eq 7-9 score row (hw + load + warm) is computed *inside*
#   the scan body from raw task/server features, so no (N, S) float64
#   operand matrices are ever materialized on the host;
# * the Eq-10 locality term is recomputed per task row from the carried
#   rings instead of carrying a full (N, S) score matrix and refreshing
#   columns — identical values, O(R*S*K) per step instead of an (N, S)
#   carry.
#
# Numerics follow the same float64 op order as the numpy oracle; the only
# divergences from the per-region path are last-ulp (XLA exp/dot rounding
# vs host numpy), pinned by the randomized parity sweep in
# ``tests/test_fused_step.py``.


@dataclasses.dataclass
class DeviceRings:
    """LocalityState for ALL regions as one stacked device-side pytree —
    carried across slots without round-tripping through host numpy.
    Padded server rows (beyond a region's real size) stay EMPTY forever
    (they are never eligible, so the scan never pushes to them)."""

    mids: jax.Array       # (R, S_pad, K) int32
    slots: jax.Array      # (R, S_pad, K) int32
    embeds: jax.Array     # (R, S_pad, K, E) float32
    norms: jax.Array      # (R, S_pad, K) float32

    @property
    def embed_dim(self) -> int:
        return self.embeds.shape[3]

    @classmethod
    def empty(cls, n_regions: int, s_pad: int, keep: int,
              embed_dim: int) -> "DeviceRings":
        return cls(
            mids=jnp.full((n_regions, s_pad, keep), EMPTY, jnp.int32),
            slots=jnp.zeros((n_regions, s_pad, keep), jnp.int32),
            embeds=jnp.zeros((n_regions, s_pad, keep, embed_dim),
                             jnp.float32),
            norms=jnp.zeros((n_regions, s_pad, keep), jnp.float32))

    def grown(self, embed_dim: int) -> "DeviceRings":
        if embed_dim <= self.embed_dim:
            return self
        pad = ((0, 0), (0, 0), (0, 0), (0, embed_dim - self.embed_dim))
        return dataclasses.replace(self, embeds=jnp.pad(self.embeds, pad))

    def region_state(self, ridx: int, n_servers: int) -> LocalityState:
        """Materialize one region's rings as a host ``LocalityState`` —
        a pure getter (lazy sync point for tests/debug).  The device
        rings carry no uids, so export uids are synthesized from a
        deterministic per-region range (``ridx * S_pad * keep`` base):
        unique across regions, stable across repeated calls,
        backend-local like the per-region scan's."""
        mids = np.asarray(self.mids[ridx, :n_servers])
        st = LocalityState(
            mids=mids, slots=np.asarray(self.slots[ridx, :n_servers]),
            embeds=np.asarray(self.embeds[ridx, :n_servers]),
            norms=np.asarray(self.norms[ridx, :n_servers]),
            uid=np.zeros(mids.shape, np.int64),
            count=(mids != EMPTY).sum(axis=1).astype(np.int32))
        base = ridx * self.mids.shape[1] * self.mids.shape[2]
        st.uid[...] = np.arange(base + 1, base + 1 + st.uid.size,
                                dtype=np.int64).reshape(st.uid.shape)
        return st


def _hw_consts():
    from repro.core.micro import _DEMAND_BY_KIND, W_HW, W_LOAD
    return W_HW, W_LOAD, jnp.asarray(_DEMAND_BY_KIND, jnp.float64)


def _switch_consts():
    from repro.sim.state import _WARM_HIT_S
    from repro.sim.cluster import MODEL_SWITCH_S
    return _WARM_HIT_S, MODEL_SWITCH_S


def _scan_assign_multi_impl(tflops, mem_s, kind_s, util0, cur_model,
                            warm_srv, switch_scale, active, proj0, speed,
                            l_mids, l_slots, l_emb, l_nrm, t_mids,
                            t_kinds, t_mem, t_work, t_embeds, t_norms,
                            t_has, n_real, t, slot_s, *,
                            checks: bool = False):
    """The fused multi-region greedy.  Server operands are (R, S_pad),
    task operands (R, N_pad); the scan walks the task axis once and each
    step does whole-(R, S) work: static Eq 7-9 row build, Eq-10 locality
    vs the carried rings, eligibility/argmax, projected-queue push and
    the per-region ring push of the chosen server.

    ``checks=True`` (the ``REPRO_SANITIZE=1`` variant, compiled through
    ``checkify``) validates the carried ring state and queue inputs
    before the scan; ``checks=False`` is the production path and is
    bitwise identical to the historical kernel."""
    if checks:
        from jax.experimental import checkify
        checkify.check(
            jnp.all((l_mids == EMPTY) | (l_mids >= 0)),
            "sanitize: ring mids carry a corrupt model id "
            "(negative but not EMPTY)")
        checkify.check(jnp.all(l_slots >= 0),
                       "sanitize: ring slot timestamps went negative")
        checkify.check(jnp.all(proj0 >= 0.0),
                       "sanitize: negative projected queue depth fed to "
                       "the fused scan")
        checkify.check(jnp.all(jnp.isfinite(l_emb)),
                       "sanitize: non-finite ring embedding entering the "
                       "locality dot")
        checkify.check(jnp.all(jnp.isfinite(t_embeds)),
                       "sanitize: non-finite task embedding entering the "
                       "locality dot")
    _, _, w_loc, w_warm, _ = _loc_consts()
    w_hw, w_load, demand_by_kind = _hw_consts()
    warm_hit_s, model_switch_s = _switch_consts()
    r, n_pad = t_mids.shape
    ar = jnp.arange(r)

    # Eq 9 load term is static during the pass (util/queue snapshot)
    load = jnp.exp(-(util0 + proj0 / jnp.maximum(slot_s, 1e-9)))
    demand = demand_by_kind[t_kinds.astype(jnp.int32)]       # (R, N) f64
    # legacy note_fields recomputes each entry's norm from its own row
    note_norms = jnp.linalg.norm(t_embeds, axis=-1)          # (R, N) f32

    def body(carry, i):
        proj, lm, ls, le, ln = carry
        mid_i = t_mids[:, i]                                 # (R,)
        mem_i = t_mem[:, i]
        work_i = t_work[:, i]
        emb_i = t_embeds[:, i]                               # (R, E)
        norm_i = t_norms[:, i]
        has_i = t_has[:, i]

        # static Eq 7-9 row (numpy-oracle op order, f64)
        c = jnp.minimum(1.0, tflops / demand[:, i][:, None])
        m = jnp.minimum(1.0, mem_s / jnp.maximum(mem_i[:, None], 1e-9))
        tm = jnp.where(kind_s == t_kinds[:, i][:, None], 1.0, 0.5)
        base = w_hw * (c * m * tm) + w_load * load
        warm = jnp.where(
            cur_model == mid_i[:, None], 1.0,
            jnp.where((warm_srv == mid_i[:, None, None]).any(-1), 0.4, 0.0))

        # Eq-10 locality of this task vs every server's carried ring
        model_eq = mid_i[:, None, None] == lm
        dots = jnp.einsum("rske,re->rsk", le, emb_i)         # f32
        denom = norm_i[:, None, None] * ln                   # f32
        ok = has_i[:, None, None] & (denom > 1e-9)
        contrib = _entry_contrib_tail(model_eq, dots, denom, ok, ls, lm, t)
        loc = _sum_newest_first(contrib)                     # (R, S)

        static_i = (base + w_loc * loc) + w_warm * warm
        eligible = (active & (mem_s >= mem_i[:, None])
                    & (proj <= 16.0 * slot_s) & (i < n_real)[:, None])
        any_e = eligible.any(axis=1)
        q = proj / slot_s
        sc = (static_i - (0.8 * q + 0.4 * q * q)) \
            - (0.3 * (work_i[:, None] / speed) / slot_s)
        sc = jnp.where(eligible, sc, -jnp.inf)
        best = jnp.argmax(sc, axis=1)                        # (R,)

        # projected-queue push: work/speed + switch seconds at the choice
        cur_b = cur_model[ar, best]
        warm_b = (warm_srv[ar, best] == mid_i[:, None]).any(-1)
        scale_b = switch_scale[ar, best]
        sw = jnp.where(cur_b == mid_i, 0.0,
                       jnp.where(warm_b, scale_b * warm_hit_s,
                                 scale_b * model_switch_s))
        add = work_i / speed[ar, best] + sw
        proj = proj.at[ar, best].add(jnp.where(any_e, add, 0.0))

        # ring push on each region's chosen server (newest-first shift)
        rowm, rows_ = lm[ar, best], ls[ar, best]             # (R, K)
        rowe, rown = le[ar, best], ln[ar, best]
        nm = jnp.concatenate([mid_i[:, None], rowm[:, :-1]], axis=1)
        ns = jnp.concatenate(
            [jnp.full((r, 1), t, rows_.dtype), rows_[:, :-1]], axis=1)
        ne = jnp.concatenate(
            [jnp.where(has_i[:, None], emb_i, 0.0)[:, None, :],
             rowe[:, :-1]], axis=1)
        nn = jnp.concatenate(
            [jnp.where(has_i, note_norms[:, i], 0.0)[:, None],
             rown[:, :-1]], axis=1)
        keep = ~any_e
        lm = lm.at[ar, best].set(jnp.where(keep[:, None], rowm, nm))
        ls = ls.at[ar, best].set(jnp.where(keep[:, None], rows_, ns))
        le = le.at[ar, best].set(jnp.where(keep[:, None, None], rowe, ne))
        ln = ln.at[ar, best].set(jnp.where(keep[:, None], rown, nn))

        out_i = jnp.where(any_e, best.astype(jnp.int32), -1)
        return (proj, lm, ls, le, ln), out_i

    carry0 = (proj0, l_mids, l_slots, l_emb, l_nrm)
    (_, lm, ls, le, ln), out = jax.lax.scan(body, carry0,
                                            jnp.arange(n_pad))
    return out.T, lm, ls, le, ln                             # out: (R, N_pad)


# Production entry: checks=False compiles to the exact historical jaxpr.
# A named function, not a partial, so the XLA module reads
# ``jit_micro_scan_all`` in a profile.
def micro_scan_all(*operands):
    return _scan_assign_multi_impl(*operands, checks=False)


_scan_assign_multi = jax.jit(micro_scan_all)
# Sanitized entry: module-level partial so sanitize.checkified's cache
# sees a stable identity (one checkify compile per process, not per call).
_scan_assign_multi_checked = functools.partial(_scan_assign_multi_impl,
                                               checks=True)
_SCAN_ALL_ERRORS = "index|float|user"


def server_pad_map(region_ptr: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(R, S_pad) global-index map + validity mask for the padded server
    axis (padded entries alias global index 0 but are masked inactive)."""
    sizes = np.diff(region_ptr)
    s_pad = max(int(sizes.max()), 1) if sizes.size else 1
    idx = region_ptr[:-1, None] + np.arange(s_pad)[None, :]
    valid = np.arange(s_pad)[None, :] < sizes[:, None]
    return np.where(valid, idx, 0), valid


def assign_scan_all(alloc, obs, ridx_rows: np.ndarray, *, mem_t, work, mids,
                    kind_ids, embeds, has_embed, norms) -> np.ndarray:
    """Host wrapper for the fused multi-region scan.  ``ridx_rows[i]`` is
    the target region of row ``i``; rows must already be in each region's
    greedy order (urgency-first — the caller's lexsort).  Returns the
    per-row server index within its region (-1 = buffer).  The locality
    rings live in ``alloc._dev_rings`` as a device-side pytree and never
    visit the host."""
    st = obs.state
    r = st.n_regions
    n = len(work)
    if n == 0:
        return np.zeros(0, np.int32)
    slot_s = obs.slot_seconds

    with obs_rt.span("micro.pack"):
        gmap, valid = server_pad_map(st.region_ptr)
        s_pad = gmap.shape[1]
        edim = max(embeds.shape[1] if n else 1, 1)
        rings = alloc._ensure_dev_rings(r, s_pad, edim)
        if embeds.shape[1] < rings.embed_dim:
            embeds = np.pad(embeds,
                            ((0, 0), (0, rings.embed_dim - embeds.shape[1])))

        counts = np.bincount(ridx_rows, minlength=r)
        n_pad = bucket(int(counts.max()))
        obs_rt.count_new_shape("micro.retrace.scan_all",
                               f"{r}x{n_pad}x{s_pad}x{rings.embed_dim}")

        # position of each row within its region (appearance order kept)
        sort_idx = np.argsort(ridx_rows, kind="stable")
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        pos = np.empty(n, np.int64)
        pos[sort_idx] = np.arange(n) - starts[ridx_rows[sort_idx]]

        def scatter(values, fill=0.0, dtype=None):
            out = np.full((r, n_pad) + values.shape[1:], fill,
                          dtype or values.dtype)
            out[ridx_rows, pos] = values
            return out

        # the host operands, in call order around the device rings
        servers = (
            st.tflops[gmap], st.mem_gb[gmap],
            st.kind_id[gmap].astype(np.int32), st.util[gmap],
            st.current_model[gmap].astype(np.int32),
            st.warm_models[gmap].astype(np.int32), st.switch_scale[gmap],
            (st.state[gmap] == _active_code()) & valid,
            np.where(valid, st.queue_s[gmap], 0.0).astype(np.float64),
            # host numpy: XLA turns /112.0 into a reciprocal multiply
            # (last-ulp off the numpy oracle's true division)
            np.maximum(st.tflops[gmap] / 112.0, 0.1))
        tasks = (
            scatter(mids.astype(np.int32)),
            scatter(kind_ids.astype(np.int32)),
            scatter(mem_t.astype(np.float64)),
            scatter(work.astype(np.float64)),
            scatter(embeds.astype(np.float32)),
            scatter(norms.astype(np.float32)),
            scatter(has_embed, fill=False, dtype=bool),
            counts.astype(np.int64), np.int32(obs.t), np.float64(slot_s))

    if sanitize.enabled():
        scan_fn = sanitize.checkified(_scan_assign_multi_checked,
                                      errors=_SCAN_ALL_ERRORS)
        obs_rt.count("micro.sanitize.scan_all")
    else:
        scan_fn = _scan_assign_multi
    obs_rt.count_transfer("h2d", "micro", lambda: servers + tasks)
    with jax.enable_x64(True):
        with obs_rt.span("micro.upload"):
            out, lm, ls, le, ln = scan_fn(
                *[jnp.asarray(a) for a in servers],
                rings.mids, rings.slots, rings.embeds, rings.norms,
                *[jnp.asarray(a) for a in tasks])
        alloc._dev_rings = DeviceRings(mids=lm, slots=ls, embeds=le,
                                       norms=ln)
        obs_rt.count("micro.host_sync.scan_all")
        with obs_rt.span("micro.host_sync"):
            out_np = np.asarray(out)  # the one device->host sync per slot
    obs_rt.count_transfer("d2h", "micro", lambda: (out_np,))
    return out_np[ridx_rows, pos].astype(np.int32)


def _active_code() -> int:
    from repro.sim.state import ACTIVE
    return ACTIVE
