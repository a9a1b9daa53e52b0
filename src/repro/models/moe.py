"""Mixture-of-Experts FFN with expert-parallel execution.

Design (TPU-native, see DESIGN.md §4):
- Token activations are sharded over the data axes and *replicated* over the
  ``model`` axis (megatron-TP convention).  Experts live on the ``model``
  axis when ``num_experts % model_size == 0`` (expert parallelism); each rank
  computes its local experts' contribution for the replicated tokens and the
  results are ``psum``-reduced over ``model`` — the same traffic class as a
  row-parallel matmul, with no gather of routed tokens across data shards.
- When experts don't divide the model axis (mixtral 8e on 16-way TP) the
  expert FFN hidden dim is tensor-parallel instead (``w_*`` sharded on F),
  and the psum plays the usual row-parallel role.
- Dispatch inside a rank is static-shape sort-based with capacity
  ``C = ceil(t·k/E · cf)`` (tokens over capacity are dropped, Switch-style;
  decode-sized batches use C = t·k so nothing drops).

The local routed-FFN math lives in :func:`moe_ffn_local` — also the oracle
used by tests — and is wrapped in ``shard_map`` when a mesh is present.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig, MoEConfig
from repro.models.params import ParamDesc
from repro.sharding.specs import AxisRules, batch_axes


def moe_param_descs(cfg: ArchConfig, rules: AxisRules) -> Dict:
    m = cfg.moe
    d, e, f = cfg.d_model, m.num_experts, m.d_ff_expert
    ep = rules.expert_axis
    expert_parallel = rules.mesh is None or rules.divisible(e, ep)
    if expert_parallel:
        espec, fspec = ep, None
        # FSDP storage sharding of the big expert tensors over data when asked
        dspec = "data" if (rules.fsdp and rules.divisible(f, "data")) else None
        w_in = P(espec, None, dspec)
        w_out = P(espec, dspec, None)
    else:
        w_in = P(None, None, ep)
        w_out = P(None, ep, None)
    return {
        "router": ParamDesc((d, e), P(None, None)),
        "w_gate": ParamDesc((e, d, f), w_in),
        "w_up": ParamDesc((e, d, f), w_in),
        "w_down": ParamDesc((e, f, d), w_out),
    }


def _routing(router: jax.Array, x: jax.Array, m: MoEConfig
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (t, D) -> (weights (t,k), experts (t,k) int32, aux scalar)."""
    logits = jnp.einsum("td,de->te", x, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, m.top_k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)  # renormalize
    # Switch-style load-balance aux: E * sum_e f_e * p_e
    e = m.num_experts
    me = probs.mean(0)                                   # (E,)
    fe = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    fe = fe / jnp.maximum(fe.sum(), 1.0)
    aux = e * jnp.sum(fe * me)
    return vals.astype(x.dtype), idx.astype(jnp.int32), aux


def moe_ffn_local(p: Dict, x: jax.Array, m: MoEConfig, act,
                  *, expert_offset: int = 0, local_experts: Optional[int] = None,
                  capacity: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Routed expert FFN on local tokens for experts
    [expert_offset, expert_offset + local_experts).

    x: (t, D).  Returns (y (t, D) — contribution of the local experts only,
    aux load-balance loss)."""
    t, d = x.shape
    e = m.num_experts
    le = local_experts if local_experts is not None else p["w_gate"].shape[0]
    weights, experts, aux = _routing(p["router"], x, m)   # (t,k)
    k = m.top_k
    tk = t * k
    if capacity is None:
        capacity = tk if tk <= 512 else max(8, int(tk / e * m.capacity_factor))
    c = min(capacity, tk)

    flat_expert = experts.reshape(-1)                    # (tk,)
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_w = weights.reshape(-1)
    # stable sort by expert id -> position within expert
    order = jnp.argsort(flat_expert, stable=True)
    sorted_e = flat_expert[order]
    # rank within the run of equal expert ids
    pos_in_e = jnp.arange(tk) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    # local expert index (drop non-local and over-capacity)
    le_idx = sorted_e - expert_offset
    keep = (le_idx >= 0) & (le_idx < le) & (pos_in_e < c)
    safe_le = jnp.where(keep, le_idx, 0)
    safe_pos = jnp.where(keep, pos_in_e, c - 1)
    src_tok = flat_token[order]
    gathered = jnp.where(keep[:, None], x[src_tok], 0.0)
    buf = jnp.zeros((le, c, d), x.dtype)
    buf = buf.at[safe_le, safe_pos].add(gathered)        # unique slots -> set
    # expert FFN: (le, c, d) x (le, d, f)
    wg = jax.lax.dynamic_slice_in_dim(p["w_gate"], 0, le, 0) if p["w_gate"].shape[0] != le else p["w_gate"]
    wu = p["w_up"][:le] if p["w_up"].shape[0] != le else p["w_up"]
    wd = p["w_down"][:le] if p["w_down"].shape[0] != le else p["w_down"]
    h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum("ecd,edf->ecf", buf, wu)
    y_e = jnp.einsum("ecf,efd->ecd", h, wd)              # (le, c, d)
    # combine back
    contrib = y_e[safe_le, safe_pos] * (flat_w[order] * keep)[:, None]
    y = jnp.zeros((t, d), x.dtype).at[src_tok].add(contrib.astype(x.dtype))
    return y, aux


def moe_ffn(p: Dict, x: jax.Array, cfg: ArchConfig, rules: AxisRules, act
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux). Dispatches to shard_map expert-parallel when
    a mesh with a >1 ``model`` axis is active and experts divide it."""
    m = cfg.moe
    b, s, d = x.shape
    mesh = rules.mesh
    ep = rules.expert_axis
    if mesh is None or rules.axis_size(ep) == 1:
        y, aux = moe_ffn_local(p, x.reshape(-1, d), m, act)
        return y.reshape(b, s, d), aux

    ep_size = rules.axis_size(ep)
    expert_parallel = rules.divisible(m.num_experts, ep)
    le = m.num_experts // ep_size if expert_parallel else m.num_experts
    ba = batch_axes(rules)
    # batch shards over data only when divisible (long_500k B=1 replicates)
    b_ok = b % max(rules.axis_size(ba), 1) == 0
    dspec = P(ba, None, None) if b_ok else P(None, None, None)

    # Decode-scale 2D expert sharding: weights stay (experts x model,
    # F x data) resident — replicating the tiny token batch (<=2 MB) beats
    # re-gathering tens of GB of FSDP-sharded experts every step
    # (EXPERIMENTS.md §Perf iteration B).
    tokens_global = b * s
    if (expert_parallel and rules.fsdp and tokens_global <= 2048
            and isinstance(ba, str)
            and rules.divisible(m.d_ff_expert, "data")):
        def body2d(router, wg, wu, wd, xl):
            x_all = jax.lax.all_gather(xl, ba, axis=0, tiled=True)
            t = x_all.shape[0] * x_all.shape[1]
            rank = jax.lax.axis_index(ep)
            pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
            y, aux = moe_ffn_local(pl, x_all.reshape(t, d), m, act,
                                   expert_offset=rank * le,
                                   local_experts=le)
            y = jax.lax.psum(y, (ba, ep))          # F-parts + expert groups
            sh = jax.lax.axis_size(ba)
            y = jax.lax.dynamic_slice_in_dim(      # back to the local slice
                y, jax.lax.axis_index(ba) * (t // sh), t // sh, 0)
            return y.reshape(xl.shape), jax.lax.pmean(aux, ba)

        w_in = P(ep, None, "data")
        w_out = P(ep, "data", None)
        y, aux = shard_map(
            body2d, mesh=mesh,
            in_specs=(P(None, None), w_in, w_in, w_out, dspec),
            out_specs=(dspec, P()),
            check_vma=False,
        )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
        return y, aux

    def body(router, wg, wu, wd, xl):
        # xl: tokens local to this data shard, replicated over model axis.
        # Dispatch is LOCAL (never crosses data shards — under plain pjit
        # the global argsort/gather costs an all-gather of every routed
        # token per layer; see EXPERIMENTS.md §Perf iteration A).
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        tl = xl.shape[0] * xl.shape[1]
        if expert_parallel:
            # experts sharded over `model`: each rank computes its experts
            rank = jax.lax.axis_index(ep)
            y, aux = moe_ffn_local(pl, xl.reshape(tl, xl.shape[-1]), m, act,
                                   expert_offset=rank * le,
                                   local_experts=le)
        else:
            # tensor-parallel experts: every rank holds an F-slice of all
            # experts; the nonlinearity is elementwise over F so slices are
            # exact, and the down-projection is partial-summed -> psum.
            y, aux = moe_ffn_local(pl, xl.reshape(tl, xl.shape[-1]), m, act,
                                   expert_offset=0, local_experts=le)
        y = jax.lax.psum(y, ep)
        aux = jax.lax.pmean(aux, ba)   # mean over data axes (str or tuple)
        return y.reshape(xl.shape), aux

    if expert_parallel:
        w_in = P(ep, None, None)
        w_out = P(ep, None, None)
    else:
        w_in = P(None, None, ep)
        w_out = P(None, ep, None)
    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), w_in, w_in, w_out, dspec),
        out_specs=(dspec, P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return y, aux
