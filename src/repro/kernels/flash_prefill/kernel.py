"""Flash prefill-attention kernel: causal (optionally sliding-window) GQA
attention over full sequences.

Grid: (B, KH, n_q, n_kv) — the KV dim is sequential ("arbitrary"); running
(max, denom, accum) scratch per q-block persists across KV blocks.  Blocks
entirely above the causal diagonal (or outside the window) are skipped with
``pl.when``, so the kernel does ~half the MXU work of a dense S x S pass —
the TPU analogue of the masked-block skipping in GPU flash attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            bq: int, bk: int, n_kv: int, window, s_valid: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = qi * bq
    k_start = ki * bk
    # causal: a kv block contributes iff its first key can be attended by
    # the q block's last query; window: iff its last key is within reach
    relevant = k_start <= q_start + bq - 1
    if window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + bk - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0]                          # (G, bq, hd)
        k = k_ref[0, 0]                          # (bk, hd)
        v = v_ref[0, 0]                          # (bk, hd)
        hd = q.shape[-1]
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((2,), (1,)), ((), ())))            # (G, bq, bk)
        s = s * (hd ** -0.5)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos <= qpos
        ok = jnp.logical_and(ok, kpos < s_valid)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = s + jnp.where(ok, 0.0, NEG_INF)[None]
        m_prev = m_sc[...]                       # (G, bq)
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(-1)
        acc_sc[...] = acc_sc[...] * corr[..., None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (0,)), ((), ()))
        ).astype(jnp.float32)
        m_sc[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)[..., None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "window",
                                             "interpret"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window=None, block_q: int = 256, block_k: int = 256,
                  interpret: bool = False) -> jax.Array:
    """q: (B, KH, G, S, hd); k, v: (B, KH, S, hd) -> (B, KH, G, S, hd).

    Causal self-attention with optional sliding window."""
    b, kh, g, s, hd = q.shape
    bq = min(block_q, s)
    bk = min(block_k, s)
    n_q = -(-s // bq)
    n_kv = -(-s // bk)
    pad_q = n_q * bq - s
    pad_k = n_kv * bk - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(_kernel, bq=bq, bk=bk, n_kv=n_kv,
                               window=window, s_valid=s)
    out = pl.pallas_call(
        kernel,
        grid=(b, kh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, bq, hd),
                         lambda bi, hi, qi, ki: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, bq, hd),
                               lambda bi, hi, qi, ki: (bi, hi, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, n_q * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, bq), jnp.float32),
            pltpu.VMEM((g, bq), jnp.float32),
            pltpu.VMEM((g, bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :, :s]
