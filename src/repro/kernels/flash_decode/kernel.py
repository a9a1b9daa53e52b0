"""Flash decode-attention kernel: one query token per sequence against a long
KV cache, blocked over the cache length.

Grid: (B, KH, n_kv_blocks) — the last dim is sequential ("arbitrary"), with
running (max, denom, accum) in VMEM scratch persisting across KV blocks (the
canonical TPU flash pattern: HBM->VMEM streaming of the cache, softmax in
f32, MXU-aligned hd=128 tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_sc, l_sc, acc_sc, *,
            n_blocks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0]                 # (G, hd)
    k = k_ref[0, :, 0, :]           # (bc, hd)
    v = v_ref[0, :, 0, :]           # (bc, hd)
    valid = valid_ref[0]            # (1, bc) int32 mask

    s = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())))      # (G, bc)
    scale = q.shape[-1] ** -0.5
    s = s * scale + jnp.where(valid > 0, 0.0, NEG_INF)     # broadcast (1,bc)

    m_prev = m_sc[...]                                     # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(-1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v).astype(jnp.float32)
    m_sc[...] = m_new

    @pl.when(ci == n_blocks - 1)
    def _done():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 valid: jax.Array, *, block_c: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q: (B, KH, G, hd); caches: (B, C, KH, hd); valid: (B, C) int32.
    Returns (B, KH, G, hd)."""
    b, kh, g, hd = q.shape
    c = k_cache.shape[1]
    bc = min(block_c, c)
    n_blocks = -(-c // bc)
    pad = n_blocks * bc - c
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    valid2 = valid[:, None, :]                               # (B, 1, C)

    kernel = functools.partial(_kernel, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(b, kh, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bc, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, bc, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, bc), lambda bi, hi, ci: (bi, 0, ci)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda bi, hi, ci: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k_cache, v_cache, valid2)
