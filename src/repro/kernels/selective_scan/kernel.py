"""Chunked Mamba-1 selective-scan kernel.

Grid: (B, n_dblocks, n_chunks) — chunks are sequential ("arbitrary"); the
recurrent state h (d_block, N) lives in VMEM scratch and carries across
chunks.  Within a chunk the recurrence runs as an in-register fori_loop —
on TPU the (d_block, N) elementwise updates map onto the VPU while the
chunk's inputs stream HBM->VMEM once.  Discretization (exp(dt*A), dt*B*x)
happens in-kernel in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(dt_ref, bm_ref, cm_ref, x_ref, a_ref, d_ref, y_ref, h_sc, *,
            chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_sc[...] = jnp.zeros_like(h_sc)

    a = a_ref[...].astype(jnp.float32)            # (db, N)
    d_skip = d_ref[...].astype(jnp.float32)       # (1, db)

    def step(s, h):
        dt = dt_ref[0, s].astype(jnp.float32)     # (db,)
        bm = bm_ref[0, s].astype(jnp.float32)     # (N,)
        cm = cm_ref[0, s].astype(jnp.float32)     # (N,)
        x = x_ref[0, s].astype(jnp.float32)       # (db,)
        abar = jnp.exp(dt[:, None] * a)           # (db, N)
        bx = (dt * x)[:, None] * bm[None, :]
        h = abar * h + bx
        y = (h * cm[None, :]).sum(-1) + d_skip[0] * x
        y_ref[0, s] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_sc[...])
    h_sc[...] = h


@functools.partial(jax.jit,
                   static_argnames=("chunk", "d_block", "interpret"))
def selective_scan(dt: jax.Array, bm: jax.Array, cm: jax.Array, x: jax.Array,
                   a: jax.Array, d_skip: jax.Array, *, chunk: int = 128,
                   d_block: int = 512, interpret: bool = False) -> jax.Array:
    """dt, x: (B, S, d_in); bm, cm: (B, S, N); a: (d_in, N); d_skip: (d_in,).
    Returns y: (B, S, d_in) = SSM(x) + D*x (pre-gate)."""
    b, s, d_in = x.shape
    n = a.shape[-1]
    db = min(d_block, d_in)
    assert d_in % db == 0, (d_in, db)
    nd = d_in // db
    ch = min(chunk, s)
    n_chunks = -(-s // ch)
    pad = n_chunks * ch - s
    if pad:
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    d2 = d_skip[None, :]

    kernel = functools.partial(_kernel, chunk=ch, n_chunks=n_chunks)
    y = pl.pallas_call(
        kernel,
        grid=(b, nd, n_chunks),
        in_specs=[
            pl.BlockSpec((1, ch, db), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, ch, n), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((1, ch, n), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((1, ch, db), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((db, n), lambda bi, di, ci: (di, 0)),
            pl.BlockSpec((1, db), lambda bi, di, ci: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, ch, db), lambda bi, di, ci: (bi, ci, di)),
        out_shape=jax.ShapeDtypeStruct((b, n_chunks * ch, d_in), x.dtype),
        scratch_shapes=[pltpu.VMEM((db, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(dt, bm, cm, x, a, d2)
    return y[:, :s]
