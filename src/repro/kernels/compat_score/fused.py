"""Fused task-server score kernel: hw + load + warm (+ locality) in one pass.

Extends the base ``compat_score`` kernel with the warm-model bonus so the
scanned micro backend (``core/micro_jax.py``) can consume one (N, S)
static score matrix straight off the accelerator:

  score = w1 * hw + w2 * load + w_warm * warm [+ w3 * locality]
  warm  = 1.0 if server's current model == task model
          0.4 if the task model is in the server's warm cache
          0.0 otherwise

Operands (model ids are float32-encoded ints; exact below 2^24):

  task_feats    (N, 8)  as in ``kernel.py``
  server_feats  (S, 8)  as in ``kernel.py``
  task_mids     (N,)    task model id
  server_models (S, 1+W) [current model, warm cache x W]
  locality      (N, S)  optional precomputed Eq-10 term

Compiles for the TPU (the CPU tests pass ``interpret=True``); the numpy oracle
is ``core.micro.hw_load_matrix_np`` plus the allocator's warm matrix
(pinned in ``tests/test_micro_jit.py``), the jnp oracle is
``ref.fused_score_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat_score.kernel import (W_LOC, _hw_load_tile
                                               as _hw_load)

W_WARM = 2.0          # same-model (no-switch) bonus, mirrors core.micro


def _warm(mid_col, sm):
    """(bn, bs) warm bonus from the transposed (1+W, bs) model strip."""
    cur = sm[0:1, :]
    hit = mid_col == sm[1:2, :]
    for w in range(2, sm.shape[0]):
        hit = hit | (mid_col == sm[w:w + 1, :])
    return jnp.where(mid_col == cur, 1.0,
                     jnp.where(hit, 0.4, 0.0))


def _fused_kernel(t_ref, s_ref, tm_ref, sm_ref, o_ref):
    tf = t_ref[...].astype(jnp.float32)                    # (bn, 8)
    sf = s_ref[...].astype(jnp.float32)                    # (8, bs)
    mid = tm_ref[...].astype(jnp.float32)                  # (bn, 1)
    sm = sm_ref[...].astype(jnp.float32)                   # (1+W, bs)
    score = _hw_load(tf, sf) + W_WARM * _warm(mid, sm)
    o_ref[...] = score.astype(o_ref.dtype)


def _fused_kernel_loc(t_ref, s_ref, tm_ref, sm_ref, loc_ref, o_ref):
    tf = t_ref[...].astype(jnp.float32)
    sf = s_ref[...].astype(jnp.float32)
    mid = tm_ref[...].astype(jnp.float32)
    sm = sm_ref[...].astype(jnp.float32)
    loc = loc_ref[...].astype(jnp.float32)
    score = (_hw_load(tf, sf) + W_WARM * _warm(mid, sm) + W_LOC * loc)
    o_ref[...] = score.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_s",
                                             "interpret"))
def fused_score(task_feats: jax.Array, server_feats: jax.Array,
                task_mids: jax.Array, server_models: jax.Array,
                locality: jax.Array | None = None, *,
                block_n: int = 256, block_s: int = 256,
                interpret: bool = False) -> jax.Array:
    """(N, 8) x (S, 8) x (N,) x (S, 1+W) [x (N, S)] -> (N, S) scores."""
    n, f = task_feats.shape
    s = server_feats.shape[0]
    w1 = server_models.shape[1]
    assert f == 8 and server_feats.shape[1] == 8
    assert task_mids.shape == (n,) and server_models.shape == (s, w1)
    tm = task_mids.reshape(n, 1).astype(jnp.float32)
    sm = server_models.astype(jnp.float32)
    bn, bs = min(block_n, n), min(block_s, s)
    nn, ns = -(-n // bn), -(-s // bs)
    if nn * bn - n or ns * bs - s:
        task_feats = jnp.pad(task_feats, ((0, nn * bn - n), (0, 0)),
                             constant_values=1.0)
        server_feats = jnp.pad(server_feats, ((0, ns * bs - s), (0, 0)),
                               constant_values=1.0)
        tm = jnp.pad(tm, ((0, nn * bn - n), (0, 0)), constant_values=-1.0)
        sm = jnp.pad(sm, ((0, ns * bs - s), (0, 0)), constant_values=-1.0)
        if locality is not None:
            locality = jnp.pad(locality,
                               ((0, nn * bn - n), (0, ns * bs - s)))

    # server-side operands go in transposed (feature rows, S lanes); see
    # the layout note in ``kernel.py``
    in_specs = [
        pl.BlockSpec((bn, 8), lambda i, j: (i, 0)),
        pl.BlockSpec((8, bs), lambda i, j: (0, j)),
        pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((w1, bs), lambda i, j: (0, j)),
    ]
    operands = [task_feats, server_feats.T, tm, sm.T]
    kernel = _fused_kernel
    if locality is not None:
        in_specs.append(pl.BlockSpec((bn, bs), lambda i, j: (i, j)))
        operands.append(locality)
        kernel = _fused_kernel_loc

    out = pl.pallas_call(
        kernel,
        grid=(nn, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nn * bn, ns * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)
    return out[:n, :s]
