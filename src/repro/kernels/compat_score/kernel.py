"""Task-server compatibility scoring kernel (micro layer, Eqs 7-10).

Computes the (N tasks x S servers) score matrix in one tiled pass:

  score = w1 * hw + w2 * load + w3 * locality
  hw    = min(1, tflops/demand) * min(1, mem_s/mem_t) * type_match
  load  = exp(-4 * (util + queue_norm) / capacity)

Task features  (N, 8): [demand_tflops, mem_gb, kind0, kind1, kind2, pad...]
Server features(S, 8): [tflops, mem_gb, kind0, kind1, kind2, util,
                        queue_norm, capacity]
Locality       (N, S): precomputed Eq-10 history term.

Grid tiles (N, S); each program computes a (bn, bs) tile in VMEM from two
feature strips — at fleet scale (1e5 tasks x 1e4 servers per §III-A) this is
the micro layer's dominant cost and is embarrassingly tileable.

TPU layout: the wrapper hands the kernel the server features transposed,
(8, S), so each server feature is a lane-dense (1, bs) row and each task
feature a (bn, 1) column; every tile term is then a plain broadcast, with
no in-kernel transpose.  The one-hot kind match is three broadcast
products rather than a K=3 matmul (exact for one-hot rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

W_HW, W_LOAD, W_LOC = 0.4, 0.4, 0.2


def _hw_load_tile(tf, sf):
    """(bn, 8) task strip x (8, bs) transposed server strip -> (bn, bs)."""
    demand = tf[:, 0:1]
    mem_t = tf[:, 1:2]
    tflops = sf[0:1, :]
    mem_s = sf[1:2, :]
    util = sf[5:6, :]
    queue = sf[6:7, :]
    cap = sf[7:8, :]

    c = jnp.minimum(1.0, tflops / jnp.maximum(demand, 1e-9))
    m = jnp.minimum(1.0, mem_s / jnp.maximum(mem_t, 1e-9))
    match = (tf[:, 2:3] * sf[2:3, :] + tf[:, 3:4] * sf[3:4, :]
             + tf[:, 4:5] * sf[4:5, :])                # 1 if same kind
    type_match = 0.5 + 0.5 * match
    hw = c * m * type_match
    load = jnp.exp(-4.0 * (util + queue) / jnp.maximum(cap, 1e-9))
    return W_HW * hw + W_LOAD * load


def _kernel(t_ref, s_ref, loc_ref, o_ref):
    tf = t_ref[...].astype(jnp.float32)            # (bn, 8)
    sf = s_ref[...].astype(jnp.float32)            # (8, bs)
    loc = loc_ref[...].astype(jnp.float32)         # (bn, bs)
    o_ref[...] = (_hw_load_tile(tf, sf) + W_LOC * loc).astype(o_ref.dtype)


def _kernel_noloc(t_ref, s_ref, o_ref):
    tf = t_ref[...].astype(jnp.float32)            # (bn, 8)
    sf = s_ref[...].astype(jnp.float32)            # (8, bs)
    o_ref[...] = _hw_load_tile(tf, sf).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_s",
                                             "interpret"))
def compat_score(task_feats: jax.Array, server_feats: jax.Array,
                 locality: jax.Array = None, *, block_n: int = 256,
                 block_s: int = 256, interpret: bool = False) -> jax.Array:
    """(N, 8) x (S, 8) [x (N, S)] -> (N, S) scores.  ``locality=None``
    skips the locality operand entirely (no zeros allocation, no third
    VMEM stream) — the hw+load part alone."""
    n, f = task_feats.shape
    s = server_feats.shape[0]
    assert f == 8 and server_feats.shape[1] == 8
    bn, bs = min(block_n, n), min(block_s, s)
    nn, ns = -(-n // bn), -(-s // bs)
    if nn * bn - n or ns * bs - s:
        task_feats = jnp.pad(task_feats, ((0, nn * bn - n), (0, 0)),
                             constant_values=1.0)
        server_feats = jnp.pad(server_feats, ((0, ns * bs - s), (0, 0)),
                               constant_values=1.0)
        if locality is not None:
            locality = jnp.pad(locality,
                               ((0, nn * bn - n), (0, ns * bs - s)))

    in_specs = [
        pl.BlockSpec((bn, 8), lambda i, j: (i, 0)),
        pl.BlockSpec((8, bs), lambda i, j: (0, j)),
    ]
    operands = [task_feats, server_feats.T]
    kernel = _kernel_noloc
    if locality is not None:
        in_specs.append(pl.BlockSpec((bn, bs), lambda i, j: (i, j)))
        operands.append(locality)
        kernel = _kernel

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
