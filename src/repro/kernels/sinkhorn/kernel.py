"""Batched log-domain Sinkhorn kernel — the macro layer's OT hot path.

During PPO training TORTA solves one R x R OT problem per (env x timeslot);
batching those into (B, R, R) turns a CPU-style solver loop into a single
TPU tensor program.  Grid tiles the batch; each program holds its (bb, R, R)
cost block in VMEM and runs all Sinkhorn iterations in-register (R <= 32, so
a full iteration is one VPU-wide logsumexp pair).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(mu_ref, nu_ref, c_ref, p_ref, *, n_iters: int, reg: float):
    mu = mu_ref[...].astype(jnp.float32)          # (bb, R)
    nu = nu_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)            # (bb, R, R)
    logmu = jnp.log(jnp.maximum(mu, 1e-30))
    lognu = jnp.log(jnp.maximum(nu, 1e-30))
    mk = -c / reg

    def body(_, fg):
        f, g = fg
        t1 = mk + g[:, None, :] / reg                 # (bb, R, R)
        m1 = t1.max(-1)
        f = reg * (logmu - (m1 + jnp.log(
            jnp.sum(jnp.exp(t1 - m1[..., None]), -1))))
        t2 = mk + f[:, :, None] / reg
        m2 = t2.max(1)
        g = reg * (lognu - (m2 + jnp.log(
            jnp.sum(jnp.exp(t2 - m2[:, None, :]), 1))))
        return f, g

    f = jnp.zeros_like(mu)
    g = jnp.zeros_like(nu)
    f, g = jax.lax.fori_loop(0, n_iters, body, (f, g))
    p_ref[...] = jnp.exp(mk + (f[:, :, None] + g[:, None, :]) / reg
                         ).astype(p_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("reg", "n_iters", "block_b",
                                    "interpret"))
def sinkhorn_batched(mu: jax.Array, nu: jax.Array, cost: jax.Array, *,
                     reg: float = 0.05, n_iters: int = 100,
                     block_b: int = 8, interpret: bool = False) -> jax.Array:
    """mu, nu: (B, R); cost: (B, R, R) -> transport plans (B, R, R)."""
    b, r = mu.shape
    bb = min(block_b, b)
    nb = -(-b // bb)
    pad = nb * bb - b
    if pad:
        mu = jnp.pad(mu, ((0, pad), (0, 0)), constant_values=1.0 / r)
        nu = jnp.pad(nu, ((0, pad), (0, 0)), constant_values=1.0 / r)
        cost = jnp.pad(cost, ((0, pad), (0, 0), (0, 0)))

    kernel = functools.partial(_kernel, n_iters=n_iters, reg=float(reg))
    p = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, r), lambda i: (i, 0)),
            pl.BlockSpec((bb, r), lambda i: (i, 0)),
            pl.BlockSpec((bb, r, r), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, r, r), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bb, r, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(mu, nu, cost)
    return p[:b]
