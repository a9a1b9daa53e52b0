"""Public wrapper for batched OT plans."""
from __future__ import annotations

import jax

from repro.kernels.sinkhorn.kernel import sinkhorn_batched
from repro.kernels.sinkhorn.ref import sinkhorn_ref


def sinkhorn_plan(mu: jax.Array, nu: jax.Array, cost: jax.Array, *,
                  reg: float = 0.05, n_iters: int = 100,
                  use_pallas: bool = True, interpret: bool = False
                  ) -> jax.Array:
    """(B, R) x (B, R) x (B, R, R) -> (B, R, R) transport plans.
    ``interpret=True`` runs the kernel through the Pallas interpreter
    (CPU tests); the default compiles it for the TPU."""
    if use_pallas:
        return sinkhorn_batched(mu, nu, cost, reg=reg, n_iters=n_iters,
                                interpret=interpret)
    return sinkhorn_ref(mu, nu, cost, reg=reg, n_iters=n_iters)
