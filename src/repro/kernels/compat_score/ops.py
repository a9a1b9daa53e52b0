"""Public wrapper for batch task-server scoring.

This is the accelerated backend of the micro layer's batched Eq 7-10
score matrix (``core.micro.batched_score_matrix``).  Feature convention
(shared with ``core.micro.task_feature_matrix`` /
``server_feature_matrix``):

  task rows   (N, 8): [demand_tflops, mem_gb, kind-onehot x3, 0, 0, 0]
  server rows (S, 8): [tflops, mem_gb, kind-onehot x3, util, queue_norm,
                       load_cap]

with ``load_cap = 4.0`` so the kernel's ``exp(-4*(util+queue)/cap)``
reduces to the scheduler's Eq-9 form ``exp(-(util+queue))``.  Enable in
the scheduler via ``TortaScheduler(use_compat_kernel=True)``.
"""
from __future__ import annotations

import jax

from repro.kernels.compat_score.kernel import compat_score
from repro.kernels.compat_score.ref import compat_score_ref


def score_matrix(task_feats, server_feats, locality=None, *,
                 use_pallas=True, interpret=False) -> jax.Array:
    """hw+load(+locality) scores.  ``locality=None`` skips the locality
    operand (callers that fold Eq-10 in on the host pass nothing instead
    of allocating an (N, S) zeros matrix per call)."""
    if use_pallas:
        return compat_score(task_feats, server_feats, locality,
                            interpret=interpret)
    return compat_score_ref(task_feats, server_feats, locality)
