"""Macro-level allocation (§V-B): demand prediction + OT + (optionally) the
trained PPO policy, producing the inter-region allocation matrix A_t."""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import policy as pol
from repro.core.env import K_HIST
from repro.core.ot import (cost_matrix, normalize_masses, routing_probs,
                           sinkhorn)
from repro.core.predictor import EmaPredictor
from repro.obs import runtime as obs_rt


@dataclasses.dataclass
class MacroAllocator:
    n_regions: int
    # smoothing step toward the OT plan when no trained policy is provided
    # (the fixed-point the smoothness-regularized policy converges to)
    eta: float = 0.35
    reg: float = 0.05
    policy_params: Optional[object] = None     # trained PPO params
    predictor: Optional[Callable] = None       # hist -> (R,) distribution
    use_sinkhorn_kernel: bool = False
    kernel_interpret: bool = False

    def __post_init__(self):
        r = self.n_regions
        self.a_prev = np.full((r, r), 1.0 / r)
        self.ema = EmaPredictor(r)
        self.hist = np.full((K_HIST, r), 1.0 / r)
        # (K, 3R) = [U, Q, H] channels per slot — the predictor's input
        self.feat_hist = np.zeros((K_HIST, 3 * r), np.float32)
        self.feat_hist[:, 2 * r:] = 1.0 / r
        self.prev_nu = np.full((r,), 1.0 / r)

    def reset(self) -> None:
        self.__post_init__()

    # ------------------------------------------------------------------

    def predict_next(self, arrivals: np.ndarray,
                     util: Optional[np.ndarray] = None,
                     queue_norm: Optional[np.ndarray] = None) -> np.ndarray:
        """Update history with realized state; forecast next distribution."""
        r = self.n_regions
        self.ema.update(arrivals)
        dist = arrivals / max(arrivals.sum(), 1e-9)
        self.hist = np.concatenate([self.hist[1:], dist[None]], axis=0)
        feat = np.concatenate([
            util if util is not None else np.zeros(r),
            queue_norm if queue_norm is not None else np.zeros(r),
            dist]).astype(np.float32)
        self.feat_hist = np.concatenate([self.feat_hist[1:], feat[None]],
                                        axis=0)
        if self.predictor is not None:
            return np.asarray(self.predictor(self.feat_hist))
        return self.ema.predict()

    def ot_plan(self, demand: np.ndarray, capacity: np.ndarray,
                power_cost: np.ndarray, latency: np.ndarray) -> np.ndarray:
        with obs_rt.span("macro.ot"):
            dem, cap, power, lat = (jnp.asarray(x, jnp.float32) for x in (
                demand, capacity, power_cost / max(power_cost.max(), 1e-9),
                latency / max(latency.max(), 1e-9)))
            obs_rt.count_transfer("h2d", "macro",
                                  lambda: (dem, cap, power, lat))
            mu, nu = normalize_masses(dem, cap)
            c = cost_matrix(power, lat)
            if self.use_sinkhorn_kernel:
                from repro.kernels.sinkhorn.ops import sinkhorn_plan
                plan = sinkhorn_plan(mu[None], nu[None], c[None],
                                     reg=self.reg,
                                     interpret=self.kernel_interpret)[0]
            else:
                plan = sinkhorn(mu, nu, c, reg=self.reg)
            probs = np.asarray(routing_probs(plan))
            obs_rt.count_transfer("d2h", "macro", lambda: (probs,))
        return probs

    def allocate(self, *, demand: np.ndarray, predicted: np.ndarray,
                 capacity: np.ndarray, power_cost: np.ndarray,
                 latency: np.ndarray, queue: np.ndarray,
                 utilization: np.ndarray, q_max: float) -> np.ndarray:
        """A_t given current demand + forecast. Row-stochastic (R, R)."""
        # blend realized demand with the forecast (temporal awareness)
        blended = 0.5 * demand + 0.5 * predicted * max(demand.sum(), 1.0)
        probs = self.ot_plan(blended, capacity, power_cost, latency)
        # track realized supply on EVERY call — leaving prev_nu stale
        # while a trained policy drives allocation made toggling the
        # policy off mid-experiment see a bogus "supply shock" snap
        nu = capacity / max(capacity.sum(), 1e-9)
        shock = float(np.abs(nu - self.prev_nu).sum()) > 0.25
        self.prev_nu = nu
        if self.policy_params is not None:
            obs = np.concatenate([
                utilization,
                queue / max(q_max, 1e-9),
                (latency / max(latency.max(), 1e-9)).reshape(-1),
                self.hist.reshape(-1),
                predicted,
                self.a_prev.reshape(-1),
            ]).astype(np.float32)
            a = np.asarray(pol.mean_action(self.policy_params,
                                           jnp.asarray(obs), self.n_regions))
        else:
            # temporally-smoothed OT: A_t = (1-eta) A_{t-1} + eta P* —
            # except under a supply shock (regional failure / recovery),
            # where smoothing toward a stale plan would keep feeding dead
            # capacity (the paper's smoothness term "allows necessary
            # adaptations"): a large nu shift snaps to P*.
            eta = 1.0 if shock else self.eta
            a = (1 - eta) * self.a_prev + eta * probs
        a = a / np.maximum(a.sum(1, keepdims=True), 1e-9)
        self.a_prev = a
        return a
