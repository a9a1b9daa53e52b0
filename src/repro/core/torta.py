"""TORTA scheduler — Algorithm 1 end to end.

Phase 1 (macro): normalize demand/supply, Sinkhorn OT, demand predictor,
RL/smoothed allocation matrix A_t, sample a region per task.
Phase 2 (micro): Eq-6 server activation per region, Eq-7-10 greedy
task-server matching, buffering.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.api import BatchDecision, SlotDecision
from repro.core.macro import MacroAllocator
from repro.core.micro import MicroAllocator
from repro.obs import runtime as obs_rt
from repro.sim.engine import SlotObs
from repro.sim.workload import Task


@dataclasses.dataclass
class TortaScheduler:
    n_regions: int
    seed: int = 0
    eta: float = 0.35
    sigma: float = 2.0
    headroom: float = 2.5
    policy_params: Optional[object] = None
    predictor: Optional[object] = None
    # Fig-12 sweep: corrupt the forecast to a target accuracy (1 = oracle-ish)
    prediction_noise: float = 0.0
    use_sinkhorn_kernel: bool = False
    # Phase-2 scoring backend: route the batched Eq 7-10 score matrix
    # through the compat_score Pallas kernel (mirrors use_sinkhorn_kernel)
    use_compat_kernel: bool = False
    # run the Pallas kernels above through the interpreter (CPU tests);
    # off by default, so on a TPU they compile for the chip
    kernel_interpret: bool = False
    # Phase-2 micro backend: "numpy" (float64 oracle, default), "jax"
    # (jit-compiled per-region lax.scan greedy over LocalityState ring
    # buffers), "fused" (ONE padded multi-region scan per slot with
    # device-resident rings and the operand build inside the jit —
    # pair with Engine(step_backend="jax") for the fused slot step), or
    # "pallas" (numpy greedy, Pallas hw+load scores — what
    # use_compat_kernel=True selects).  None = derive from
    # use_compat_kernel for backward compatibility.
    micro_backend: Optional[str] = None
    # with micro_backend="jax": fused Pallas static-score kernel (float32)
    # instead of the float64 numpy-oracle-ordered static matrix
    micro_fused_kernel: bool = False
    # Phase-1 task distribution: "sample" = per-task sampling from
    # A_t[origin,:] (Algorithm 1 line 7, paper-faithful — also the better
    # performer, see EXPERIMENTS.md §Ablations); "sticky" = work-quota
    # chunking with (origin, model) stickiness (beyond-paper experiment,
    # wins power/switches on small topologies, loses response at scale).
    distribution: str = "sample"
    name: str = "TORTA"

    def __post_init__(self):
        self.macro = MacroAllocator(self.n_regions, eta=self.eta,
                                    policy_params=self.policy_params,
                                    predictor=self.predictor,
                                    use_sinkhorn_kernel=self.use_sinkhorn_kernel,
                                    kernel_interpret=self.kernel_interpret)
        backend = self.micro_backend or (
            "pallas" if self.use_compat_kernel else "numpy")
        self.micro = MicroAllocator(
            sigma=self.sigma, headroom=self.headroom, backend=backend,
            interpret=self.kernel_interpret,
            fused=self.micro_fused_kernel)
        self.rng = np.random.default_rng(self.seed)
        self.prediction_log = []
        self._sticky = {}

    def reset(self) -> None:
        self.macro.reset()
        self.micro.reset()
        self.rng = np.random.default_rng(self.seed)
        # clear per-run state so repeated runs don't leak sticky routing or
        # stale forecasts into prediction-accuracy metrics
        self.prediction_log = []
        self._sticky = {}

    # ------------------------------------------------------------------

    @property
    def supports_batch(self) -> bool:
        """Batch-native scheduling is available for the paper-faithful
        per-task sampling distribution (the sticky variant is inherently
        object-grouped)."""
        return self.distribution == "sample"

    def _macro_step(self, obs: SlotObs, demand: np.ndarray) -> np.ndarray:
        """Shared phase-1 macro computation: predict next-slot demand,
        corrupt it if requested, log it, and solve for A_t."""
        with obs_rt.span("macro.phase1"):
            r = self.n_regions
            with obs_rt.span("macro.predict"):
                q_norm = obs.queue_tasks / max(float(obs.queue_tasks.max()),
                                               1.0)
                predicted = self.macro.predict_next(demand, obs.utilization,
                                                    q_norm)
                if self.prediction_noise > 0:
                    noise = self.rng.dirichlet(np.ones(r))
                    predicted = (1 - self.prediction_noise) * predicted \
                        + self.prediction_noise * noise
                self.prediction_log.append(np.asarray(predicted))

            # supply = capacity net of existing backlog (temporal load
            # awareness)
            cap = np.maximum(obs.capacities - obs.queue_tasks,
                             0.05 * np.maximum(obs.capacities, 1e-6))
            a = self.macro.allocate(
                demand=demand, predicted=predicted, capacity=cap,
                power_cost=obs.power_prices, latency=obs.latency,
                queue=obs.queue_s, utilization=obs.utilization,
                q_max=10.0 * float(cap.sum()) * obs.slot_seconds)
            self._predicted = predicted
        return a

    def _row_probs(self, a: np.ndarray, origin: int,
                   mask: np.ndarray) -> np.ndarray:
        pm = a[origin] * mask
        if pm.sum() <= 0:
            pm = mask.astype(float)
        if pm.sum() <= 0:
            pm = np.ones(self.n_regions)
        return pm / pm.sum()

    def schedule_batch(self, obs: SlotObs, batch) -> BatchDecision:
        """Batch-native Algorithm 1: phase-1 sampling and phase-2 greedy
        matching directly over ``TaskBatch`` arrays — no Task objects."""
        r = self.n_regions
        n = len(batch)
        demand = batch.origin_counts(r).astype(np.float64)
        a = self._macro_step(obs, demand)
        predicted = self._predicted

        with obs_rt.span("macro.sample"):
            region_of = np.full(n, -1, np.int32)
            mask = obs.capacities > 0
            for origin in np.unique(batch.origin):
                idx = np.flatnonzero(batch.origin == origin)
                pm = self._row_probs(a, int(origin), mask)
                region_of[idx] = self.rng.choice(r, size=idx.size, p=pm)

        pred_inbound = self._pred_inbound(obs, a, demand, predicted)
        if self.micro.backend == "fused":
            # fused slot path: phase-1 outputs (sampled regions + Eq-6
            # targets from pred_inbound) feed ONE multi-region scan
            # dispatch instead of R per-region assign calls
            with obs_rt.span("micro.activation"):
                activation = self.micro.activation_targets(obs, pred_inbound)
            server_of = self.micro.assign_batch_all(obs, batch, region_of)
        else:
            activation = np.empty(r, np.int64)   # api array form
            server_of = np.full(n, -1, np.int32)
            for j in range(r):
                activation[j] = self.micro.activation_target(
                    obs, j, float(pred_inbound[j]))
                idx = np.flatnonzero(region_of == j)
                if idx.size:
                    server_of[idx] = self.micro.assign_batch(obs, j, batch,
                                                             idx)
        return BatchDecision(region=np.where(server_of >= 0, region_of, -1),
                             server=server_of, activation=activation)

    def schedule(self, obs: SlotObs, tasks: List[Task]) -> SlotDecision:
        """Legacy object path.  Kept as a REAL implementation (not the
        one-line shim) for two callers only: the ``sticky`` distribution
        (inherently object-grouped, routed through the engine's adapter)
        and the frozen per-object oracle (``sim/reference.py``'s
        ``make_reference_torta``), whose ``RefSlotObs``/object micro
        allocator cannot consume ``TaskBatch`` arrays.  For
        ``distribution="sample"`` it is trajectory-identical to
        ``schedule_batch`` (pinned by the adapter-parity tests)."""
        r = self.n_regions
        origins = np.fromiter((t.origin for t in tasks), np.int64,
                              count=len(tasks))
        demand = np.bincount(origins, minlength=r).astype(np.float64)
        a = self._macro_step(obs, demand)
        predicted = self._predicted

        # Phase 1: distribute tasks per A_t[origin, :]
        by_region: Dict[int, List[Task]] = {j: [] for j in range(r)}
        mask = obs.capacities > 0
        by_origin: Dict[int, List[Task]] = {}
        for task in tasks:
            by_origin.setdefault(task.origin, []).append(task)
        if self.distribution == "sample":
            # Algorithm 1 line 7: sample a region per task, batched per
            # origin (every task of one origin shares the same A_t row).
            # NOTE: the batched draw consumes the seeded RNG stream in a
            # different order than the original per-task loop, so seeded
            # trajectories differ from pre-array-refactor runs (still
            # deterministic per seed; distribution is unchanged).
            for origin, group in by_origin.items():
                pm = self._row_probs(a, origin, mask)
                js = self.rng.choice(r, size=len(group), p=pm)
                for task, j in zip(group, js):
                    by_region[int(j)].append(task)
            return self._phase2(obs, a, demand, predicted, by_region)
        for origin, group in by_origin.items():
            pm = self._row_probs(a, origin, mask)
            # keep same-model tasks cohesive (warm locality) but apportion
            # by WORK, greedily filling the region with the largest
            # remaining work quota — count-based chunking in a fixed order
            # would systematically dump the heaviest model group on the
            # highest-probability region every slot.
            by_model: Dict[str, List[Task]] = {}
            for tk in group:
                by_model.setdefault(tk.model, []).append(tk)
            total_work = sum(tk.work_s for tk in group)
            quota = pm * total_work
            q_cap = max(float(quota.max()), 1e-6)
            # adaptive granularity: under system stress (queues building
            # anywhere) chunk finely and follow quotas strictly so overload
            # disperses; in steady state keep big sticky chunks (locality)
            stress = float(np.max(obs.queue_tasks /
                                  np.maximum(obs.capacities, 1e-6))) > 0.10
            chunk_scale = 1.0 if stress else 2.0
            sticky_slack = 0.5 if stress else -0.25
            subgroups = sorted(by_model.values(),
                               key=lambda g2: -sum(tk.work_s for tk in g2))
            for g2 in subgroups:
                w2 = sum(tk.work_s for tk in g2)
                n_chunks = max(1, int(np.ceil(w2 / (chunk_scale * q_cap))))
                step = max(1, -(-len(g2) // n_chunks))
                for k0 in range(0, len(g2), step):
                    part = g2[k0:k0 + step]
                    pw = sum(tk.work_s for tk in part)
                    key = (origin, part[0].model)
                    j = self._sticky.get(key, -1)
                    if j < 0 or quota[j] < sticky_slack * pw or not mask[j]:
                        j = int(np.argmax(quota))
                    self._sticky[key] = j
                    by_region[j].extend(part)
                    quota[j] -= pw

        return self._phase2(obs, a, demand, predicted, by_region)

    def _pred_inbound(self, obs, a, demand, predicted) -> np.ndarray:
        """Expected next-slot inbound tasks per region under A_t, trend-
        extrapolated: cold start spans ~2 slots but the forecast is 1 slot
        ahead, so ramps must be pre-warmed in time."""
        total = max(demand.sum(), 1.0)
        pred_inbound = a.T @ (predicted * total)
        hist = obs.arrivals_history
        if hist.shape[0] >= 2:
            prev_tot = max(float(hist[-2].sum()), 1.0)
            trend = float(np.clip(total / prev_tot, 1.0, 1.6))
        else:
            trend = 1.0
        pred_inbound = pred_inbound * trend
        obs_rt.record_forecast(pred_inbound)
        return pred_inbound

    def _phase2(self, obs, a, demand, predicted, by_region):
        # Phase 2: micro layer per region
        r = self.n_regions
        assignments: Dict[int, Optional[Tuple[int, int]]] = {}
        activation: Dict[int, int] = {}
        pred_inbound = self._pred_inbound(obs, a, demand, predicted)
        for j in range(r):
            activation[j] = self.micro.activation_target(
                obs, j, float(pred_inbound[j]))
            assignments.update(self.micro.assign_region(obs, j, by_region[j]))
        return SlotDecision(assignments=assignments, activation=activation)
