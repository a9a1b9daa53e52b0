"""The benchmark's own fleet, topology and traffic generators.

Copies of the program's generators (``sim/cluster.make_cluster``,
``sim/topology.make_topology``, ``workload/legacy.generate_traffic`` and
``workload/stream.StreamingWorkload.slot_batch``), kept here so that a
change to the program cannot move the yardstick.  They read every size and
rate from a configuration file and a traffic file, and produce plain numpy
arrays; ``harness.program`` wraps them into the program's types.

Draw order follows the program's generators, so a configuration built here
is the fleet ``make_cluster_state(R, seed=...)`` builds, without its
per-server Python objects.  Departures, each stated in the traffic file:
the diurnal period is given in slots (not "two cycles over the
horizon"); the fleet-wide expected arrivals of every slot are scaled to
the same total, so that the slots a faster run reaches carry the same
expected load as the slots before them; slots are drawn in blocks; and
the run's seed draws only the order and the embeddings of a fixed set of
tasks per slot, and nothing of the warm-up slots.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np

OFF, WARMING, ACTIVE = 0, 1, 2
NO_MODEL = -1


# ---------------------------------------------------------------- fleet


@dataclasses.dataclass
class Fleet:
    """Per-server arrays, region-major (region ``r`` owns
    ``region_ptr[r]:region_ptr[r+1]``), in the program's column layout."""

    region_ptr: np.ndarray
    power_price: np.ndarray
    gpu_id: np.ndarray
    tflops: np.ndarray
    mem_gb: np.ndarray
    power_w: np.ndarray
    kind_id: np.ndarray
    capacity: np.ndarray
    switch_scale: np.ndarray

    @property
    def n_regions(self) -> int:
        return len(self.region_ptr) - 1

    @property
    def n_servers(self) -> int:
        return int(self.region_ptr[-1])

    def dynamic_columns(self, warm_slots: int) -> Dict[str, np.ndarray]:
        """The initial dynamic state: every server active and idle."""
        s = self.n_servers
        return dict(state=np.full(s, ACTIVE, np.int8),
                    warm_remaining_s=np.zeros(s),
                    queue_s=np.zeros(s), util=np.zeros(s),
                    idle_slots=np.zeros(s, np.int64),
                    current_model=np.full(s, NO_MODEL, np.int16),
                    warm_models=np.full((s, warm_slots), NO_MODEL,
                                        np.int16))


def gpu_table(cfg: dict):
    names = [k for k in cfg["gpu_types"] if not k.startswith("_")]
    return names, [cfg["gpu_types"][n] for n in names]


def model_table(cfg: dict):
    names = [k for k in cfg["models"] if not k.startswith("_")]
    return names, [cfg["models"][n] for n in names]


def make_fleet(cfg: dict) -> Fleet:
    """``make_cluster`` with the configuration's sizes: per region a
    server count, a Dirichlet GPU mix, per-server GPU and capacity draws,
    and an electricity price (same draws, same order)."""
    names, rows = gpu_table(cfg)
    kinds = cfg["kinds"]
    lo, hi = cfg["servers_per_region"]
    fl = cfg["fleet"]
    rng = np.random.default_rng(fl["seed"])
    n_regions = cfg["topology"]["nodes"]
    ptr = [0]
    prices: List[float] = []
    gpu: List[int] = []
    cap: List[float] = []
    alpha = np.ones(len(names)) * fl["mix_dirichlet_alpha"]
    p_lo, p_hi = fl["price_per_kwh_range"]
    for _ in range(n_regions):
        n_srv = int(rng.integers(lo, hi + 1))
        mix = rng.dirichlet(alpha)
        for _ in range(n_srv):
            g = int(rng.choice(len(names), p=mix))
            c_lo, c_hi = rows[g][4]
            gpu.append(g)
            cap.append(float(rng.uniform(c_lo, c_hi)))
        ptr.append(len(gpu))
        prices.append(float(rng.uniform(p_lo, p_hi)))
    g = np.asarray(gpu, np.int64)
    col = lambda k, dt: np.asarray([rows[i][k] for i in range(len(rows))],
                                   dt)[g]
    return Fleet(
        region_ptr=np.asarray(ptr, np.int64),
        power_price=np.asarray(prices, np.float64),
        gpu_id=g.astype(np.int8),
        tflops=col(0, np.float64), mem_gb=col(1, np.float64),
        power_w=col(2, np.float64),
        kind_id=np.asarray([kinds.index(r[3]) for r in rows], np.int8)[g],
        capacity=np.asarray(cap, np.float64),
        switch_scale=col(5, np.float64))


def throughput_per_slot(cfg: dict, fleet: Fleet) -> float:
    """Fleet throughput in tasks per slot, speed-adjusted
    (``sim/cluster.throughput_per_slot``)."""
    return float(np.sum(cfg["slot_seconds"]
                        * (fleet.tflops / cfg["reference_speed_tflops"])
                        / cfg["reference_task_work_s"]))


# ------------------------------------------------------------- topology


def make_latency(cfg: dict):
    """(R, R) latency in ms: a seeded connected Watts-Strogatz graph with
    the configuration's node count, shortest-path edge latencies scaled to
    its mean base latency, 1 ms on the diagonal (``make_topology``)."""
    import networkx as nx

    tp = cfg["topology"]
    n = tp["nodes"]
    rng = np.random.default_rng(tp["seed"])
    graph = nx.connected_watts_strogatz_graph(
        n, k=tp["watts_strogatz_k"], p=tp["rewire_p"],
        seed=int(rng.integers(1 << 30)))
    e_lo, e_hi = tp["edge_latency_range"]
    for u, v in graph.edges:
        graph[u][v]["lat"] = float(rng.uniform(e_lo, e_hi))
    paths = dict(nx.all_pairs_dijkstra_path_length(graph, weight="lat"))
    lat = np.zeros((n, n))
    for i in range(n):
        for j, d in paths[i].items():
            lat[i, j] = d
    off = lat[~np.eye(n, dtype=bool)]
    lat = lat * (tp["base_latency_ms"] / max(off.mean(), 1e-9))
    np.fill_diagonal(lat, 1.0)
    return lat, graph


# -------------------------------------------------------------- traffic


def expected_arrivals(traffic: dict, n_slots: int, n_regions: int,
                      total_rate: float) -> np.ndarray:
    """(T, R) expected arrivals per slot from the traffic file's fixed
    ``shape_seed``: time-zone phases, Dirichlet region weights, a diurnal
    sine of ``period_slots`` and multiplicative noise
    (``generate_traffic`` without surges), each slot scaled to
    ``total_rate`` expected tasks."""
    rng = np.random.default_rng(traffic["shape_seed"])
    t = np.arange(n_slots)[:, None] / traffic["period_slots"]
    phase = rng.uniform(0, 2 * np.pi, n_regions)[None, :]
    weight = rng.dirichlet(np.ones(n_regions)
                           * traffic["weight_dirichlet_alpha"]) * n_regions
    wave = 1.0 + traffic["diurnal_amp"] * np.sin(2 * np.pi * t + phase)
    rates = (total_rate / n_regions) * weight[None, :] * wave
    rates *= np.maximum(1.0 + traffic["noise"]
                        * rng.standard_normal((n_slots, n_regions)),
                        traffic["noise_floor"])
    rates = np.maximum(rates, traffic["min_rate"])
    return rates * (total_rate / rates.sum(axis=1, keepdims=True))


def model_mix(traffic: dict, n_models: int) -> np.ndarray:
    pop = 1.0 / np.arange(1, n_models + 1) ** traffic["model_zipf_exponent"]
    return pop / pop.sum()


@dataclasses.dataclass
class Slot:
    """One slot's new tasks as parallel arrays (the program's
    ``TaskBatch`` columns)."""

    t: int
    ids: np.ndarray
    origin: np.ndarray
    model_idx: np.ndarray
    kind_id: np.ndarray
    work_s: np.ndarray
    mem_gb: np.ndarray
    deadline_slot: np.ndarray
    arrival_slot: np.ndarray
    embeds: np.ndarray

    def __len__(self) -> int:
        return int(self.ids.shape[0])


class Traffic:
    """Task generator over a fixed expected-arrival matrix.

    Slots are made in blocks, each from its own generators, so a slot's
    tasks do not depend on how far the run generates: the warm-up slots
    ``[0, warmup_slots)`` form one block, and the window's slots follow in
    blocks of the file's ``block_slots``.  Within a block the counts and
    task sizes (model, work, deadline) come from the file's ``shape_seed``
    and are the same in every run; the run's seed (taken whole, so seeds
    above 2**31 differ) draws the embeddings and the order of the tasks
    within each origin.  The warm-up block takes ``shape_seed`` in the
    seed's place, so set-up does the same work in every run."""

    def __init__(self, cfg: dict, traffic: dict, fleet: Fleet, seed: int):
        self.cfg = cfg
        self.spec = traffic
        self.seed = int(seed)
        self.n_regions = fleet.n_regions
        self.s0 = int(cfg["warmup_slots"])
        self.block = int(traffic["block_slots"])
        self.total_rate = traffic["utilization"] * throughput_per_slot(
            cfg, fleet)
        names, rows = model_table(cfg)
        self.n_models = len(names)
        kinds = cfg["kinds"]
        act = np.asarray([r[0] for r in rows], np.float64)
        # work seconds on the reference GPU: ~25 s for an 8B model,
        # linear in active parameters, 2 s floor (``task_profile``)
        self.model_work = np.maximum(2.0, 25.0 * act / 8.0)
        self.model_mem = np.asarray([r[1] for r in rows], np.float64)
        self.model_kind = np.asarray([kinds.index(r[2]) for r in rows],
                                     np.int8)
        self.mix = model_mix(traffic, self.n_models)
        self._rates = np.zeros((0, self.n_regions))
        self._made: Dict[int, Slot] = {}

    def rates(self, n_slots: int) -> np.ndarray:
        """The first ``n_slots`` rows of the expected arrivals.  Row ``t``
        does not depend on how many rows are made, so the matrix grows by
        doubling."""
        if self._rates.shape[0] < n_slots:
            self._rates = expected_arrivals(
                self.spec, max(n_slots, 2 * self._rates.shape[0], 256),
                self.n_regions, self.total_rate)
        return self._rates[:n_slots]

    def _block_of(self, t: int):
        """(key, first slot, end slot) of the block that holds slot t."""
        if t < self.s0:
            return 0, 0, self.s0
        k = (t - self.s0) // self.block
        first = self.s0 + k * self.block
        return k + 1, first, first + self.block

    def slot(self, t: int) -> Slot:
        """Slot ``t``'s new tasks (see the class docstring for which draws
        take the run's seed)."""
        if t not in self._made:
            self._make_block(*self._block_of(int(t)))
        return self._made[t]

    def _make_block(self, key: int, first: int, end: int) -> None:
        shape_seed = self.spec["shape_seed"]
        shape = np.random.default_rng([shape_seed, key])
        own = np.random.default_rng([self.seed if key else shape_seed,
                                     key, 1])
        r = self.n_regions
        counts = shape.poisson(self.rates(end)[first:end])     # (B, R)
        n = int(counts.sum())
        per_slot = counts.sum(axis=1)
        slot_of = np.repeat(np.arange(end - first), per_slot)
        origin = np.repeat(np.tile(np.arange(r, dtype=np.int32),
                                   end - first), counts.ravel())
        midx = shape.choice(self.n_models, size=n, p=self.mix).astype(
            np.int16)
        w_lo, w_hi = self.spec["work_jitter"]
        work = self.model_work[midx] * shape.uniform(w_lo, w_hi, size=n)
        d_lo, d_hi = self.spec["deadline_slots"]
        ahead = shape.integers(d_lo, d_hi, size=n)
        # shuffle within each (slot, origin); rows stay grouped by origin
        order = np.lexsort((own.random(n), origin, slot_of))
        midx, work, ahead = midx[order], work[order], ahead[order]
        embeds = own.standard_normal(
            (n, self.spec["embed_dim"])).astype(np.float32)
        bounds = np.concatenate(([0], np.cumsum(per_slot)))
        for i, t in enumerate(range(first, end)):
            lo, hi = bounds[i], bounds[i + 1]
            m = midx[lo:hi]
            self._made[t] = Slot(
                t=t,
                ids=(np.int64(t) << np.int64(32))
                + np.arange(hi - lo, dtype=np.int64),
                origin=origin[lo:hi], model_idx=m,
                kind_id=self.model_kind[m], work_s=work[lo:hi],
                mem_gb=self.model_mem[m],
                deadline_slot=t + ahead[lo:hi].astype(np.int64),
                arrival_slot=np.full(hi - lo, t, np.int64),
                embeds=embeds[lo:hi])


def warm_slots(cfg: dict) -> int:
    return int(cfg["warm_models_kept"])


def switch_seconds(cfg: dict):
    """(model switch s, warm-cache hit s) on the V100-class reference."""
    st = cfg["switch_stages_s"]
    return (math.fsum([st["unload"], st["cleanup"], st["load"],
                       st["init"], st["reconfig"]]),
            0.5 * (st["load"] + st["reconfig"]))
