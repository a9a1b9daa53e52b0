"""Observability subsystem: counter registry + Prometheus round-trip,
span nesting/monotonicity, windowed-percentile series vs the numpy
oracle, JSONL/CSV export, RunReport round-trip, engine wiring (fallback,
retrace and host-sync counters on seeded trajectories) and the golden
parity guard — default-on observability changes no engine metric
bitwise."""
import math

import networkx as nx
import numpy as np
import pytest

from repro.api import BatchDecision
from repro.core.torta import TortaScheduler
from repro.obs import (Counters, ObsConfig, Observability, RunReport,
                       SeriesRecorder, Tracer, make_obs,
                       parse_prometheus_text, windowed_percentiles)
from repro.obs import runtime as obs_rt
from repro.sim import Engine, make_cluster_state
from repro.sim.cluster import throughput_per_slot
from repro.sim.metrics import MetricsAggregator
from repro.sim.topology import Topology
from repro.workload import make_source

METRIC_KEYS = ("completed", "dropped", "model_switches", "mean_response_s",
               "mean_wait_s", "mean_work_s", "power_cost_total",
               "switch_cost_total", "operational_overhead", "load_balance",
               "mean_queue_tasks")


def _topology(r: int, seed: int = 0) -> Topology:
    rng = np.random.default_rng(seed)
    lat = rng.uniform(10, 80, (r, r))
    lat = (lat + lat.T) / 2
    np.fill_diagonal(lat, 0.0)
    return Topology(name=f"synth{r}", n_regions=r, bandwidth_gbps=10,
                    latency=lat, graph=nx.cycle_graph(r))


def _small_world(r=5, spr=10, util=0.4, scenario="diurnal", slots=8):
    topo = _topology(r, seed=1)
    cs = make_cluster_state(r, seed=3, servers_per_region=(spr, spr + 1))
    rate = util * throughput_per_slot(cs) / r
    src = make_source(scenario, slots, r, seed=2, base_rate=rate)
    return topo, cs, src


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def test_counters_basic():
    c = Counters()
    assert c.inc("a.b") == 1
    assert c.inc("a.b", 4) == 5
    c.inc("a.b", shape="8x16")
    c.inc("a.b", shape="8x16")
    c.inc("other")
    assert c.get("a.b") == 5
    assert c.get("a.b", shape="8x16") == 2
    assert c.get("missing") == 0
    assert c.total("a.b") == 7
    assert list(c.names()) == ["a.b", "other"]
    d = c.as_dict()
    assert d["a.b"] == 5 and d["a.b{shape=8x16}"] == 2


def test_prometheus_round_trip():
    c = Counters()
    c.inc("micro.retrace.scan", shape="15x256")
    c.inc("micro.retrace.scan", shape="15x512")
    c.inc("engine.tasks.arrived", 1234)
    text = c.prometheus_text()
    assert "# TYPE repro_micro_retrace_scan counter" in text
    parsed = parse_prometheus_text(text)
    assert parsed['repro_micro_retrace_scan{shape="15x256"}'] == 1
    assert parsed["repro_engine_tasks_arrived"] == 1234
    # every cell survives the round trip
    assert len(parsed) == len(c.as_dict())
    assert sorted(parsed.values()) == sorted(c.as_dict().values())


def test_counters_inactive_hooks_are_noops():
    # outside an activated run the hooks must not raise and not record
    obs_rt.count("x.y", 3, shape="1")
    assert obs_rt.count_new_shape("x.y", "1") is False
    with obs_rt.span("nothing"):
        pass
    obs = Observability()
    with obs_rt.activate(obs):
        obs_rt.count("x.y", 3)
        assert obs_rt.count_new_shape("x.z", "8") is True
        assert obs_rt.count_new_shape("x.z", "8") is False  # same shape
        assert obs_rt.count_new_shape("x.z", "16") is True  # new shape
    assert obs.counters.get("x.y") == 3
    assert obs.counters.total("x.z") == 2
    assert obs_rt.active() is None


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def _fake_clock():
    t = [0.0]

    def tick():
        t[0] += 1.0
        return t[0]
    return tick


def test_span_nesting_and_monotonicity():
    tr = Tracer(clock=_fake_clock())
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    outer, in1, in2 = tr.records
    assert (outer.depth, in1.depth, in2.depth) == (0, 1, 1)
    assert in1.parent == 0 and in2.parent == 0 and outer.parent == -1
    # start times strictly increase in record order; children close
    # before their parent so nest durations stay consistent
    assert outer.t_start < in1.t_start < in2.t_start
    assert outer.duration_s >= in1.duration_s + in2.duration_s
    rows = {r["name"]: r for r in tr.summary()}
    assert rows["inner"]["count"] == 2 and rows["inner"]["depth"] == 1
    assert rows["outer"]["count"] == 1 and rows["outer"]["depth"] == 0
    assert rows["inner"]["mean_s"] == pytest.approx(
        rows["inner"]["total_s"] / 2)
    assert "outer" in tr.summary_table()


def test_traced_decorator():
    tr = Tracer(clock=_fake_clock())

    @tr.traced("work")
    def fn(x):
        return x + 1

    assert fn(1) == 2 and fn(2) == 3
    assert [r.name for r in tr.records] == ["work", "work"]


# ---------------------------------------------------------------------------
# series
# ---------------------------------------------------------------------------


def _feed_recorder(rec, rng, n_slots, r):
    per_slot = []
    for t in range(n_slots):
        n = int(rng.integers(0, 6))
        if t in (2, 3):            # a gap: empty window start behavior
            n = 0
        resp = rng.exponential(20.0, n)
        per_slot.append(resp)
        rec.end_slot(t, responses=resp,
                     queue_tasks=float(rng.integers(0, 50)),
                     arrivals=rng.integers(0, 9, r),
                     drops=int(rng.integers(0, 3)),
                     saturation=rng.random(r),
                     load_balance=float(rng.random()))
    return per_slot


def test_windowed_percentiles_match_numpy_oracle():
    rng = np.random.default_rng(7)
    rec = SeriesRecorder(n_regions=4, window=3)
    per_slot = _feed_recorder(rec, rng, n_slots=12, r=4)
    oracle = windowed_percentiles(per_slot, window=3)
    ts = rec.timeseries()
    got = np.stack([ts["p50_response_s"], ts["p95_response_s"],
                    ts["p99_response_s"]], axis=1)
    assert got.shape == oracle.shape == (12, 3)
    np.testing.assert_array_equal(np.isnan(got), np.isnan(oracle))
    np.testing.assert_allclose(got[~np.isnan(got)],
                               oracle[~np.isnan(oracle)], rtol=0, atol=0)


def test_series_jsonl_and_csv_round_trip(tmp_path):
    rng = np.random.default_rng(11)
    rec = SeriesRecorder(n_regions=3, window=4)
    _feed_recorder(rec, rng, n_slots=6, r=3)
    jp = tmp_path / "series.jsonl"
    rec.to_jsonl(jp)
    rows = SeriesRecorder.read_jsonl(jp)
    ts = rec.timeseries()
    assert len(rows) == 6
    for t, row in enumerate(rows):
        assert row["slot"] == int(ts["slot"][t])
        assert row["queue_depth"] == ts["queue_depth"][t]
        assert row["arrivals"] == [float(x) for x in ts["arrivals"][t]]
        p95 = ts["p95_response_s"][t]
        assert (math.isnan(row["p95_response_s"]) if math.isnan(p95)
                else row["p95_response_s"] == p95)
    cp = tmp_path / "series.csv"
    rec.to_csv(cp)
    lines = cp.read_text().strip().splitlines()
    assert len(lines) == 7                       # header + 6 slots
    assert "arrivals_r0" in lines[0] and "saturation_r2" in lines[0]


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_make_obs_specs():
    assert make_obs(False) is None
    for spec in (None, True):
        obs = make_obs(spec)
        assert obs.counters is not None and obs.tracer is None
    assert make_obs("trace").tracer is not None
    assert make_obs("trace-xla").tracer.xla is True
    cfg = ObsConfig(counters=False, series=False, trace=True)
    obs = make_obs(cfg)
    assert obs.counters is None and obs.tracer is not None
    shared = Observability()
    assert make_obs(shared) is shared
    with pytest.raises(ValueError):
        make_obs("bogus")
    with pytest.raises(TypeError):
        make_obs(3.14)


# ---------------------------------------------------------------------------
# metrics satellites
# ---------------------------------------------------------------------------


def test_summary_zero_completions_reports_nan_not_zero():
    m = MetricsAggregator()
    s = m.summary()
    assert s["completed"] == 0
    for key in ("mean_response_s", "p50_response_s", "p95_response_s",
                "p99_response_s", "mean_wait_s", "mean_work_s",
                "mean_net_s"):
        assert math.isnan(s[key]), key
    # an all-dropping run must not score best-in-class on response
    m.record_drops(5, t=0)
    assert math.isnan(m.summary()["mean_response_s"])
    assert m.summary()["completion_rate"] == 0.0


def test_drops_by_slot_series():
    m = MetricsAggregator()
    m.record_drop(None, t=2)
    m.record_drops(3, t=2)
    m.record_drops(2, t=5)
    m.record_drops(0, t=6)               # no-op: no phantom slot entry
    assert m.dropped == 6
    assert m.drops_by_slot == {2: 4, 5: 2}
    np.testing.assert_array_equal(m.drops_series(8),
                                  [0, 0, 4, 0, 0, 2, 0, 0])


# ---------------------------------------------------------------------------
# engine wiring
# ---------------------------------------------------------------------------


def test_engine_counters_on_seeded_trajectory():
    topo, cs, src = _small_world(slots=8)
    eng = Engine(topo, cs.copy(), src, TortaScheduler(5, seed=0), seed=4)
    eng.run(8)
    rep = eng.run_report
    assert rep is not None
    arrived = rep.counter("engine.tasks.arrived")
    assert arrived == int(src.arrivals_matrix()[:8].sum())
    assert 0 < rep.counter("engine.tasks.assigned") <= arrived
    # a 5x10 fleet at 40% util collides constantly: the grouped apply's
    # same-server fallback must have fired
    assert rep.counter("engine.fallback.same_server_conflict") > 0
    assert rep.counter("engine.tasks.dropped") == rep.summary["dropped"]
    # series channels span the run
    assert len(rep.series_array("p95_response_s")) == 8
    assert rep.series_array("saturation").shape == (8, 5)
    # TORTA records its phase-1 forecast every slot
    assert not np.isnan(rep.series_array("forecast")).any()


def test_engine_obs_off_and_report_round_trip(tmp_path):
    topo, cs, src = _small_world(slots=4)
    eng = Engine(topo, cs.copy(), src, TortaScheduler(5, seed=0), seed=4,
                 obs=False)
    eng.run(4)
    assert eng.run_report is None
    eng2 = Engine(topo, cs.copy(), src, TortaScheduler(5, seed=0), seed=4)
    eng2.run(4)
    path = tmp_path / "report.json"
    eng2.run_report.save(path)
    rep = RunReport.load(path)
    assert rep.summary["completed"] == eng2.run_report.summary["completed"]
    assert rep.counters == eng2.run_report.counters
    assert rep.meta["n_slots"] == 4 and rep.meta["n_regions"] == 5
    np.testing.assert_array_equal(
        rep.series_array("queue_depth"),
        eng2.run_report.series_array("queue_depth"))
    # counters export in Prometheus text form
    text = eng2.obs.prometheus_text()
    parsed = parse_prometheus_text(text)
    assert parsed["repro_engine_tasks_arrived"] == \
        rep.counter("engine.tasks.arrived")


def test_obs_parity_bitwise_numpy_engine():
    """Default-on observability (and full tracing) changes NO metric:
    the layer is observation-only — on the numpy engine and on the fused
    path (fused scan + jitted engine step), whose spans and transfer
    counters sit around every dispatch."""
    topo, cs, src = _small_world(slots=6)

    def summarize(obs_spec, fused=False):
        sched = TortaScheduler(5, seed=0,
                               micro_backend="fused" if fused else None)
        return Engine(topo, cs.copy(), src, sched, seed=4,
                      step_backend="jax" if fused else "numpy",
                      obs=obs_spec).run(6).summary()

    s_off = summarize(False)
    s_def = summarize(None)
    s_trc = summarize("trace")
    for k in METRIC_KEYS:
        assert s_off[k] == s_def[k] == s_trc[k], k
    f_off = summarize(False, fused=True)
    f_trc = summarize("trace", fused=True)
    for k in METRIC_KEYS:
        assert f_off[k] == f_trc[k], k


def test_decision_host_sync_counter():
    jnp = pytest.importorskip("jax.numpy")
    cs = make_cluster_state(2, seed=0, servers_per_region=(3, 4))
    obs = Observability()
    with obs_rt.activate(obs):
        dec = BatchDecision(region=jnp.array([0, 1], np.int32),
                            server=jnp.array([1, 2], np.int32))
        dec.validate(2, cs)
        # numpy-backed decisions never count a sync
        BatchDecision(region=np.array([0], np.int32),
                      server=np.array([1], np.int32)).validate(1, cs)
    assert obs.counters.get("decision.host_sync") == 1


# ---------------------------------------------------------------------------
# the acceptance trajectory: fused 15x40 flash_crowd with tracing
# ---------------------------------------------------------------------------


def _run_fused_15x40(obs_spec):
    topo = _topology(15, seed=1)
    cs = make_cluster_state(15, seed=3, servers_per_region=(40, 41))
    rate = 0.3 * throughput_per_slot(cs) / 15
    src = make_source("flash_crowd", 10, 15, seed=2, base_rate=rate)
    sched = TortaScheduler(15, seed=0, micro_backend="fused")
    eng = Engine(topo, cs.copy(), src, sched, seed=0,
                 step_backend="jax", obs=obs_spec)
    eng.run(10)
    return eng


def test_fused_run_report_acceptance():
    eng_off = _run_fused_15x40(False)
    eng = _run_fused_15x40("trace")
    rep = eng.run_report

    # observation-only: every summary metric bitwise equal to obs-off
    s_off = eng_off.metrics.summary()
    for k in METRIC_KEYS:
        assert s_off[k] == rep.summary[k], k

    # per-slot series of length n_slots
    assert len(rep.series_array("p95_response_s")) == 10
    assert len(rep.series_array("queue_depth")) == 10
    assert rep.series_array("saturation").shape == (10, 15)

    # nonzero retrace counters (fused scan + jitted engine step) and
    # nonzero numpy-fallback activations
    assert rep.counter("micro.retrace.scan_all") > 0
    assert rep.counter("engine.retrace.close_step") > 0
    assert rep.counter("engine.fallback.same_server_conflict") > 0
    # exactly one device->host sync per slot on the fused micro path
    assert rep.counter("micro.host_sync.scan_all") == 10

    # span table with at least 4 named phases, spans monotone
    names = rep.span_names()
    assert len(names) >= 4
    for phase in ("schedule.batch", "macro.phase1", "micro.assign",
                  "engine.apply"):
        assert phase in names, phase
    starts = [r.t_start for r in eng.obs.tracer.records]
    assert starts == sorted(starts)
    assert all(r.duration_s >= 0 for r in eng.obs.tracer.records)


# ---------------------------------------------------------------------------
# export guard: series/summary values are finite or nan, never inf
# ---------------------------------------------------------------------------


def test_series_exports_are_finite_or_nan():
    """Infinities injected into every SeriesRecorder channel (upstream
    divide-by-zero artifacts) must export as nan — the finite-or-nan
    contract of ``timeseries()``."""
    from repro.obs.series import SeriesRecorder

    rec = SeriesRecorder(2)
    rec.note_forecast(np.array([np.inf, 1.0]))
    rec.end_slot(0, responses=np.array([np.inf, 3.0]),
                 queue_tasks=np.inf, arrivals=np.array([1.0, np.inf]),
                 drops=0, saturation=np.array([0.5, -np.inf]),
                 load_balance=np.inf)
    rec.end_slot(1, responses=np.array([1.0, 2.0]), queue_tasks=4.0,
                 arrivals=np.array([2.0, 2.0]), drops=1,
                 saturation=np.array([0.5, 0.5]), load_balance=0.9)
    ts = rec.timeseries()
    for name, arr in ts.items():
        assert not np.isinf(np.asarray(arr, np.float64)).any(), name
    # finite slots pass through untouched
    assert ts["queue_depth"][1] == 4.0
    assert ts["load_balance"][1] == 0.9
    # jsonl export never writes Infinity
    import json as _json
    rows = list(rec._rows())
    for row in rows:
        text = _json.dumps(row, default=float)
        assert "Infinity" not in text, text


def test_metrics_summary_finite_or_nan():
    """MetricsAggregator.summary() converts inf artifacts to nan while
    finite metrics stay bitwise identical."""
    from repro.sim.metrics import MetricsAggregator

    m = MetricsAggregator()
    m.record_completions(0, wait_s=[1.0, np.inf], work_s=[2.0, 3.0],
                         net_s=[0.0, 0.0])
    m.record_slot(0, utils=np.array([0.5, 0.5]), power_cost=np.inf,
                  switch_cost=1.0, overhead_s=0.0, n_switches=0,
                  queue_tasks=2.0)
    s = m.summary()
    for key, value in s.items():
        if isinstance(value, float):
            assert not np.isinf(value), key
    assert s["switch_cost_total"] == 1.0
    assert s["completed"] == 2

    # clean aggregator: bitwise identical summaries with the guard
    clean = MetricsAggregator()
    clean.record_completions(0, wait_s=[1.0, 2.0], work_s=[2.0, 3.0],
                             net_s=[0.0, 0.5])
    assert clean.summary() == clean.summary()
    assert clean.summary()["mean_wait_s"] == 1.5
