"""Every file the manifest names is found by name, and a new
configuration, traffic mix or per-layer metric is picked up by adding
files and entries alone."""
import json

import bench_testkit as kit
import numpy as np
import pytest

from harness import world
from harness.manifest import Manifest


def test_every_named_file_is_found():
    m = Manifest(kit.REPO)
    for c in m.data["configs"]:
        cfg = m.config(c["name"])
        assert cfg["name"] == c["name"]
        assert c["file"].startswith("bench/")
    for w in m.data["workloads"]:
        assert m.cell(w["name"]) is w
        assert m.traffic(w["traffic"])["name"] == w["traffic"]
        m.config(w["config"])
    for metric in m.data["per_layer"]:
        assert callable(m.reader(metric["name"]))


def test_each_cell_reports_its_metrics():
    m = Manifest(kit.REPO)
    for w in m.data["workloads"]:
        e2e = {x["name"] for x in m.metrics_for("end_to_end", w["name"])}
        assert {"slot_s", "setup_s"} <= e2e
        assert m.metrics_for("per_layer", w["name"])
    assert [x["name"] for x in m.metrics_for(
        "end_to_end", "paper-gabriel-diurnal")] == [
        "slot_s", "decision_p95_ms", "setup_s"]


@pytest.mark.parametrize("kind", ["traffic", "config", "metric"])
def test_added_files_are_picked_up(tmp_path, kind):
    root = kit.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    data = json.loads((root / "BENCHMARK.json").read_text())
    if kind == "traffic":
        spec = json.loads((root / "bench/traffic/diurnal.json").read_text())
        spec.update(name="quiet", utilization=0.2)
        (root / "bench/traffic/quiet.json").write_text(json.dumps(spec))
        data["workloads"].append({"name": "paper-quiet",
                                  "config": "paper-gabriel",
                                  "traffic": "quiet", "chips": 1,
                                  "why": "test"})
    elif kind == "config":
        cfg = json.loads(
            (root / "bench/configs/paper-gabriel.json").read_text())
        cfg.update(name="paper-small", servers_per_region=[2, 3])
        (root / "bench/configs/paper-small.json").write_text(json.dumps(cfg))
        data["configs"].append({"name": "paper-small", "source": "test",
                                "file": "bench/configs/paper-small.json",
                                "reduced": ["servers_per_region"],
                                "why": "test"})
        data["workloads"].append({"name": "small-diurnal",
                                  "config": "paper-small",
                                  "traffic": "diurnal", "chips": 1,
                                  "why": "test"})
    else:
        (root / "bench/metrics/slots_seen.py").write_text(
            "def read(ctx):\n    return float(ctx.slots)\n")
        data["per_layer"].append({"name": "slots_seen", "unit": "slots",
                                  "better": "higher",
                                  "source": "program_counter",
                                  "layer": "engine loop", "moves": "slot_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    after = {p: p.read_bytes() for p in before}
    assert after == before          # no file that was there changed
    m = Manifest(root)
    if kind == "traffic":
        cell = m.cell("paper-quiet")
        cfg = m.config(cell["config"])
        fleet = world.make_fleet(cfg)
        quiet = world.Traffic(cfg, m.traffic("quiet"), fleet, 3)
        base = world.Traffic(cfg, m.traffic("diurnal"), fleet, 3)
        assert np.isclose(quiet.total_rate / base.total_rate, 0.2 / 0.35)
        assert len(quiet.slot(5)) < len(base.slot(5))
    elif kind == "config":
        fleet = world.make_fleet(m.config(m.cell("small-diurnal")["config"]))
        assert fleet.n_regions == 25
        assert set(np.diff(fleet.region_ptr)) <= {2, 3}
    else:
        ctx = type("Ctx", (), {"slots": 7})()
        assert m.reader("slots_seen")(ctx) == 7.0
        assert "slots_seen" in [x["name"] for x in m.metrics_for(
            "per_layer", "paper-gabriel-diurnal")]


@pytest.mark.parametrize("config", ["paper-gabriel", "paper-cost2"])
def test_generators_match_the_programs(config):
    """The copied fleet and topology generators draw what the program's
    own generators draw for each paper-size configuration."""
    from repro.sim import make_cluster_state, make_topology
    cfg = Manifest(kit.REPO).config(config)
    fleet = world.make_fleet(cfg)
    state = make_cluster_state(cfg["topology"]["nodes"],
                               seed=cfg["fleet"]["seed"])
    for name in ("region_ptr", "power_price", "gpu_id", "tflops", "mem_gb",
                 "power_w", "kind_id", "capacity", "switch_scale"):
        assert np.array_equal(getattr(fleet, name), getattr(state, name))
    latency, _ = world.make_latency(cfg)
    topo = make_topology(cfg["topology"]["name"],
                         seed=cfg["topology"]["seed"])
    assert np.array_equal(latency, topo.latency)


def test_traffic_is_level_and_seeded():
    cfg = Manifest(kit.REPO).config("paper-gabriel")
    spec = Manifest(kit.REPO).traffic("diurnal")
    fleet = world.make_fleet(cfg)
    big = 2 ** 31 + 7
    a = world.Traffic(cfg, spec, fleet, big)
    b = world.Traffic(cfg, spec, fleet, big)
    c = world.Traffic(cfg, spec, fleet, 7)
    assert np.array_equal(a.slot(11).origin, b.slot(11).origin)
    assert not np.array_equal(a.slot(11).work_s, c.slot(11).work_s)
    totals = a.rates(500).sum(axis=1)
    assert np.allclose(totals, a.total_rate)
    # every seed offers the same tasks per slot, in another order
    x, y = a.slot(11), c.slot(11)
    assert np.array_equal(np.sort(x.work_s), np.sort(y.work_s))
    assert np.array_equal(x.origin, y.origin)
    # the warm-up slots are the same for every seed
    s0 = cfg["warmup_slots"]
    for t in (0, s0 - 1):
        assert np.array_equal(a.slot(t).embeds, c.slot(t).embeds)
        assert np.array_equal(a.slot(t).work_s, c.slot(t).work_s)


def test_a_slot_does_not_depend_on_how_far_traffic_was_made():
    cfg = Manifest(kit.REPO).config("paper-gabriel")
    spec = Manifest(kit.REPO).traffic("diurnal")
    fleet = world.make_fleet(cfg)
    late = world.Traffic(cfg, spec, fleet, 5)
    walked = world.Traffic(cfg, spec, fleet, 5)
    for t in range(300):
        walked.slot(t)
    for t in (3, 150, 299, 1000):
        x, y = late.slot(t), walked.slot(t)
        for k in ("ids", "origin", "model_idx", "work_s", "deadline_slot",
                  "embeds"):
            assert np.array_equal(getattr(x, k), getattr(y, k)), (t, k)
