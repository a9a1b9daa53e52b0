"""Shared helpers of the benchmark's CPU tests: a checkout-like root in a
temporary directory holding a copy of ``BENCHMARK.json`` and the
benchmark's data files, plus a tiny cell (25 regions of 3-5 servers) that
runs end to end on the CPU in seconds."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for path in (BENCH, REPO / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

TINY = "tiny-diurnal"


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A copy of the manifest and data files, with the tiny cell added."""
    root = tmp / "checkout"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, root / "bench" / sub)
    data = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "paper-gabriel.json").read_text())
    cfg.update(name="tiny", servers_per_region=[3, 5], warmup_slots=4)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    spec = json.loads((BENCH / "traffic" / "diurnal.json").read_text())
    spec.update(name="tiny", trace_seconds=0.5,
                horizon_factor=20.0)
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(spec))
    data["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    data["workloads"].append({"name": TINY, "config": "tiny",
                              "traffic": "tiny", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return root


def world_of(root: pathlib.Path, cell: str, seed: int):
    from harness import world
    from harness.manifest import Manifest
    m = Manifest(root)
    c = m.cell(cell)
    cfg = m.config(c["config"])
    fleet = world.make_fleet(cfg)
    latency, graph = world.make_latency(cfg)
    traffic = world.Traffic(cfg, m.traffic(c["traffic"]), fleet, seed)
    return cfg, fleet, latency, graph, traffic


def run_tiny(root: pathlib.Path, seed: int, seconds: float = 1.0):
    """The program through the harness at the tiny size; returns the
    run record."""
    from harness import program
    cfg, fleet, latency, graph, traffic = world_of(root, TINY, seed)
    return cfg, fleet, latency, program.run_program(
        cfg, traffic, fleet, latency, graph, seconds=seconds, obs_spec=None,
        on_open_extra=lambda: None, log=lambda _: None)
