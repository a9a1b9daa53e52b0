"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; each lives in a file of
its own (``bench/configs/<name>.json``, ``bench/traffic/<name>.json``), and
each per-layer metric is a reader in ``bench/metrics/<name>.py`` with a
``read(ctx)`` function; a configuration may name its own copy of the
plain reference (``"reference": "bench/<path>.py"``).  Adding a cell, a
mix, a metric or a reference is adding such files and entries; nothing
here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import Callable, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


class Manifest:
    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.bench = self.root / BENCH_DIR.name
        with open(self.root / "BENCHMARK.json") as fh:
            self.data = json.load(fh)

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                       f"{', '.join(w['name'] for w in self.data['workloads'])}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(self.root / c["file"]) as fh:
                    return json.load(fh)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(self.bench / "traffic" / f"{name}.json") as fh:
            return json.load(fh)

    def metrics_for(self, kind: str, cell: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [m for m in self.data[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str) -> Callable:
        return _load(self.bench / "metrics" / f"{metric}.py",
                     f"bench_metric_{metric}").read


def _load(path: pathlib.Path, name: str):
    """The Python file ``path`` as a module named ``name``."""
    name = name.replace(".", "_").replace("-", "_").replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    # dataclasses look their module up while the file runs
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def reference_class(cfg: dict, root: Optional[pathlib.Path] = None):
    """The ``Reference`` class of the plain reference the configuration
    names under ``reference`` (a ``.py`` file under the checkout's
    ``bench/``, given from the root of the checkout ``root``, by default
    the one that holds this file), or ``harness.reference``'s."""
    name = cfg.get("reference")
    if name is None:
        from harness.reference import Reference
        return Reference
    root = pathlib.Path(root) if root is not None else BENCH_DIR.parent
    path = (root / name).resolve()
    if (path.suffix != ".py"
            or (root / BENCH_DIR.name).resolve() not in path.parents):
        raise ValueError(f"reference {name!r} of configuration "
                         f"{cfg.get('name')!r}: not a .py file under "
                         f"{BENCH_DIR.name}/")
    return _load(path, f"bench_reference_{name[:-3]}").Reference


def span_total(ctx, name: str) -> Optional[float]:
    """Seconds spent in span ``name`` inside the traced window (None when
    the span never opened there)."""
    rows = [d for n, _, d in ctx.spans if n == name]
    return sum(rows) if rows else None


def per_slot_ms(ctx, seconds: Optional[float]) -> Optional[float]:
    if seconds is None or ctx.slots <= 0:
        return None
    return 1000.0 * seconds / ctx.slots
