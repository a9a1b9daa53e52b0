"""The harness end to end on the CPU at a tiny size, and the entry
point's refusals."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import bench_testkit as kit
import pytest

from harness import check, runner


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kit.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, traced, capsys, monkeypatch):
    import jax
    monkeypatch.setattr(runner, "compile_cache_dir", lambda _root: "off")
    monkeypatch.setattr(check, "CHECK_TASKS", 3000)
    rc = runner.run_cell(root, kit.TINY, 2 ** 31 + 3, 1.0, traced,
                         time.perf_counter(), jax.devices()[0])
    out, err = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    return result, out, err


def test_rehearsal_untraced(root, capsys, monkeypatch):
    result, out, err = _run(root, False, capsys, monkeypatch)
    assert result["correct"] is True
    assert list(result)[-1] == "check"
    assert set(result["check"]) == set(check.LIMITS)
    assert set(result["metrics"]) == {"slot_s", "decision_p95_ms", "setup_s"}
    assert result["metrics"]["slot_s"]["unit"] == "s/slot"
    assert 0 < result["metrics"]["slot_s"]["value"] < 1.0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] >= 1
    assert "0 inside it" in out             # nothing compiles in the window
    tail = err.strip().splitlines()[-len(check.LIMITS):]
    assert [line.split()[0] for line in tail] == list(check.LIMITS)


def test_rehearsal_traced(root, capsys, monkeypatch):
    result, _, _ = _run(root, True, capsys, monkeypatch)
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in ("macro_ms", "micro_wait_ms", "micro_build_ms", "apply_ms",
                 "close_ms", "conflict_rows_pct", "device_idle_pct"):
        assert name in metrics, sorted(metrics)
    assert 0 <= metrics["conflict_rows_pct"]["value"] <= 100
    assert "slot_s" not in metrics
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_compile_inside_the_window_fails_the_run(root, capsys,
                                                  monkeypatch):
    import jax
    import numpy as np

    from harness import program
    from harness.manifest import Manifest
    monkeypatch.setattr(runner, "compile_cache_dir", lambda _root: "off")
    inner = program.TimedScheduler.schedule_batch
    first = Manifest(root).config("tiny")["warmup_slots"] + 1

    def schedule_batch(self, obs, batch):
        if obs.t == first:
            jax.jit(lambda x: x * 3.0 + 1.0)(np.arange(7.0))
        return inner(self, obs, batch)

    monkeypatch.setattr(program.TimedScheduler, "schedule_batch",
                        schedule_batch)
    rc = runner.run_cell(root, kit.TINY, 2 ** 31 + 5, 1.0, False,
                         time.perf_counter(), jax.devices()[0])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "inside the measured window" in err
    assert '"correct"' not in out


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "paper-gabriel-diurnal", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    proc = _entry(kit.REPO)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(kit.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "tests"))
    shutil.copy(kit.REPO / "BENCHMARK.json", tmp_path)
    env = {"PYTHONPATH": ""}
    proc = _entry(tmp_path, env)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert not pathlib.Path(tmp_path, "src").exists()
