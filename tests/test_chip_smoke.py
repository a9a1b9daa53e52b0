"""``chip_smoke.py``'s path at toy size on the CPU.

The script itself refuses to run without a TPU; its phases (fleet and
traffic, fused engine run, numpy oracle run, outcome comparison) are
exercised here on a 3x8 fleet so that a broken path is found before any
chip time is spent.
"""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main() != 0
    out = capsys.readouterr().out
    assert "no TPU found" in out
    assert '"ok"' not in out


def test_fused_path_matches_numpy_oracle_toy(smoke):
    slots = 3
    world = smoke.build_world(3, 8, slots)
    fused, slot_s = smoke.run_engine(world, slots, fused=True)
    oracle, _ = smoke.run_engine(world, slots, fused=False)
    assert len(slot_s) == slots and min(slot_s) > 0.0
    assert fused.metrics.completed > 0
    assert smoke.compare(fused.metrics.summary(),
                         oracle.metrics.summary()) == {}
    assert smoke.response_agreement(fused, oracle).startswith("0 of ")
    counters = smoke.path_counters(fused.run_report.counters)
    assert any(k.startswith("micro.host_sync.scan_all") for k in counters)
    assert any(".retrace." in k for k in counters)


def test_compare_reports_each_difference(smoke):
    want = {k: 1.0 for k in smoke.COMPARED}
    got = dict(want, p95_response_s=1.5, mean_response_s=float("nan"))
    want["mean_response_s"] = float("nan")
    assert smoke.compare(got, want) == {"p95_response_s": (1.5, 1.0)}
    assert smoke.compare(got, want, rel=0.5) == {}
    assert smoke.compare(got, want, rel=0.49) == {"p95_response_s":
                                                   (1.5, 1.0)}
    # counts are held exactly, whatever the tolerance
    got["completed"] = 1.0 + 1e-9
    assert smoke.compare(got, want, rel=0.5) == {"completed":
                                                 (1.0 + 1e-9, 1.0)}


def test_float64_probe_is_exact_on_the_host_cpu(smoke):
    """XLA's CPU float64 is IEEE for upload, add, mul and div; its exp
    differs from numpy's by at most about one ulp."""
    probe = smoke.probe_f64(n=4096)
    for key in ("roundtrip", "add", "mul", "div"):
        assert probe[f"{key}_mismatch"] == 0, probe
    assert probe["exp_max_rel"] < 1e-15
