"""The trace reduction on a small trace recorded on a TPU v5e: a jitted
scan under ``micro.assign``/``micro.host_sync`` and a small op under
``engine.apply`` (after a 2 ms sleep), three times, inside the
``bench.window`` annotation."""
import bench_testkit as kit
import numpy as np
import pytest

from harness import trace as tr

SMALL = kit.BENCH / "testdata" / "v5e_small.xplane.pb"
SPANS = {"micro.assign", "micro.host_sync", "engine.apply"}


@pytest.fixture(scope="module")
def small():
    return tr.reduce_trace(str(SMALL), SPANS)


def test_window_and_busy(small):
    assert small.devices == 1
    assert small.window_s == pytest.approx(0.024287401, abs=1e-12)
    # the six module runs, four of them inside the window
    assert small.busy_s == pytest.approx(3.4637e-05, rel=1e-9)
    assert 0 < small.busy_s < small.window_s


def test_device_time_by_module(small):
    names = [n for n, _ in small.device_ops]
    assert names[0].startswith("jit__lambda(")
    # a module's span covers its ops and the short waits between them
    total = sum(s for _, s in small.device_ops)
    assert small.busy_s <= total < 1.05 * small.busy_s


def test_idle_time_is_split_over_the_open_spans(small):
    gaps = dict(small.idle_gaps)
    assert set(gaps) <= SPANS | {tr.OUTSIDE}
    # every idle instant is counted once: busy + idle = window
    assert small.busy_s + sum(gaps.values()) == pytest.approx(
        small.window_s, rel=1e-9)
    # each engine.apply span holds a 2 ms sleep with the device idle
    assert gaps["engine.apply"] > 3 * 0.002
    assert gaps["micro.host_sync"] > 0


def test_union_and_overlap():
    u = tr.union(np.array([0.0, 5, 1, 10]), np.array([2.0, 6, 3, 12]))
    assert u.tolist() == [[0, 3], [5, 6], [10, 12]]
    seg_s, seg_e, seg_l = tr._label_segments(
        [("a", 0.0, 10.0), ("b", 2.0, 4.0), ("c", 12.0, 13.0)])
    got = tr._overlap(np.array([1.0, 9.0]), np.array([3.0, 14.0]),
                      seg_s, seg_e, seg_l)
    # gap [1,3): a 1, b 1; gap [9,14): a 1, outside 2 + 1, c 1
    assert got == {"a": 2.0, "b": 1.0, "c": 1.0, tr.OUTSIDE: 3.0}
