"""The per-layer readers of the slot's sub-spans, the transfer and upload
counters and the fused scan's device time, each against a synthetic layer context:
the value it reads, and None when what it reads is absent (as on a
program that lacks the span, counter or module)."""
import bench_testkit as kit
import pytest

from harness.manifest import Manifest
from harness.runner import LayerContext
from harness.trace import TraceSummary

SPAN_READERS = {
    "apply_conflict_ms": "engine.apply.conflict",
    "apply_single_ms": "engine.apply.single",
    "close_step_ms": "engine.close_step",
    "macro_ot_ms": "macro.ot",
    "micro_upload_ms": "micro.upload",
    "observe_ms": "engine.observe",
}


def _reader(name):
    return Manifest(kit.REPO).reader(name)


def _trace(device_ops):
    return TraceSummary(window_s=5.0, busy_s=0.7, devices=1,
                        device_ops=device_ops, idle_gaps=[])


def _ctx(spans=(), counters=None, slots=4, trace=None):
    return LayerContext(spans=list(spans), counters=dict(counters or {}),
                        slots=slots, trace=trace)


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader(metric):
    read = _reader(metric)
    span = SPAN_READERS[metric]
    spans = [(span, 1.0, 0.003), ("engine.apply", 1.0, 0.010),
             (span, 2.0, 0.005)]
    assert read(_ctx(spans)) == pytest.approx(1000.0 * 0.008 / 4)
    assert read(_ctx([("engine.apply", 1.0, 0.010)])) is None
    assert read(_ctx(spans, slots=0)) is None


LINK = {"device.h2d_bytes{layer=micro}": 600_000,
        "device.h2d_bytes{layer=engine}": 150_000,
        "device.h2d_bytes{layer=macro}": 50_000,
        "device.transfers{dir=h2d,layer=micro}": 80,
        "device.transfers{dir=d2h,layer=micro}": 4,
        "device.transfers{dir=h2d,layer=engine}": 40,
        "engine.tasks.assigned": 6000}


def test_h2d_kb_sums_the_layers():
    read = _reader("h2d_kb")
    assert read(_ctx(counters=LINK)) == pytest.approx(800.0 / 4)
    no_bytes = {k: v for k, v in LINK.items() if "h2d_bytes" not in k}
    assert read(_ctx(counters=no_bytes)) is None
    assert read(_ctx(counters=LINK, slots=0)) is None


def test_link_transfers_sums_both_directions():
    read = _reader("link_transfers")
    assert read(_ctx(counters=LINK)) == pytest.approx(124 / 4)
    no_count = {k: v for k, v in LINK.items() if "transfers" not in k}
    assert read(_ctx(counters=no_count)) is None
    assert read(_ctx(counters=LINK, slots=0)) is None


def test_scan_device_ms_reads_the_named_module_only():
    read = _reader("scan_device_ms")
    ops = [("jit_micro_scan_all(3022822456533254266)", 0.6),
           ("jit_micro_scan_all_checked(17)", 0.2),
           ("jit__unknown(17697524965668738612)", 0.06),
           ("jit_sinkhorn(5121322687130414258)", 0.03)]
    assert read(_ctx(trace=_trace(ops))) == pytest.approx(1000.0 * 0.6 / 4)
    # the parent's unnamed scan, and no trace at all
    assert read(_ctx(trace=_trace(ops[2:]))) is None
    assert read(_ctx(trace=None)) is None
