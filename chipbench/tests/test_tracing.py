"""The trace reduction, on a trace recorded on a TPU v5e
(``record_trace.py``: a 20,000 x 1536 corpus served in 64-query batches
through ``fused-batch``, 0.15 s under the profiler)."""
from pathlib import Path

import numpy as np
import pytest

from chipbench import tracing
from chipbench.harness import Run, load_module

DATA = Path(__file__).parent / "data" / "served_scan.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary():
    return tracing.reduce_trace(DATA)


def test_window_busy_and_idle_add_up(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(0.164306994)
    assert 0 < summary.busy_s < summary.window_s
    idle = sum(summary.idle_by_host.values())
    assert summary.busy_s + idle == pytest.approx(summary.window_s,
                                                  rel=1e-9)


def test_ops_are_named_by_program_and_control_flow_is_left_out(summary):
    top = summary.top_ops()
    assert len(top) == 10
    assert all(": %" in name for name, _ in top)
    assert not any(name.endswith(" while") for name, _ in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert any(name.startswith("jit__fused_batch_scan: %sort")
               for name, _ in top)


def test_idle_gaps_name_host_events(summary):
    gaps = dict(summary.top_gaps())
    assert tracing.SHORT_GAPS in gaps
    assert "np.asarray(jax.Array)" in gaps


def test_kernel_events_and_roofline(summary):
    roof = load_module("layer_metrics/scan_kernel_roofline.bulk.py")
    calls, seconds = summary.op_seconds(roof.is_kernel)
    assert calls == 414 and seconds == pytest.approx(0.004487666)
    run = Run(trace=summary, device_kind="TPU v5 lite",
              traced={"repro_serve_batches_total": {
                  "bucket=64,executor=fused-batch,shed=False": 6.0}},
              store={"P": 69, "D": 1536, "C": 1024, "value_bytes": 1})
    share = roof.read(run)
    # least time per call: 2,228,224 bytes at 819 GB/s
    expect = 100 * 414 * 2_228_224 / 819e9 / 0.004487666
    assert share == pytest.approx(expect, rel=1e-6)
    assert 0 < share < 100
    idle = load_module("layer_metrics/device_idle.bulk.py").read(run)
    assert idle == pytest.approx(
        100 * (1 - summary.busy_s / summary.window_s))


def test_merge_is_a_union():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]], np.float64)
    np.testing.assert_array_equal(
        tracing._merge(iv), [[0, 3], [5, 9], [10, 11]])


def test_attribute_prefers_the_most_specific_host_event():
    gaps = np.array([[0, 100_000], [200_000, 210_000]], np.float64)
    host = [(0, 1_000_000, "bench.wait"), (0, 100_000, "PjitFunction(f)")]
    out = tracing._attribute(gaps, host)
    assert out == {"PjitFunction(f)": 1e-4, tracing.SHORT_GAPS: 1e-5}
