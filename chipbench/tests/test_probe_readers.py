"""The routed cell's readers (``route_ms.pool64``, ``probe_share.pool64``,
``scan_kernel_roofline.pool64``) on hand-built runs, and a tiny whole run
of ``gist960.pool64`` on the CPU."""
import copy
import time

import pytest

from chipbench import harness
from chipbench.harness import Run, load_module


class _Trace:
    def __init__(self, name, calls, seconds):
        self.name, self.calls, self.seconds = name, calls, seconds

    def op_seconds(self, match):
        return (self.calls, self.seconds) if match(self.name) else (0, 0.0)


class _Served:
    def __init__(self, **ms):
        self.ms = ms

    def find(self, name):
        if name not in self.ms:
            return None
        return type("S", (), {"duration_s": self.ms[name] / 1e3})()


def test_route_reader_averages_the_route_span():
    read = load_module("layer_metrics/route_ms.pool64.py").read
    run = Run(spans=[_Served(route=0.5, scan=9.0), _Served(route=1.5)])
    assert read(run) == pytest.approx(1.0)
    # a program that routes nothing on the device, or no served batch
    assert read(Run(spans=[_Served(scan=9.0)])) is None
    assert read(Run()) is None


def test_probe_share_reader():
    read = load_module("layer_metrics/probe_share.pool64.py").read
    counted = {"repro_ivf_probe_partitions_total": {
        "executor=fused-batch,set=scanned": 300.0,
        "executor=fused-batch,set=store": 1000.0,
        "executor=fused-scan,set=scanned": 100.0,
        "executor=fused-scan,set=store": 1000.0}}
    assert read(Run(counters=counted)) == pytest.approx(20.0)
    # nprobe not stated, obs off, or the parent program
    assert read(Run(counters={"repro_serve_batches_total": {"": 3.0}})) \
        is None
    assert read(Run()) is None


def test_pool64_roofline_reads_the_batched_kernel():
    read = load_module("layer_metrics/scan_kernel_roofline.pool64.py").read
    kernel = ("batched_distance_quant_pallas.1 = f32[16,1024] "
              "custom-call(...), custom_call_target=\"tpu_custom_call\"")
    store = {"P": 1500, "D": 960, "C": 1024, "value_bytes": 1}
    traced = {"repro_serve_batches_total": {
        "bucket=16,executor=fused-batch,shed=False": 10.0}}
    # 16 x 960 queries, a 960 x 1024 int8 tile, 16 x 1024 distances: the
    # bytes bound, 1,110,016 B at 819 GB/s = 1.3553 us a call
    run = Run(trace=_Trace(kernel, 400, 400 * 5.4212e-6), traced=traced,
              store=store, device_kind="TPU v5 lite")
    assert read(run) == pytest.approx(25.0, rel=1e-3)
    assert read(Run(trace=_Trace("fusion.3 = f32[16]", 400, 1.0),
                    traced=traced, store=store,
                    device_kind="TPU v5 lite")) is None
    assert read(Run()) is None


def test_tiny_routed_run_is_correct(monkeypatch):
    """``gist960.pool64`` at a tiny size on the CPU, its lists probed at
    half of nlist: the check passes and ``qps`` is reported."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    bench = harness.load_benchmark()
    cell, config, traffic = copy.deepcopy(
        harness.cell_parts(bench, "gist960.pool64"))
    config.update(n=3000, dim=64)
    config["data"]["n_clusters"] = 8
    config["build"].update(nlist=16, nprobe=8)
    config["check"]["sample"] = 32
    traffic["query_pool"] = 256
    out = harness.run_cell(
        "gist960.pool64", 2**35 + 11, 1.0, False,
        t_start=time.perf_counter(), bench=bench,
        parts=(cell, config, traffic), require_tpu=False,
    )
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"qps", "setup_s"}
