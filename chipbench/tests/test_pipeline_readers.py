"""The readers of the serving pipeline's spans and the host-stall counter,
on hand-built runs."""
import pytest

from chipbench.harness import Run, load_module
from repro.obs.trace import QueryTrace, Span


def _trace(tid, **ms):
    """A served trace whose spans last the given milliseconds."""
    tr = QueryTrace(trace_id=tid, t0=float(tid), attrs={"served": True})
    t = tr.t0
    for name, d in ms.items():
        tr.spans.append(Span(name=name, t0=t, t1=t + d / 1e3, depth=0,
                             attrs={}))
        t += d / 1e3
    return tr


@pytest.mark.parametrize("metric,span", [
    ("admit_wait_ms.online", "admit"),
    ("handoff_wait_ms.online", "handoff"),
    ("deliver_ms.bulk", "deliver"),
])
def test_span_readers_average_their_span(metric, span):
    read = load_module(f"layer_metrics/{metric}.py").read
    spans = [_trace(0, **{span: 2.0, "scan": 100.0}),
             _trace(1, **{span: 4.0, "scan": 50.0})]
    assert read(Run(spans=spans)) == pytest.approx(3.0)
    # a program without the span (the parent of the change) reads nothing
    assert read(Run(spans=[_trace(2, queue=5.0, scan=9.0)])) is None
    assert read(Run()) is None


@pytest.mark.parametrize("metric", ["host_stall_ms.bulk",
                                    "host_stall_ms.online"])
def test_host_stall_readers(metric):
    read = load_module(f"layer_metrics/{metric}.py").read
    grew = {"repro_host_stall_seconds_total": {"": 0.25},
            "repro_host_stalls_total": {"": 2.0}}
    assert read(Run(traced=grew)) == pytest.approx(250.0)
    # present and never grew: the profiled delta holds the name, no series
    assert read(Run(traced={"repro_host_stall_seconds_total": {}})) == 0.0
    # the profiled seconds, not the whole window (whose counters are read
    # after the profiler's export)
    assert read(Run(counters=grew,
                    traced={"repro_host_stall_seconds_total": {}})) == 0.0
    # obs off (an untraced run), or a program that keeps no such counter
    assert read(Run()) is None
    assert read(Run(counters=grew)) is None
    assert read(Run(traced={"repro_serve_queries_total": {"": 64.0}})) \
        is None
