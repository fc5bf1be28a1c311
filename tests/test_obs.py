"""repro.obs telemetry tests: registry semantics (snapshot determinism,
log2 bucket edges, label-cardinality bound), tracer ring + disabled-mode
no-op guarantees, SearchStats on every executor, and the collective-meter
parity invariant on the routed 8-fake-device path (subprocess, see
tests/test_dist.py for why)."""
import json

import numpy as np
import pytest

from repro.core.engine import SearchSpec, VectorSearchEngine
from repro.core.pdxearch import SearchStats
from repro.data.synthetic import make_dataset
from repro.obs import metrics, trace
from repro.obs.metrics import MetricsRegistry, bucket_edge, bucket_index
from repro.obs.trace import Tracer

from test_dist import run_devices


@pytest.fixture
def obs():
    """Enable telemetry on a clean registry/ring; always restore disabled."""
    reg = metrics.get_registry()
    tr = trace.get_tracer()
    reg.reset()
    tr.clear()
    metrics.set_enabled(True)
    try:
        yield reg
    finally:
        metrics.set_enabled(False)
        reg.reset()
        tr.clear()


# ------------------------------------------------------------------- registry
def test_histogram_bucket_edges():
    # bucket i holds (2**(i-1), 2**i]; exact powers land on their own edge
    assert bucket_index(0.0) is None and bucket_index(-3.0) is None
    assert bucket_index(1.0) == 0
    assert bucket_index(1.0001) == 1
    assert bucket_index(2.0) == 1
    assert bucket_index(3.0) == 2
    assert bucket_index(4.0) == 2
    assert bucket_index(0.5) == -1
    assert bucket_index(0.3) == -1       # (0.25, 0.5]
    assert bucket_index(1e-30) == -64    # clamped underflow floor
    assert bucket_edge(None) == 0.0
    assert bucket_edge(3) == 8.0 and bucket_edge(-2) == 0.25


def test_snapshot_determinism():
    # same events, different arrival order and label kwarg order -> the
    # serialized snapshots are byte-identical
    events = [
        ("counter", "repro_x_total", 2.0, {"a": "1", "b": "2"}),
        ("counter", "repro_x_total", 1.0, {"b": "2", "a": "1"}),
        ("counter", "repro_x_total", 5.0, {"a": "9"}),
        ("gauge", "repro_g", 7.0, {"z": "q"}),
        ("observe", "repro_h", 3.0, {}),
        ("observe", "repro_h", 0.4, {}),
        ("observe", "repro_h", 1000.0, {}),
    ]
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    for kind, name, v, labels in events:
        getattr(r1, kind)(name, v, **labels)
    for kind, name, v, labels in reversed(events):
        getattr(r2, kind)(name, v, **labels)
    assert r1.dump_json() == r2.dump_json()
    snap = r1.snapshot()
    assert snap["counters"]["repro_x_total"]["a=1,b=2"] == 3.0
    assert snap["histograms"]["repro_h"][""]["count"] == 3


def test_label_cardinality_bound():
    reg = MetricsRegistry(max_series_per_metric=4)
    for i in range(10):
        reg.counter("repro_leak_total", 1.0, qid=str(i))
    series = reg.snapshot()["counters"]["repro_leak_total"]
    assert len(series) == 5                      # 4 real + the overflow sink
    assert series["other=true"] == 6.0
    assert reg.dropped_series == 6
    # existing series keep accumulating past the cap
    reg.counter("repro_leak_total", 1.0, qid="0")
    assert reg.get("repro_leak_total", qid="0") == 2.0


def test_get_sum_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("repro_bytes_total", 100.0, executor="a", component="scan")
    reg.counter("repro_bytes_total", 50.0, executor="a", component="wire")
    reg.counter("repro_bytes_total", 7.0, executor="b", component="scan")
    reg.gauge("repro_fill", 0.5)
    reg.observe("repro_lat_seconds", 0.3, executor="a")
    reg.observe("repro_lat_seconds", 0.6, executor="a")
    assert reg.get("repro_bytes_total", executor="b", component="scan") == 7.0
    assert reg.sum("repro_bytes_total", executor="a") == 150.0
    assert reg.sum("repro_bytes_total") == 157.0
    text = reg.prometheus_text()
    assert "# TYPE repro_bytes_total counter" in text
    assert 'repro_bytes_total{component="scan",executor="a"} 100' in text
    assert "# TYPE repro_lat_seconds histogram" in text
    # cumulative buckets: 0.3 -> le=0.5, 0.6 -> le=1; +Inf == count
    assert 'repro_lat_seconds_bucket{executor="a",le="0.5"} 1' in text
    assert 'repro_lat_seconds_bucket{executor="a",le="1"} 2' in text
    assert 'repro_lat_seconds_bucket{executor="a",le="+Inf"} 2' in text
    assert 'repro_lat_seconds_count{executor="a"} 2' in text


# --------------------------------------------------------------------- tracer
def test_tracer_ring_eviction(obs):
    tr = Tracer(capacity=3)
    for i in range(5):
        with tr.query(i=i):
            with tr.span("scan"):
                pass
    kept = tr.traces()
    assert len(kept) == 3
    assert [t.attrs["i"] for t in kept] == [2, 3, 4]
    assert kept[-1].span_names() == ("scan",)
    assert tr.last().attrs["i"] == 4


def test_tracer_no_nested_query_traces(obs):
    tr = trace.get_tracer()
    with trace.query(outer=True) as outer:
        with trace.query(inner=True) as inner:
            assert inner is None          # nested call records nothing
        with trace.span("scan"):
            pass
    assert len(tr.traces()) == 1
    assert outer.span_names() == ("scan",)


def test_disabled_mode_is_noop():
    assert not metrics.enabled()
    reg = metrics.get_registry()
    before = reg.dump_json()
    metrics.counter("repro_x_total", 1.0)
    metrics.gauge("repro_g", 1.0)
    metrics.observe("repro_h", 1.0)
    with trace.query(a=1) as t:
        assert t is None
        with trace.span("scan") as s:
            assert s is None
    assert trace.current_trace() is None
    assert trace.get_tracer().last() is None
    assert reg.dump_json() == before

    # a full engine search mutates neither registry nor ring, and the
    # result carries no trace — including the cascade executor, whose
    # per-stage survivor/byte meters must be strict no-ops when disabled
    X, Q = make_dataset(512, 16, "normal", n_queries=2, seed=0)
    eng = VectorSearchEngine.build(X, pruner="linear", capacity=128)
    res = eng.search(Q, SearchSpec(k=3))
    assert res.trace is None
    res = eng.search(Q, SearchSpec(k=3, cascade=("int8", "f32"),
                                   kernel="jnp"))
    assert res.plan.executor == "cascade-batch" and res.trace is None
    assert reg.dump_json() == before
    assert trace.get_tracer().last() is None

    # the async upload meters too: both the wait histogram and the overlap
    # gauge must leave the registry untouched when metrics are disabled
    from repro.obs.meters import cache_upload_wait

    cache_upload_wait(12.5, 100.0)
    cache_upload_wait(0.0, 0.0)
    assert reg.dump_json() == before


# ------------------------------------------------------------ engine telemetry
def test_engine_metrics_trace_and_stats_parity(obs, tmp_path):
    X, Q = make_dataset(2048, 32, "clustered", n_queries=4, seed=1)
    eng = VectorSearchEngine.build(
        X, index="ivf", pruner="adsampling", capacity=128, nlist=16,
    )
    stats = SearchStats()
    res = eng.search(Q[0], SearchSpec(k=5), stats=stats)
    assert res.plan.executor == "adaptive"
    qt = res.trace
    assert qt is not None and qt.attrs["executor"] == "adaptive"
    names = qt.span_names()
    assert names.index("plan") < names.index("route") < names.index("scan")
    assert "merge" in names
    assert qt.duration_s > 0 and all(s.duration_s >= 0 for s in qt.spans)

    snap = eng.metrics()
    assert snap["counters"]["repro_search_batches_total"]["executor=adaptive"] \
        == 1.0
    assert snap["counters"]["repro_search_queries_total"]["executor=adaptive"] \
        == 1.0
    # the registry mirrors the SearchStats work account exactly
    reg = metrics.get_registry()
    for kind, want in (
        ("total", stats.values_total),
        ("computed", stats.values_computed),
        ("avoided", stats.values_avoided),
    ):
        got = reg.get(
            "repro_pruning_values_total", executor="adaptive", kind=kind,
        )
        assert got == pytest.approx(want), (kind, got, want)
    hist = snap["histograms"]["repro_search_latency_seconds"]
    assert hist["executor=adaptive"]["count"] == 1

    # Perfetto export round-trips through engine.dump_trace
    path = tmp_path / "trace.json"
    doc = eng.dump_trace(str(path))
    names = [e["name"] for e in doc["traceEvents"]]
    assert "query" in names and "scan" in names
    assert json.loads(path.read_text()) == doc


def test_stats_populated_on_every_single_device_executor(obs):
    X, Q = make_dataset(2048, 32, "clustered", n_queries=4, seed=2)
    eng = VectorSearchEngine.build(
        X, index="ivf", pruner="adsampling", capacity=128, nlist=16,
    )
    total_1 = float(np.asarray(eng.store.counts).sum()) * eng.store.dim
    # exact=True: the executor scans the whole store at full width, so the
    # work account must be saturated (computed == total == live * D * B);
    # exact=False paths account only what they visit/compute
    flat = VectorSearchEngine.build(X, pruner="adsampling", capacity=128)
    cases = [
        (eng, "adaptive", SearchSpec(k=5), Q[0], False),
        (flat, "batch-matmul", SearchSpec(k=5), Q, True),
        (eng, "fused-scan", SearchSpec(k=5, scan_dtype="int8", kernel="jnp",
                                       executor="fused-scan"), Q[0], False),
        (eng, "fused-batch", SearchSpec(k=5, scan_dtype="bf16",
                                        executor="fused-batch"), Q, True),
    ]
    for e, name, spec, q, exact in cases:
        stats = SearchStats()
        res = e.search(q, spec, stats=stats)
        assert res.plan.executor == name, res.plan
        B = 1 if q.ndim == 1 else len(q)
        assert 0 < stats.values_total <= total_1 * B + 1e-6, name
        assert 0 < stats.values_computed <= stats.values_total, name
        if exact:
            assert stats.values_total == pytest.approx(total_1 * B), name
            assert stats.values_computed == stats.values_total, name
        assert stats.values_avoided == pytest.approx(
            stats.values_total - stats.values_computed
        ), name
        assert stats.partitions_visited > 0, name
    # jit-masked (flat store) obeys the same identity
    stats = SearchStats()
    res = flat.search(Q[0], SearchSpec(k=5, prefer_static=True), stats=stats)
    assert res.plan.executor == "jit-masked", res.plan
    assert stats.values_total > 0
    assert stats.values_avoided == pytest.approx(
        stats.values_total - stats.values_computed
    )


def test_cascade_stage_meters(obs):
    """The cascade executor reports per-stage survivors and realized bytes:
    survivors are monotone non-increasing across stages (each stage only
    prunes), never drop below k on an exact-recall config, and the byte
    meters reflect each stage mirror's width."""
    # flat store on normal data: true neighbours scatter across partitions,
    # so the scan stages (which exclude the exact START partition) must keep
    # at least ~k survivors per query for the re-rank to stay exact
    X, Q = make_dataset(2048, 32, "normal", n_queries=4, seed=6)
    eng = VectorSearchEngine.build(X, pruner="adsampling", capacity=128)
    cascade = ("proj8:int8", "int4", "f32")
    stats = SearchStats()
    res = eng.search(
        Q, SearchSpec(k=5, cascade=cascade, kernel="jnp",
                      executor="cascade-scan"),  # per-query meters under test
        stats=stats,
    )
    assert res.plan.executor == "cascade-scan", res.plan

    reg = metrics.get_registry()
    surv = [
        reg.get("repro_cascade_stage_survivors", stage=str(si),
                stage_name=cascade[si])
        for si in range(2)
    ]
    byts = [
        reg.get("repro_cascade_stage_bytes", stage=str(si),
                stage_name=cascade[si])
        for si in range(2)
    ]
    assert surv[0] >= surv[1] >= len(Q) * 5  # monotone, >= k per query
    # stage 0 streams every partition of the rank-8 int8 projection mirror;
    # stage 1 fetches at most the full int4 store (prefetch-skip can only
    # shrink it), and both meters carry real traffic
    P, C, D = (eng.store.num_partitions, eng.store.capacity, eng.store.dim)
    assert byts[0] == pytest.approx(len(Q) * P * 8 * C * 1)
    assert 0 < byts[1] <= len(Q) * P * D * C * 0.5
    # the realized d-tile meter never exceeds the partition-granular model
    # (an entering partition billed for its full stage mirror); stage 0's
    # single proj tile makes them equal there by construction
    pmodel = [
        reg.get("repro_cascade_stage_bytes_partition_model", stage=str(si),
                stage_name=cascade[si])
        for si in range(2)
    ]
    assert byts[0] == pytest.approx(pmodel[0])
    assert 0 < byts[1] <= pmodel[1]
    # the device-bytes account carries the same scan traffic per dtype,
    # plus the exact f32 START and re-rank components
    assert reg.get("repro_device_bytes_total", executor="cascade-scan",
                   component="scan", dtype="int8") == byts[0]
    assert reg.get("repro_device_bytes_total", executor="cascade-scan",
                   component="scan", dtype="int4") == byts[1]
    assert reg.get("repro_device_bytes_total", executor="cascade-scan",
                   component="start", dtype="f32") > 0
    assert reg.get("repro_device_bytes_total", executor="cascade-scan",
                   component="rerank", dtype="f32") > 0
    # SearchStats: total is the single-resolution full-scan equivalent;
    # cascade work may exceed it when pruning is weak (each stage re-reads
    # survivors at a new width), so only "avoided" is clamped at zero
    total_1 = float(np.asarray(eng.store.counts).sum()) * eng.store.dim
    assert stats.values_computed > 0
    assert stats.values_total == pytest.approx(total_1 * len(Q))
    assert stats.values_avoided == max(
        stats.values_total - stats.values_computed, 0.0
    )


def test_cascade_batch_meters_amortize_bytes(obs):
    """The batched cascade pays each stage's compacted-union gather ONCE
    per batch: its scan-bytes account must undercut B per-query mirror
    walks, and stage-0 bytes equal the pow2-padded union width exactly."""
    X, Q = make_dataset(2048, 32, "normal", n_queries=4, seed=6)
    eng = VectorSearchEngine.build(X, pruner="adsampling", capacity=128)
    cascade = ("proj8:int8", "int4", "f32")
    res = eng.search(Q, SearchSpec(k=5, cascade=cascade, kernel="jnp"))
    assert res.plan.executor == "cascade-batch", res.plan
    reg = metrics.get_registry()
    P, C = eng.store.num_partitions, eng.store.capacity
    from repro.core.plan import pow2_bucket

    # every slot outside the per-query START partition enters stage 0; the
    # batch's union is all live slots minus the intersection of the START
    # partitions, pow2-padded — with distinct starts that is all P*C slots
    b0 = reg.get("repro_cascade_stage_bytes", stage="0",
                 stage_name=cascade[0])
    assert b0 == pytest.approx(pow2_bucket(P * C, P * C) * 8 * 1)
    assert b0 <= len(Q) * P * 8 * C  # never worse than B per-query walks
    assert reg.get("repro_device_bytes_total", executor="cascade-batch",
                   component="scan", dtype="int8") == b0
    assert reg.get("repro_device_bytes_total", executor="cascade-batch",
                   component="rerank", dtype="f32") > 0


def test_cache_and_mutation_metrics(obs):
    X, _ = make_dataset(1024, 16, "normal", n_queries=1, seed=3)
    eng = VectorSearchEngine.build(X, pruner="linear", capacity=128)
    reg = metrics.get_registry()
    eng.insert(X[:8] + 0.5)
    assert reg.get("repro_store_mutations_total", op="insert") == 1.0
    assert reg.get("repro_store_rows_mutated_total", op="insert") == 8.0
    assert reg.get("repro_store_live_vectors") == 1032.0
    assert 0.0 < reg.get("repro_store_head_fill") <= 1.0
    eng.delete(np.arange(4))
    assert reg.get("repro_store_mutations_total", op="delete") == 1.0
    assert reg.get("repro_store_live_vectors") == 1028.0


# ----------------------------------------------- routed-path meter invariants
def test_routed_collective_meters_and_trace_8dev():
    run_devices("""
    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.core.pdxearch import SearchStats
    from repro.data.synthetic import make_dataset
    from repro.obs import metrics, trace

    metrics.set_enabled(True)
    X, Q = make_dataset(8192, 32, "clustered", n_queries=16, seed=0)
    mesh = jax.make_mesh((8,), ("data",))
    eng = VectorSearchEngine.build(
        X, index="ivf", pruner="linear", capacity=128, nlist=32, mesh=mesh,
    )
    reg = metrics.get_registry()
    n_batches = 3
    stats = SearchStats()
    for _ in range(n_batches):
        res = eng.search(Q, SearchSpec(k=5, nprobe=4, scan_dtype="bf16"),
                         stats=stats)
        assert res.plan.executor == "routed_bucket", res.plan

    # routed stats: work accounted over the selected buckets only
    full = float(np.asarray(eng.store.counts).sum()) * eng.store.dim
    assert 0 < stats.values_total <= full * len(Q) * n_batches
    assert stats.values_computed == stats.values_total  # no pruning on-shard
    assert stats.partitions_visited > 0

    # acceptance trace: plan -> route -> scan with rerank + merge recorded
    qt = res.trace
    names = qt.span_names()
    assert "plan" in names and "route" in names and "scan" in names, names
    assert "rerank" in names and "merge" in names, names
    assert qt.find("rerank").attrs.get("fused") == "on-shard"

    # collective gate: the issued account is exactly per-batch rounds
    # all-to-alls + ONE packed all-gather, and it matches what the compile
    # -time jaxpr meter counted per call
    issued_a2a = reg.get("repro_collectives_issued_total",
                         executor="routed_bucket", primitive="all_to_all")
    issued_ag = reg.get("repro_collectives_issued_total",
                        executor="routed_bucket", primitive="all_gather")
    per_call_a2a = reg.get("repro_collectives_per_call",
                           executor="routed_bucket", primitive="all_to_all")
    per_call_ag = reg.get("repro_collectives_per_call",
                          executor="routed_bucket", primitive="all_gather")
    assert issued_ag == n_batches, (issued_ag, n_batches)
    assert per_call_ag == 1.0, per_call_ag
    assert issued_a2a == per_call_a2a * n_batches, (issued_a2a, per_call_a2a)

    # wire/scan bytes recorded per component at the mirror dtype
    scan_b = reg.get("repro_device_bytes_total", executor="routed_bucket",
                     component="scan", dtype="bf16")
    a2a_b = reg.get("repro_device_bytes_total", executor="routed_bucket",
                    component="all_to_all", dtype="bf16")
    rr_b = reg.get("repro_device_bytes_total", executor="routed_bucket",
                   component="rerank", dtype="bf16")
    assert scan_b > 0 and a2a_b > 0 and rr_b > 0
    print("OK")
    """)


# ---------------------------------------------------------------------------
# Thread-locality: concurrent worker traces + cross-thread query lifecycle
# ---------------------------------------------------------------------------
def test_concurrent_worker_traces_land_in_shared_ring(obs):
    """N worker threads x M searches each: every query trace must land in
    the one shared ring with the full span taxonomy, and the aggregated
    registry (engine.metrics()) must account every query."""
    import threading

    X, Q = make_dataset(n=512, dim=16, n_queries=8, seed=0)
    eng = VectorSearchEngine.build(X, pruner="adsampling", capacity=128)
    tr = trace.get_tracer()
    tr.clear()
    n_threads, per_thread = 4, 5
    errs = []

    def worker(t):
        try:
            for i in range(per_thread):
                eng.search(Q[(t + i) % len(Q)], k=3)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    traces = tr.traces()
    assert len(traces) == n_threads * per_thread
    for qt in traces:
        assert qt.t1 > qt.t0
        assert "plan" in qt.span_names() and "scan" in qt.span_names()
    snap = eng.metrics()
    total = sum(
        snap["counters"]["repro_search_queries_total"].values()
    )
    assert total == n_threads * per_thread


def test_cross_thread_query_lifecycle_no_dangling_current(obs):
    """start_query on one thread, use/span on a worker, finish on a third:
    the trace lands in the ring with its spans, and NO thread is left with
    a dangling current trace."""
    import threading

    tr = trace.get_tracer()
    tr.clear()
    qt = tr.start_query(bucket=4)
    assert qt is not None and trace.current_trace() is None  # not bound here

    def worker():
        with tr.use(qt):
            assert trace.current_trace() is qt
            tr.span_at("queue", qt.t0, qt.t0 + 0.001, depth_at_drain=3)
            with tr.span("scan", executor="batch-matmul"):
                pass
        assert trace.current_trace() is None

    th = threading.Thread(target=worker)
    th.start()
    th.join()

    def finisher():
        tr.finish_query(qt)

    th2 = threading.Thread(target=finisher)
    th2.start()
    th2.join()
    assert trace.current_trace() is None        # starter thread not clobbered
    assert tr.last() is qt
    assert qt.span_names() == ("queue", "scan")
    assert qt.find("queue").attrs["depth_at_drain"] == 3
    # a new query on this thread still traces normally (no stale binding)
    with tr.query(n_queries=1) as q2:
        assert q2 is not None and q2 is not qt
    assert len(tr.traces()) == 2


def test_use_restores_previous_binding(obs):
    """A worker interleaving a served trace inside its own query context
    gets its own binding back afterwards (use() is re-entrant-safe)."""
    tr = trace.get_tracer()
    tr.clear()
    served = tr.start_query()
    with tr.query() as outer:
        with tr.use(served):
            assert trace.current_trace() is served
        assert trace.current_trace() is outer
    tr.finish_query(served)
    assert {t.trace_id for t in tr.traces()} == {
        served.trace_id, outer.trace_id
    }


# ---------------------------------------------------------------------------
# Host runtime: the stall sampler and the GC clock (obs.host)
# ---------------------------------------------------------------------------
def _sampler_threads():
    import threading

    return [t for t in threading.enumerate()
            if t.name == "repro-host-sampler"]


def test_host_sampler_counts_a_forced_stall_and_stops(obs):
    """A pure-Python busy loop under a long switch interval keeps the
    sampler off the interpreter for 0.3 s: one stall, about that long.
    Turning telemetry off stops the thread."""
    import sys
    import time

    assert len(_sampler_threads()) == 1
    assert obs.get("repro_host_stalls_total") == 0.0
    time.sleep(0.05)                      # the sampler is asleep in a step
    old = sys.getswitchinterval()
    sys.setswitchinterval(5.0)
    try:
        t_end = time.perf_counter() + 0.3
        while time.perf_counter() < t_end:
            pass
    finally:
        sys.setswitchinterval(old)
    deadline = time.perf_counter() + 5.0
    while (obs.get("repro_host_stalls_total") < 1
           and time.perf_counter() < deadline):
        time.sleep(0.01)
    assert obs.get("repro_host_stalls_total") >= 1.0
    assert obs.get("repro_host_stall_seconds_total") > 0.25
    metrics.set_enabled(False)
    assert _sampler_threads() == []


def test_host_counters_start_at_zero_and_gc_time_is_counted(obs):
    """The sampler registers its counters at zero (a reader tells 'never
    stalled' from 'not sampled'), and a collection adds to its generation's
    seconds once the sampler has flushed it."""
    import gc
    import time

    snap = obs.snapshot()["counters"]
    assert snap["repro_host_stalls_total"] == {"": 0.0}
    assert snap["repro_host_stall_seconds_total"] == {"": 0.0}
    assert set(snap["repro_host_gc_seconds_total"]) == {
        "generation=0", "generation=1", "generation=2"}
    gc.collect()
    deadline = time.perf_counter() + 5.0
    while (obs.get("repro_host_gc_seconds_total", generation=2) == 0.0
           and time.perf_counter() < deadline):
        time.sleep(0.01)
    assert obs.get("repro_host_gc_seconds_total", generation=2) > 0.0


def test_activity_stamps_its_interval_and_is_null_when_off(obs):
    import time

    with trace.activity("drain") as act:
        time.sleep(0.002)
    assert act.name == "drain" and act.t1 - act.t0 >= 0.002
    assert trace.get_tracer().last() is None  # bound to no trace
    metrics.set_enabled(False)
    with trace.activity("drain") as act:
        assert act is None


def test_concurrent_enable_toggles_leave_one_sampler_or_none(obs):
    """Threads flipping telemetry on and off under a short switch interval
    never leave two samplers running, and the last 'off' leaves none."""
    import sys
    import threading

    seen = []

    def flip():
        for _ in range(30):
            metrics.set_enabled(True)
            seen.append(len(_sampler_threads()))
            metrics.set_enabled(False)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=flip) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert max(seen) <= 1
    metrics.set_enabled(False)
    assert _sampler_threads() == []


def test_env_flag_starts_the_sampler_like_set_enabled():
    """``REPRO_OBS=1`` turns telemetry on at import, sampler included, so
    both ways of turning it on count stalls; off stops it."""
    import os
    import subprocess
    import sys

    code = (
        "import threading, repro.obs\n"
        "from repro.obs import metrics\n"
        "names = lambda: [t.name for t in threading.enumerate()]\n"
        "assert metrics.enabled() and 'repro-host-sampler' in names()\n"
        "snap = metrics.get_registry().snapshot()['counters']\n"
        "assert snap['repro_host_stall_seconds_total'] == {'': 0.0}\n"
        "metrics.set_enabled(False)\n"
        "assert 'repro-host-sampler' not in names()\n"
    )
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, REPRO_OBS="1", PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
