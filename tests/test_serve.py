"""Serving engine + RAG pipeline tests (tiny model, CPU)."""
import jax
import numpy as np

from repro.configs import get_config
from repro.models.lm import build_model
from repro.serve.engine import GenerationEngine
from repro.serve.rag import RagPipeline


def _engine(arch="llama3.2-3b", cache_len=64):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, GenerationEngine(model=model, params=params, cache_len=cache_len)


def test_generate_batched_greedy_deterministic():
    cfg, eng = _engine()
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab, (3, 8)).astype(np.int32)}
    a = eng.generate(batch, max_new_tokens=5)
    b = eng.generate(batch, max_new_tokens=5)
    assert a.shape == (3, 5)
    np.testing.assert_array_equal(a, b)
    assert (a >= 0).all() and (a < cfg.vocab).all()


def test_generate_temperature_sampling_runs():
    cfg, eng = _engine()
    rng = np.random.default_rng(1)
    batch = {"tokens": rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)}
    out = eng.generate(batch, max_new_tokens=4, temperature=1.0, seed=7)
    assert out.shape == (2, 4)


def test_rag_pipeline_end_to_end():
    cfg, eng = _engine(cache_len=96)
    rng = np.random.default_rng(2)
    docs = rng.integers(0, cfg.vocab, (20, 12)).astype(np.int32)
    rag = RagPipeline.build(eng, docs, pruner="bond", index="flat", retrieve_k=2)
    q = {"tokens": rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)}
    out, doc_ids = rag.answer(q, max_new_tokens=4)
    assert out.shape == (2, 4)
    assert doc_ids.shape == (2, 2)
    assert (doc_ids >= 0).all() and (doc_ids < 20).all()


def test_rag_retrieves_self_document():
    """A query identical to a stored doc must retrieve that doc (exact BOND)."""
    cfg, eng = _engine(cache_len=96)
    rng = np.random.default_rng(3)
    docs = rng.integers(0, cfg.vocab, (16, 10)).astype(np.int32)
    rag = RagPipeline.build(eng, docs, pruner="bond", index="flat", retrieve_k=1)
    q = {"tokens": docs[5:6]}
    ids = rag.retrieve(q)
    assert ids[0, 0] == 5


def test_rag_add_documents_live():
    """Documents added after build are retrievable immediately (write-head),
    with ids that keep indexing doc_tokens."""
    cfg, eng = _engine(cache_len=96)
    rng = np.random.default_rng(4)
    docs = rng.integers(0, cfg.vocab, (12, 10)).astype(np.int32)
    rag = RagPipeline.build(eng, docs, pruner="bond", index="flat", retrieve_k=1)
    extra = rng.integers(0, cfg.vocab, (3, 10)).astype(np.int32)
    new_ids = rag.add_documents(extra)
    assert new_ids.tolist() == [12, 13, 14]
    assert rag.doc_tokens.shape == (15, 10)
    # self-retrieval of a freshly added (unflushed, write-head) document
    ids = rag.retrieve({"tokens": extra[1:2]})
    assert ids[0, 0] == 13
    # the full pipeline prepends the right doc tokens
    out, doc_ids = rag.answer({"tokens": extra[1:2]}, max_new_tokens=2)
    assert doc_ids[0, 0] == 13 and out.shape == (1, 2)


# ---------------------------------------------------------------------------
# Vector-serving tier: batcher primitives + VectorServer
# ---------------------------------------------------------------------------
import threading
import time
from concurrent.futures import Future

import pytest

from repro.core.engine import VectorSearchEngine
from repro.serve.batcher import (
    AdmissionQueue,
    DeadlineExceeded,
    QueryItem,
    ServerClosed,
    ServerOverloaded,
    pad_batch,
    shape_bucket,
)
from repro.serve.vector import VectorServer, jit_compile_count


def _item(spec="s", deadline=None, q=None):
    return QueryItem(
        query=q if q is not None else np.zeros(4, np.float32),
        spec=spec,
        future=Future(),
        t_enqueue=time.perf_counter(),
        deadline=deadline,
    )


def _vec_engine(n=1024, dim=32, seed=0, **kw):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, dim)).astype(np.float32)
    eng = VectorSearchEngine.build(
        X, pruner=kw.pop("pruner", "adsampling"),
        capacity=kw.pop("capacity", 256), **kw,
    )
    return eng, X


def test_shape_bucket_pow2():
    assert [shape_bucket(n, 64) for n in (1, 2, 3, 5, 8, 9, 64)] == [
        1, 2, 4, 8, 8, 16, 64
    ]
    assert shape_bucket(100, 64) == 64
    with pytest.raises(ValueError):
        shape_bucket(0, 64)


def test_pad_batch_repeats_last_row():
    Q = np.arange(12, dtype=np.float32).reshape(3, 4)
    P = pad_batch(Q, 8)
    assert P.shape == (8, 4)
    np.testing.assert_array_equal(P[3:], np.repeat(Q[-1:], 5, axis=0))
    assert pad_batch(Q, 3) is Q
    with pytest.raises(ValueError):
        pad_batch(Q, 2)


def test_admission_queue_empty_flush_times_out():
    q = AdmissionQueue(8)
    t0 = time.perf_counter()
    batch, expired = q.drain(4, window_s=0.0, timeout_s=0.02)
    assert batch == [] and expired == []
    assert time.perf_counter() - t0 < 1.0


def test_admission_queue_deadline_expiry_mid_queue():
    q = AdmissionQueue(8)
    live = _item()
    dead = _item(deadline=time.perf_counter() - 1.0)
    live2 = _item()
    for it in (live, dead, live2):
        assert q.put(it)
    batch, expired = q.drain(4, timeout_s=0.1)
    assert batch == [live, live2]
    assert expired == [dead]
    assert len(q) == 0


def test_admission_queue_groups_by_spec_preserving_order():
    q = AdmissionQueue(8)
    a1, b1, a2 = _item("a"), _item("b"), _item("a")
    for it in (a1, b1, a2):
        q.put(it)
    batch, _ = q.drain(4, timeout_s=0.1)
    assert batch == [a1, a2]          # same-spec coalesced
    batch2, _ = q.drain(4, timeout_s=0.1)
    assert batch2 == [b1]             # different spec waited its turn


def test_admission_queue_backpressure_and_close():
    q = AdmissionQueue(2)
    assert q.put(_item()) and q.put(_item())
    assert not q.put(_item())          # full -> reject, never block
    q.close()
    with pytest.raises(ServerClosed):
        q.put(_item())
    # closed but non-empty: drain still returns the queued work
    batch, _ = q.drain(4, timeout_s=0.1)
    assert len(batch) == 2
    assert q.drain(4, timeout_s=0.1) == ([], [])


def test_server_single_query_smallest_bucket_no_recompile():
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="batch-matmul")
    with VectorServer(eng, spec=spec, max_batch=8) as srv:
        srv.warmup()
        ids, dists = srv.search(X[3])
        assert ids.shape == (5,) and ids[0] == 3
        assert srv.jit_compiles_since_warmup() == 0


def test_server_cascade_warmup_zero_recompiles():
    """warmup() with a cascade spec compiles every cascade executable: the
    survivor counts of a served workload change loop trip counts, never
    shapes, so queries whose survivors differ from the warm batch's must
    still mint nothing."""
    eng, X = _vec_engine(n=1024, dim=32)
    spec = eng.spec.replace(
        k=5, cascade=("int8", "f32"), kernel="jnp",
    )
    with VectorServer(eng, spec=spec, max_batch=8) as srv:
        srv.warmup()
        futs = [srv.submit(X[i]) for i in range(16)]
        for i, f in enumerate(futs):
            ids, _ = f.result()
            assert ids[0] == i
        assert srv.jit_compiles_since_warmup() == 0


def test_server_matches_engine_results():
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=10, executor="batch-matmul")
    ref = eng.search(X[:6], spec)
    with VectorServer(eng, spec=spec, max_batch=8) as srv:
        futs = [srv.submit(X[i]) for i in range(6)]
        for i, f in enumerate(futs):
            ids, dists = f.result(timeout=30)
            np.testing.assert_array_equal(ids, np.asarray(ref.ids)[i])


def test_server_shutdown_drains_in_flight():
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="batch-matmul")
    srv = VectorServer(eng, spec=spec, max_batch=4, flush_interval_s=0.0)
    futs = [srv.submit(X[i]) for i in range(12)]
    srv.close(drain=True)
    for i, f in enumerate(futs):
        ids, _ = f.result(timeout=1)   # already done: drain completed them
        assert ids[0] == i
    with pytest.raises(ServerClosed):
        srv.submit(X[0])


def test_server_close_without_drain_fails_queued():
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="batch-matmul")
    srv = VectorServer(eng, spec=spec, max_batch=4)
    futs = [srv.submit(X[i]) for i in range(8)]
    srv.close(drain=False)
    outcomes = set()
    for f in futs:
        try:
            f.result(timeout=1)
            outcomes.add("ok")
        except ServerClosed:
            outcomes.add("closed")
    assert "closed" in outcomes        # at least the still-queued ones failed


def test_server_deadline_exceeded():
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="batch-matmul")
    with VectorServer(eng, spec=spec, max_batch=4) as srv:
        fut = srv.submit(X[0], timeout_s=-0.001)   # already expired
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=30)


def test_server_overload_rejects():
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="batch-matmul")
    srv = VectorServer(eng, spec=spec, max_batch=1, queue_depth=1,
                       flush_interval_s=0.0)
    # stall the executor stage so submissions pile up in the bounded queue
    rejected = 0
    try:
        for i in range(200):
            try:
                srv.submit(X[i % len(X)])
            except ServerOverloaded:
                rejected += 1
                break
        assert rejected >= 1
    finally:
        srv.close(drain=True)


def test_server_mutations_and_version_fenced_maintenance():
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="batch-matmul")
    with VectorServer(eng, spec=spec, max_batch=8,
                      maintenance_interval_s=0.02,
                      head_fill_threshold=0.0) as srv:
        rng = np.random.default_rng(1)
        V = rng.standard_normal((4, X.shape[1])).astype(np.float32)
        new_ids = srv.insert(V).result(timeout=30)
        assert len(new_ids) == 4
        # a freshly inserted vector is immediately searchable via the server
        ids, _ = srv.search(V[2])
        assert ids[0] == new_ids[2]
        assert srv.delete([int(new_ids[0])]).result(timeout=30) == 1
        deadline = time.time() + 10
        while time.time() < deadline:
            if getattr(eng.store, "head_count", 1) == 0:
                break                   # background repack drained the head
            time.sleep(0.02)
        assert eng.store.head_count == 0
        ids, _ = srv.search(V[2])       # survives the adopted repack
        assert ids[0] == new_ids[2]


def test_store_adopt_version_fence():
    from repro.core.layout import MutablePDXStore, build_flat_store

    rng = np.random.default_rng(0)
    X = rng.standard_normal((100, 8)).astype(np.float32)
    ms = MutablePDXStore.from_store(build_flat_store(X, capacity=32),
                                    head_capacity=16)
    ms.insert(rng.standard_normal((2, 8)).astype(np.float32))
    base = ms.version
    clone = ms.clone()
    clone.repack()
    # a mutation lands between clone and adopt -> the swap must be refused
    ms.insert(rng.standard_normal((1, 8)).astype(np.float32))
    assert not ms.adopt(clone, expect_version=base)
    assert ms.num_vectors == 103
    # retry against the now-current version succeeds
    base2 = ms.version
    clone2 = ms.clone()
    clone2.repack()
    assert ms.adopt(clone2, expect_version=base2)
    assert ms.num_vectors == 103 and ms.head_count == 0


# ------------------------------------------------ tracing the served pipeline
@pytest.fixture
def obs_on():
    from repro.obs import metrics, trace

    reg, tr = metrics.get_registry(), trace.get_tracer()
    reg.reset()
    tr.clear()
    metrics.set_enabled(True)
    try:
        yield reg, tr
    finally:
        metrics.set_enabled(False)
        reg.reset()
        tr.clear()


def _serve_rounds(srv, X, rounds=3, per_round=4):
    """Submit ``rounds`` bursts, each awaited before the next, so every
    batch is planned while the executor is idle."""
    for r in range(rounds):
        futs = [srv.submit(X[r * per_round + i]) for i in range(per_round)]
        for i, f in enumerate(futs):
            assert f.result(timeout=60)[0][0] == r * per_round + i


def test_served_batch_writes_profiler_annotations(obs_on, tmp_path):
    """Under the profiler, every thread of the pipeline leaves its
    ``repro.*`` host events: the batcher's drain, plan and hand-off, the
    executor's wait for work, scan, query rotation, re-rank and delivery."""
    import glob

    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="fused-batch", scan_dtype="int8",
                            kernel="jnp")
    with VectorServer(eng, spec=spec, max_batch=8) as srv:
        srv.warmup()
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve_rounds(srv, X, rounds=2)
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    threads: dict = {}
    for plane in pd.planes:
        for i, line in enumerate(plane.lines):   # one line per thread
            for ev in line.events:
                if ev.name.startswith("repro."):
                    threads.setdefault(ev.name, set()).add((plane.name, i))
    for name in ("repro.drain", "repro.plan", "repro.handoff",
                 "repro.await", "repro.scan", "repro.transform",
                 "repro.rerank", "repro.deliver"):
        assert name in threads, sorted(threads)
    # each thread's work is on that thread's line
    assert threads["repro.drain"] == threads["repro.handoff"]
    assert threads["repro.await"] == threads["repro.deliver"]
    assert threads["repro.drain"].isdisjoint(threads["repro.deliver"])


def test_served_waits_tile_the_queue_span(obs_on):
    """``admit``, ``plan`` and ``handoff`` are disjoint, in that order, and
    add up to ``queue`` within 2 ms; the trace still starts at the
    executor's start, before any of its execution spans."""
    _, tr = obs_on
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="batch-matmul")
    with VectorServer(eng, spec=spec, max_batch=8) as srv:
        srv.warmup()
        tr.clear()
        _serve_rounds(srv, X, rounds=4)
    served = [t for t in tr.traces() if t.attrs.get("served")]
    assert len(served) >= 4
    for t in served:
        q, a, p, h = (t.find(n) for n in ("queue", "admit", "plan",
                                          "handoff"))
        assert q.t0 == a.t0 and h.t1 == q.t1
        assert a.t0 <= a.t1 <= p.t0 <= p.t1 == h.t0 <= h.t1
        total = a.duration_s + p.duration_s + h.duration_s
        assert abs(total - q.duration_s) < 2e-3, (total, q.duration_s)
        scan, deliver = t.find("scan"), t.find("deliver")
        assert q.t1 <= t.t0 <= scan.t0 < scan.t1 <= deliver.t0
        assert deliver.t1 <= t.t1
        assert deliver.attrs["n_queries"] == t.attrs["n_queries"]


def test_disabled_server_makes_no_annotation_and_no_sampler(monkeypatch):
    from repro.obs import metrics

    def refuse(*a, **kw):
        raise AssertionError("a profiler annotation was made while off")

    assert not metrics.enabled()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="fused-batch", scan_dtype="int8",
                            kernel="jnp")
    with VectorServer(eng, spec=spec, max_batch=8) as srv:
        _serve_rounds(srv, X, rounds=2)
        names = {t.name for t in threading.enumerate()}
    assert "repro-host-sampler" not in names
    assert {"serve-batcher", "serve-executor"} <= names


def test_removed_serving_histograms_are_absent(obs_on):
    reg, _ = obs_on
    eng, X = _vec_engine()
    spec = eng.spec.replace(k=5, executor="batch-matmul")
    with VectorServer(eng, spec=spec, max_batch=8) as srv:
        _serve_rounds(srv, X, rounds=1)
    hists = reg.snapshot()["histograms"]
    assert hists["repro_serve_queue_wait_seconds"][""]["count"] == 4
    assert "repro_serve_latency_seconds" not in hists
    assert "repro_serve_batch_fill" not in hists
