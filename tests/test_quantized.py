"""Quantized device mirrors + fused executors: the dtype policy
(``core.layout.device_mirror``), the fused-scan/fused-batch executors at
f32/bf16/int8 with both kernel bodies (Pallas interpret mode gates the
kernels on CPU), exact-recall-after-re-rank on seed datasets incl. a
churned ``MutablePDXStore``, and the 8-fake-device sharded paths scanning
bf16/int8 mirrors (see tests/test_dist.py for the subprocess harness)."""
import numpy as np
import pytest

from repro.core.engine import SearchSpec, VectorSearchEngine
from repro.core.layout import device_mirror
from repro.core.plan import plan_search
from repro.data.synthetic import ground_truth, make_dataset, recall_at_k

from test_dist import run_devices

DTYPES = ("f32", "bf16", "int8")


# ----------------------------------------------------------------- spec knobs
def test_spec_validates_scan_knobs():
    assert SearchSpec().scan_dtype == "f32"
    assert SearchSpec(scan_dtype="int8", kernel="pallas").rerank_mult == 4
    for bad in (
        dict(scan_dtype="fp8"), dict(kernel="cuda"), dict(rerank_mult=0),
    ):
        with pytest.raises(ValueError):
            SearchSpec(**bad)


# -------------------------------------------------------------- device mirror
def test_device_mirror_caching_and_versions():
    X, _ = make_dataset(600, 24, "normal", n_queries=1, seed=0)
    eng = VectorSearchEngine.build(X, pruner="linear", capacity=128)
    eng.insert(np.zeros((1, 24), np.float32))  # upgrade to mutable first
    m1 = device_mirror(eng.store, "int8")
    assert m1.data.dtype == np.int8 and m1.bytes_per_value == 1
    assert device_mirror(eng.store, "int8") is m1  # cached per version
    assert device_mirror(eng.store, "bf16").bytes_per_value == 2

    # head-only insert: sealed tiles untouched -> same mirror object
    eng.insert(np.ones((1, 24), np.float32))
    assert device_mirror(eng.store, "int8") is m1
    # compact moves sealed tiles -> stale entries evicted, fresh quantization
    eng.compact()
    m2 = device_mirror(eng.store, "int8")
    assert m2 is not m1 and m2.tiles_version == eng.store.tiles_version
    assert all(
        k[1] == eng.store.tiles_version for k in eng.store._mirror_cache
    )

    with pytest.raises(ValueError, match="scan dtype"):
        device_mirror(eng.store, "fp64")


def test_int8_mirror_roundtrip_error_bounded():
    """Exact-range quantization: reconstruction error of live values is at
    most half a quantization step of the observed per-dim deviation."""
    X, _ = make_dataset(2000, 16, "skewed", n_queries=1, seed=3)  # heavy tails
    eng = VectorSearchEngine.build(X, pruner="linear", capacity=256)
    m = device_mirror(eng.store, "int8")
    T = np.asarray(eng.store.data)
    live = np.asarray(eng.store.ids) >= 0
    deq = (np.asarray(m.data, np.float32)
           * np.asarray(m.scale)[None, :, None]
           + np.asarray(m.offset)[None, :, None])
    err = np.abs(deq - T)[np.broadcast_to(live[:, None, :], T.shape)]
    step = np.asarray(m.scale).max()
    assert err.max() <= step / 2 + 1e-5  # no clipping, ever


# ---------------------------------------------------- fused executor parity
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", ["pallas", "jnp"])
def test_fused_executors_exact_on_nonaligned_store(dtype, kernel, rng):
    """fused-scan + fused-batch vs brute-force ground truth at non-aligned
    D with PAD lanes (n % capacity != 0): recall@k == 1.0 after the f32
    re-rank, and bf16 returns bitwise-identical ids to ground truth."""
    X, Q = make_dataset(1900, 50, "normal", n_queries=4, seed=7)
    gt_ids, gt_d = ground_truth(X, Q, k=5)
    eng = VectorSearchEngine.build(X, pruner="adsampling", capacity=256)
    spec = SearchSpec(k=5, scan_dtype=dtype, kernel=kernel)

    res = eng.search(Q, spec.replace(executor="fused-batch"))
    assert recall_at_k(res.ids, gt_ids) == 1.0, (dtype, kernel, res.ids)
    if dtype == "bf16":
        np.testing.assert_array_equal(res.ids, gt_ids)  # bitwise-equal ids
    if dtype != "f32":  # re-ranked distances are exact f32
        np.testing.assert_allclose(
            np.sort(res.dists, axis=1), np.sort(gt_d, axis=1),
            rtol=1e-4, atol=1e-3,
        )

    r1 = eng.search(Q[0], spec.replace(executor="fused-scan"))
    assert set(r1.ids.tolist()) == set(gt_ids[0].tolist()), (dtype, kernel)


def test_fused_planner_dispatch():
    X, _ = make_dataset(512, 16, "normal", n_queries=1, seed=1)
    store = VectorSearchEngine.build(X, pruner="linear", capacity=128).store
    spec = SearchSpec(k=5)

    # default CPU dispatch is unchanged (kernel="auto" resolves to jnp)
    assert plan_search(spec, store, 1).executor == "adaptive"
    assert plan_search(spec, store, 4).executor == "batch-matmul"
    # forcing pallas or requesting a mirror dtype engages the fused path
    p = plan_search(spec.replace(kernel="pallas"), store, 1)
    assert p.executor == "fused-scan" and "pallas" in p.reason
    p = plan_search(spec.replace(scan_dtype="bf16"), store, 4)
    assert p.executor == "fused-batch" and "bf16" in p.reason
    # non-l2 single queries take the batch kernel (megakernel is L2-only)
    p = plan_search(spec.replace(scan_dtype="int8", metric="ip"), store, 1)
    assert p.executor == "fused-batch"
    p = plan_search(spec.replace(scan_dtype="int8"), store, 1)
    assert p.executor == "fused-scan"


def test_fused_scan_rejects_non_l2():
    X, Q = make_dataset(400, 16, "normal", n_queries=1, seed=2)
    eng = VectorSearchEngine.build(X, pruner="linear", capacity=128)
    with pytest.raises(ValueError, match="L2-only"):
        eng.search(Q[0], SearchSpec(k=3, metric="l1",
                                    executor="fused-scan"))


def test_fused_ivf_store_scans_exactly(rng):
    """With an IVF engine the fused executors scan every bucket (exact),
    and fused-scan seeds its threshold from the routed nearest bucket."""
    X, Q = make_dataset(2048, 32, "clustered", n_queries=3, seed=4)
    gt_ids, _ = ground_truth(X, Q, k=5)
    eng = VectorSearchEngine.build(
        X, index="ivf", pruner="adsampling", capacity=128, nlist=8,
    )
    for dtype in ("bf16", "int8"):
        spec = SearchSpec(k=5, scan_dtype=dtype, kernel="jnp")
        res = eng.search(Q, spec)
        assert res.plan.executor == "fused-batch", res.plan
        assert recall_at_k(res.ids, gt_ids) == 1.0, dtype
        r1 = eng.search(Q[0], spec)
        assert r1.plan.executor == "fused-scan", r1.plan
        assert set(r1.ids.tolist()) == set(gt_ids[0].tolist()), dtype


def test_fused_batch_counts_its_slot_topk_batch():
    """With telemetry on, one fused-batch search counts one slot-wise top-k
    batch (certified on this data) and answers as it does with it off."""
    from repro.obs import metrics, trace

    X, Q = make_dataset(1900, 50, "normal", n_queries=4, seed=7)
    gt_ids, _ = ground_truth(X, Q, k=5)
    eng = VectorSearchEngine.build(X, pruner="adsampling", capacity=256)
    spec = SearchSpec(k=5, scan_dtype="int8", kernel="jnp",
                      executor="fused-batch")
    off = eng.search(Q, spec)
    reg = metrics.get_registry()
    reg.reset()
    metrics.set_enabled(True)
    try:
        on = eng.search(Q, spec)
        name = "repro_slot_topk_batches_total"
        assert reg.sum(name) == 1.0
        assert reg.get(name, outcome="certified") == 1.0
    finally:
        metrics.set_enabled(False)
        reg.reset()
        trace.get_tracer().clear()
    np.testing.assert_array_equal(on.ids, off.ids)
    np.testing.assert_array_equal(on.dists, off.dists)
    assert recall_at_k(on.ids, gt_ids) == 1.0


# ------------------------------------------------------------- churned store
def test_fused_executors_on_churned_mutable_store():
    """A churned MutablePDXStore answers through the quantized fused path
    exactly like a store rebuilt from the survivors: write-head rows are
    merged exactly, tombstones never surface, and the mirror re-quantizes
    only when sealed tiles change."""
    X, Q = make_dataset(1500, 24, "normal", n_queries=3, seed=9)
    eng = VectorSearchEngine.build(X, pruner="linear", capacity=128)
    rows = {i: X[i] for i in range(len(X))}
    rng = np.random.default_rng(5)
    new = rng.standard_normal((40, 24)).astype(np.float32)
    ids = eng.insert(new)
    for r, i in enumerate(ids):
        rows[int(i)] = new[r]
    dels = rng.choice(1500, size=200, replace=False)
    eng.delete(dels)
    for i in dels:
        rows.pop(int(i), None)

    im = np.asarray(sorted(rows))
    Xs = np.stack([rows[i] for i in sorted(rows)])
    gt_ids, _ = ground_truth(Xs, Q, k=5)

    def check():
        for dtype in ("bf16", "int8"):
            res = eng.search(
                Q, SearchSpec(k=5, scan_dtype=dtype, kernel="jnp"))
            assert res.plan.executor == "fused-batch", res.plan
            got = np.searchsorted(im, np.asarray(res.ids))
            assert recall_at_k(got, gt_ids) == 1.0, dtype

    check()          # mid-churn: head merged exactly, tombstones invisible
    v0 = eng.store.tiles_version
    eng.compact()
    assert eng.store.tiles_version > v0
    check()          # post-compact: mirror rebuilt from the new tiles


# ------------------------------------------------- sharded mirrors (8 dev)
def test_routed_bucket_bf16_parity_8dev():
    """Satellite: the routed-bucket path scanning a bf16 mirror returns the
    true top-k at full probe and agrees with the f32 routed run at partial
    probe, on the seed dataset."""
    run_devices("""
    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.data.synthetic import make_dataset, ground_truth, recall_at_k

    X, Q = make_dataset(2048, 32, "clustered", n_queries=6, seed=0)
    nlist = 16
    mesh = jax.make_mesh((8,), ("data",))
    eng = VectorSearchEngine.build(X, index="ivf", pruner="linear",
                                   capacity=64, nlist=nlist, mesh=mesh)
    gt_ids, gt_d = ground_truth(X, Q, k=5)

    for dt in ("bf16", "int8"):
        res = eng.search(Q, SearchSpec(k=5, nprobe=nlist, scan_dtype=dt))
        assert res.plan.executor == "routed_bucket", res.plan
        assert recall_at_k(res.ids, gt_ids) == 1.0, dt
        np.testing.assert_allclose(   # re-ranked dists are exact f32
            np.sort(res.dists, axis=1), np.sort(gt_d, axis=1),
            rtol=1e-4, atol=1e-3)

    for nprobe in (1, 4):
        rf = eng.search(Q, SearchSpec(k=5, nprobe=nprobe))
        rq = eng.search(Q, SearchSpec(k=5, nprobe=nprobe,
                                      scan_dtype="bf16"))
        for qi in range(len(Q)):
            assert set(rq.ids[qi].tolist()) == set(rf.ids[qi].tolist()), \
                (nprobe, qi)
    print("OK")
    """)


def test_batch_block_sharded_quantized_one_allgather_8dev():
    """The quantized batch-block path still issues exactly ONE all-gather
    per batch (carrying exact f32 candidates — see pdx_sharded for why the
    wire is not rounded), and matches ground truth after its on-shard f32
    re-rank."""
    run_devices("""
    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.core.layout import build_flat_store, device_mirror
    from repro.core.plan import _get_placement
    from repro.data.synthetic import make_dataset, ground_truth, recall_at_k
    from repro.dist.pdx_sharded import (collective_counts,
                                        search_batch_block_sharded)

    X, Q = make_dataset(2048, 32, "normal", n_queries=8, seed=0)
    gt_ids, _ = ground_truth(X, Q, k=5)
    mesh = jax.make_mesh((8,), ("data",))
    eng = VectorSearchEngine.build(X, pruner="linear", capacity=128,
                                   mesh=mesh)
    for dt in ("bf16", "int8"):
        res = eng.search(Q, SearchSpec(k=5, scan_dtype=dt))
        assert res.plan.executor == "batch-block-sharded", res.plan
        assert recall_at_k(res.ids, gt_ids) == 1.0, dt

    pl = _get_placement(eng.store, 8, "block")
    mirror = device_mirror(eng.store, "int8")
    counts = collective_counts(
        lambda qq: search_batch_block_sharded(
            mesh, Q=qq, k=5, placement=pl, mirror=mirror),
        jnp.asarray(Q))
    assert counts == {"all_gather": 1}, counts
    print("OK")
    """)
