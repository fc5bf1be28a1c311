"""Placement + bucket-routed distributed search: greedy bucket->shard
assignment invariants, block-placement equivalence with the old padding,
the placement cache (keyed per (tiles_version, n_shards, kind) so two mesh
sizes never thrash), the routed executor's exactness vs single-host IVF
ground truth (8 fake devices, in subprocesses — see tests/test_dist.py for
why), and the per-batch collective gate: exactly one all-to-all plus one
packed all-gather, independent of B and nprobe."""
import dataclasses

import numpy as np
import pytest

from repro.core.engine import SearchSpec, VectorSearchEngine
from repro.core.plan import _get_placement, plan_search
from repro.data.synthetic import make_dataset
from repro.dist.placement import Placement, assign_buckets

from test_dist import run_devices


# ---------------------------------------------------------------- placement
def test_assign_buckets_greedy_balance():
    rng = np.random.default_rng(0)
    for trial in range(20):
        parts = rng.integers(0, 12, size=rng.integers(1, 40))
        n = int(rng.integers(1, 9))
        shard = assign_buckets(parts, n)
        assert shard.shape == parts.shape and (0 <= shard).all() and (shard < n).all()
        load = np.bincount(shard, weights=parts, minlength=n)
        # LPT bound: spread never exceeds the largest single bucket
        assert load.max() - load.min() <= max(int(parts.max(initial=0)), 1)
    # deterministic
    parts = np.asarray([5, 1, 3, 3, 0, 7])
    assert (assign_buckets(parts, 3) == assign_buckets(parts, 3)).all()


def test_block_placement_matches_legacy_padding():
    from repro.core.layout import PAD_VALUE, build_flat_store

    X, _ = make_dataset(500, 8, "normal", n_queries=1, seed=1)
    store = build_flat_store(X, capacity=64)  # 8 partitions
    pl = Placement.block(store.data, store.ids, 3)
    assert pl.num_slots == 9 and pl.parts_per_shard == 3
    np.testing.assert_array_equal(np.asarray(pl.data[:8]), np.asarray(store.data))
    assert (np.asarray(pl.data[8]) == PAD_VALUE).all()
    assert (np.asarray(pl.ids[8]) == -1).all()
    assert pl.part_perm.tolist() == [0, 1, 2, 3, 4, 5, 6, 7, -1]
    # already divisible: untouched, zero copies
    pl2 = Placement.block(store.data, store.ids, 4)
    assert pl2.data is store.data and pl2.ids is store.ids


def test_bucket_placement_invariants():
    X, _ = make_dataset(1024, 16, "clustered", n_queries=1, seed=2)
    eng = VectorSearchEngine.build(
        X, index="ivf", pruner="linear", capacity=64, nlist=8,
    )
    pl = _get_placement(eng.store, 4, "bucket", ivf=eng.ivf)
    assert pl.kind == "bucket" and pl.num_slots % 4 == 0
    # every source partition placed exactly once
    real = np.sort(pl.part_perm[pl.part_perm >= 0])
    np.testing.assert_array_equal(real, np.arange(eng.store.num_partitions))
    # each slot's bucket is owned by the shard whose slice holds the slot
    width = pl.parts_per_shard
    for i in range(pl.num_slots):
        b = pl.slot_bucket[i]
        if b >= 0:
            assert pl.bucket_shard[b] == i // width
    # arranged tiles are the source tiles, permuted
    src = np.asarray(eng.store.data)
    for i, p in enumerate(pl.part_perm):
        if p >= 0:
            np.testing.assert_array_equal(np.asarray(pl.data[i]), src[p])
    # the same slice holds every partition of each owned bucket contiguously
    pl.check()


def test_placement_check_rejects_corruption():
    X, _ = make_dataset(256, 8, "normal", n_queries=1, seed=3)
    eng = VectorSearchEngine.build(
        X, index="ivf", pruner="linear", capacity=64, nlist=4,
    )
    pl = _get_placement(eng.store, 2, "bucket", ivf=eng.ivf)
    dup = pl.part_perm.copy()
    dup[-1] = dup[0]  # place a partition twice
    with pytest.raises(ValueError, match="more than once"):
        dataclasses.replace(pl, part_perm=dup).check()
    flipped = pl.bucket_shard.copy()
    flipped[:] = (flipped + 1) % 2  # every bucket claims the other shard
    with pytest.raises(ValueError, match="span shard slices"):
        dataclasses.replace(pl, bucket_shard=flipped).check()


def test_placement_cache_no_thrash_across_mesh_sizes():
    """Satellite: the cache keys on (tiles_version, n_shards, kind), so one
    store serving two mesh sizes (or block + bucket layouts) keeps every
    entry live, and head-only inserts never invalidate them."""
    X, _ = make_dataset(600, 8, "normal", n_queries=1, seed=4)
    eng = VectorSearchEngine.build(
        X, index="ivf", pruner="linear", capacity=64, nlist=4,
    )
    eng.insert(np.zeros((1, 8), np.float32))  # upgrade to mutable
    store = eng.store
    a2 = _get_placement(store, 2, "block")
    a4 = _get_placement(store, 4, "block")
    b2 = _get_placement(store, 2, "bucket", ivf=eng.ivf)
    # alternating mesh sizes / kinds returns the SAME objects — no rebuild
    assert _get_placement(store, 2, "block") is a2
    assert _get_placement(store, 4, "block") is a4
    assert _get_placement(store, 2, "bucket", ivf=eng.ivf) is b2
    # head-only insert: tiles untouched -> placements stay valid
    eng.insert(np.ones((1, 8), np.float32))
    assert _get_placement(store, 2, "block") is a2
    # compact moves sealed tiles -> stale entries evicted, fresh ones built
    eng.compact()
    a2b = _get_placement(store, 2, "block")
    assert a2b is not a2
    assert all(k[0] == store.tiles_version for k in store._placement_cache)


# ------------------------------------------------------------- planner rules
class _FakeMesh:
    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


def test_planner_routes_ivf_on_data_mesh():
    X, _ = make_dataset(512, 16, "normal", n_queries=1, seed=5)
    store = VectorSearchEngine.build(X, pruner="linear", capacity=64).store
    spec = SearchSpec(k=5)
    ivf = object()
    mesh = _FakeMesh(data=8)
    p = plan_search(spec, store, 4, ivf=ivf, mesh=mesh)
    assert p.executor == "routed_bucket" and "bucket-owned" in p.reason
    # opt-out keeps the IVF routing host-side
    p = plan_search(spec.replace(routing="broadcast"), store, 4, ivf=ivf,
                    mesh=mesh)
    assert p.executor == "adaptive" and "broadcast" in p.reason
    # no data axis -> cannot route
    p = plan_search(spec, store, 1, ivf=ivf, mesh=_FakeMesh(model=8))
    assert p.executor == "adaptive" and "'data' axis" in p.reason
    # stats no longer pin the executor — the routed path fills SearchStats
    # from the selected buckets' host-side metadata
    p = plan_search(spec, store, 4, ivf=ivf, mesh=mesh)
    assert p.executor == "routed_bucket"


# ----------------------------------------------------------- budget spill
def test_plan_routing_spills_oversubscribed_budgets():
    """Satellite: under skewed demand the exchange splits into two rounds
    (b1, b2) whenever that moves fewer padded slots than one round at the
    global max's pow2 ceiling; balanced demand stays single-round."""
    from repro.dist.routing import _pow2_at_least, plan_routing

    bucket_shard = np.asarray([0, 1, 2, 3])
    bucket_parts = np.asarray([2, 2, 2, 2])

    # balanced: every query to every shard -> equal demand, one round
    sel = np.tile(np.arange(4), (16, 1))
    rp = plan_routing(sel, bucket_shard, bucket_parts, 4)
    assert rp.round_budgets[1] == 0
    assert rp.budget == rp.round_budgets[0]

    # high skew: all 33 queries select bucket 0 -> per-(src, dst) demand 9
    # (the batch splits over 4 source shards); the padded single round
    # would cost pow2(9) = 16 slots, the spilled plan (8, 4) = 12
    sel = np.zeros((33, 1), np.int64)
    rp = plan_routing(sel, bucket_shard, bucket_parts, 4)
    assert rp.round_budgets == (8, 4)
    assert rp.budget == 12 and rp.budget < _pow2_at_least(9)

    # bytes moved are pinned by the budget: the send buffer is
    # n * n * (b1 + b2) slots, not n * n * pow2(max demand)
    from repro.dist.routing import build_send_buffer

    Q = np.zeros((33, 8), np.float32)
    buf = build_send_buffer(Q, sel, rp)
    assert buf.shape == (4, 4, 12, 8 + 1)
    single_round_bytes = 4 * 4 * 16 * (8 + 1) * 4
    assert buf.nbytes == single_round_bytes * 3 // 4


def test_spilled_routing_matches_single_round_8dev():
    """A plan that spills into two all-to-all rounds returns exactly the
    same top-k as the unspilled executor (the rounds are slices of one
    buffer; concatenation reproduces the single-round layout)."""
    run_devices("""
    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.data.synthetic import make_dataset, ground_truth, recall_at_k
    from repro.dist.routing import plan_routing
    from repro.core.plan import _get_placement

    # 24 near-copies of one vector: every query routes to the same bucket,
    # so one owner shard absorbs the whole batch = maximally skewed demand
    X, _ = make_dataset(2048, 32, "clustered", n_queries=1, seed=3)
    rng = np.random.default_rng(11)
    Q = (X[0][None] + rng.normal(0, 0.01, (24, 32))).astype(np.float32)
    nlist = 16
    mesh = jax.make_mesh((8,), ("data",))
    eng = VectorSearchEngine.build(X, index="ivf", pruner="linear",
                                   capacity=64, nlist=nlist, mesh=mesh)
    gt_ids, _ = ground_truth(X, Q, k=5)

    pl = _get_placement(eng.store, 8, "bucket", ivf=eng.ivf)
    sel = eng.ivf.route_batch(jnp.asarray(Q), 1)
    rp = plan_routing(sel, pl.bucket_shard, pl.bucket_parts, 8)
    assert rp.round_budgets[1] > 0, rp.round_budgets  # the spill engaged

    res = eng.search(Q, SearchSpec(k=5, nprobe=1))
    assert res.plan.executor == "routed_bucket", res.plan
    host = VectorSearchEngine.build(X, index="ivf", pruner="linear",
                                    capacity=64, nlist=nlist)
    want = host.search(Q, SearchSpec(k=5, nprobe=1, executor="adaptive"))
    for qi in range(len(Q)):
        assert set(res.ids[qi].tolist()) == set(want.ids[qi].tolist()), qi
    print("OK")
    """)


# ------------------------------------------- routed executor (8 fake devices)
def test_routed_bucket_matches_single_host_ivf_8dev():
    run_devices("""
    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.data.synthetic import make_dataset, ground_truth, recall_at_k

    X, Q = make_dataset(2048, 32, "clustered", n_queries=6, seed=0)
    nlist = 16
    mesh = jax.make_mesh((8,), ("data",))
    eng = VectorSearchEngine.build(X, index="ivf", pruner="linear",
                                   capacity=64, nlist=nlist, mesh=mesh)
    host = VectorSearchEngine.build(X, index="ivf", pruner="linear",
                                    capacity=64, nlist=nlist)
    gt_ids, gt_d = ground_truth(X, Q, k=5)

    # full probe: exact vs brute-force ground truth
    res = eng.search(Q, SearchSpec(k=5, nprobe=nlist))
    assert res.plan.executor == "routed_bucket", res.plan
    assert recall_at_k(res.ids, gt_ids) == 1.0
    np.testing.assert_allclose(np.sort(res.dists, axis=1),
                               np.sort(gt_d, axis=1), rtol=1e-3, atol=1e-2)

    # partial probe: identical answer set to single-host IVF at the same
    # nprobe (both rank buckets with the same centroid arithmetic)
    for nprobe in (1, 4):
        r = eng.search(Q, SearchSpec(k=5, nprobe=nprobe))
        assert r.plan.executor == "routed_bucket", r.plan
        w = host.search(Q, SearchSpec(k=5, nprobe=nprobe, executor="adaptive"))
        for qi in range(len(Q)):
            assert set(r.ids[qi].tolist()) == set(w.ids[qi].tolist()), qi
        np.testing.assert_allclose(np.sort(r.dists, axis=1),
                                   np.sort(w.dists, axis=1),
                                   rtol=1e-4, atol=1e-4)

    # single query routes too, and broadcast opt-out falls back host-side
    r1 = eng.search(Q[0], SearchSpec(k=5, nprobe=nlist))
    assert r1.plan.executor == "routed_bucket"
    assert set(r1.ids.tolist()) == set(gt_ids[0].tolist())
    rb = eng.search(Q, SearchSpec(k=5, nprobe=8, routing="broadcast"))
    assert rb.plan.executor == "adaptive", rb.plan
    print("OK")
    """)


def test_routed_bucket_one_alltoall_one_allgather_8dev():
    """Acceptance gate: the routed executor issues exactly ONE all-to-all
    (query exchange) + ONE packed all-gather (hierarchical merge) per query
    batch, independent of B and nprobe — no replicated query broadcast."""
    run_devices("""
    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.core.plan import _get_placement
    from repro.data.synthetic import make_dataset
    from repro.dist.pdx_sharded import collective_counts
    from repro.dist.routing import (build_send_buffer, make_routed_fn,
                                    plan_routing)

    X, Q = make_dataset(2048, 32, "clustered", n_queries=16, seed=1)
    mesh = jax.make_mesh((8,), ("data",))
    eng = VectorSearchEngine.build(X, index="ivf", pruner="linear",
                                   capacity=64, nlist=16, mesh=mesh)
    pl = _get_placement(eng.store, 8, "bucket", ivf=eng.ivf)
    for B in (2, 4, 16):
        for nprobe in (1, 4, 16):
            sel = eng.ivf.route_batch(jnp.asarray(Q[:B]), nprobe)
            rp = plan_routing(sel, pl.bucket_shard, pl.bucket_parts, 8)
            fn = make_routed_fn(mesh, pl, rp, Q.shape[1], sel.shape[1], 5)
            buf = jnp.asarray(build_send_buffer(Q[:B], sel, rp))
            counts = collective_counts(fn, buf)
            assert counts == {"all_to_all": 1, "all_gather": 1}, \
                (B, nprobe, counts)
    print("OK")
    """)


def test_routed_bucket_quantized_routing_keeps_collective_gate_8dev():
    """Quantized centroid routing (route_dtype="int8") is host-side and
    pre-collective: the routed executor still issues exactly ONE all-to-all
    + ONE packed all-gather per batch, and full-probe answers stay exact."""
    run_devices("""
    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.data.synthetic import make_dataset, ground_truth, recall_at_k
    from repro.obs import metrics

    metrics.set_enabled(True)
    X, Q = make_dataset(2048, 32, "clustered", n_queries=6, seed=0)
    nlist = 16
    mesh = jax.make_mesh((8,), ("data",))
    eng = VectorSearchEngine.build(X, index="ivf", pruner="linear",
                                   capacity=64, nlist=nlist, mesh=mesh)
    gt_ids, _ = ground_truth(X, Q, k=5)
    reg = metrics.get_registry()

    res = eng.search(Q, SearchSpec(k=5, nprobe=nlist, route_dtype="int8"))
    assert res.plan.executor == "routed_bucket", res.plan
    assert recall_at_k(res.ids, gt_ids) == 1.0
    assert reg.get("repro_collectives_issued_total",
                   executor="routed_bucket", primitive="all_to_all") == 1.0
    assert reg.get("repro_collectives_issued_total",
                   executor="routed_bucket", primitive="all_gather") == 1.0
    # the quantized centroid scan's bytes are metered at the routing dtype
    assert reg.get("repro_device_bytes_total", executor="route",
                   component="scan", dtype="int8") > 0

    # partial probe: same answer set as f32 routing on separated clusters
    rq = eng.search(Q, SearchSpec(k=5, nprobe=4, route_dtype="int8"))
    rf = eng.search(Q, SearchSpec(k=5, nprobe=4))
    for qi in range(len(Q)):
        assert set(rq.ids[qi].tolist()) == set(rf.ids[qi].tolist()), qi
    print("OK")
    """)


def test_routed_bucket_parity_under_churn_8dev():
    """A churned MutablePDXStore answers through the routed path exactly
    like a store rebuilt from the survivors: write-head rows reachable,
    tombstones invisible, placement re-derived only after compact."""
    run_devices("""
    from repro.core.engine import SearchSpec, VectorSearchEngine
    from repro.data.synthetic import make_dataset

    X, Q = make_dataset(2048, 32, "clustered", n_queries=4, seed=2)
    nlist = 8
    mesh = jax.make_mesh((8,), ("data",))
    eng = VectorSearchEngine.build(X, index="ivf", pruner="linear",
                                   capacity=64, nlist=nlist, mesh=mesh)
    rows = {i: X[i] for i in range(len(X))}
    rng = np.random.default_rng(77)
    new = rng.standard_normal((50, 32)).astype(np.float32)
    ids = eng.insert(new)
    for r, i in enumerate(ids):
        rows[int(i)] = new[r]
    dels = rng.choice(2048, size=250, replace=False)
    eng.delete(dels)
    for i in dels:
        rows.pop(int(i), None)

    im = np.asarray(sorted(rows))
    Xs = np.stack([rows[i] for i in sorted(rows)])
    ref = VectorSearchEngine.build(Xs, index="ivf", pruner="linear",
                                   capacity=64, nlist=nlist)
    spec = SearchSpec(k=5, nprobe=nlist)  # full probe -> exact

    def check():
        got = eng.search(Q, spec)
        assert got.plan.executor == "routed_bucket", got.plan
        want = ref.search(Q, spec.replace(executor="batch-matmul"))
        np.testing.assert_array_equal(np.searchsorted(im, got.ids), want.ids)

    check()          # mid-churn: head merged exactly through the routed path
    v0 = eng.store.tiles_version
    eng.compact()
    assert eng.store.tiles_version > v0
    check()          # post-compact: placement rebuilt from the new tiles
    print("OK")
    """)
