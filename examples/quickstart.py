"""Quickstart: build a PDX store, search it through the spec/plan API.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.engine import SearchSpec, SearchStats, VectorSearchEngine
from repro.data.synthetic import ground_truth, make_dataset, recall_at_k
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # 50K skewed vectors, 128-dim (SIFT-like per the paper's taxonomy)
    X, Q = make_dataset(50_000, 128, "skewed", n_queries=8, seed=0)
    gt_ids, gt_d = ground_truth(X, Q, k=10)
    spec = SearchSpec(k=10)

    # --- exact search with PDX-BOND (no preprocessing, no recall loss) ----
    bond = VectorSearchEngine.build(X, pruner="bond", capacity=4096)
    stats = SearchStats()
    ids, dists = bond.search(Q[0], spec, stats=stats)
    print(f"PDX-BOND exact: recall={recall_at_k(ids[None], gt_ids[:1]):.2f} "
          f"pruning_power={stats.pruning_power:.1%}")

    # --- approximate IVF search with ADSampling ---------------------------
    # Same entry point: the planner routes through the IVF index.
    ads = VectorSearchEngine.build(
        X, index="ivf", pruner="adsampling", capacity=1024
    )
    ivf_spec = spec.replace(nprobe=16)
    recs = []
    for qi, q in enumerate(Q):
        ids, _ = ads.search(q, ivf_spec)
        recs.append(recall_at_k(ids[None], gt_ids[qi : qi + 1]))
    print(f"PDX-ADSampling IVF (nprobe=16): recall={np.mean(recs):.2f}")

    # --- batched queries: same entry point, planner picks the MXU scan ----
    res = bond.search(Q, spec)
    print(f"batched ({res.plan.executor}): "
          f"recall={recall_at_k(res.ids, gt_ids):.2f}")
    print(f"  plan: {res.plan.reason}")

    # --- reduced-precision device scan: 2x fewer bytes, exact results -----
    # The store keeps f32 masters; scan_dtype="bf16" streams a bfloat16
    # device mirror through the fused executors and re-ranks the top
    # rerank_mult*k candidates against the masters, so the returned
    # distances are still exact f32.  (The fused batch executor scans the
    # whole store exactly — hence the higher recall than nprobe=16 above.)
    from repro.core.layout import device_mirror

    ads32 = ads.search(Q, spec.replace(nprobe=16))
    res16 = ads.search(Q, spec.replace(nprobe=16, scan_dtype="bf16"))
    m32 = device_mirror(ads.store, "f32")
    m16 = device_mirror(ads.store, "bf16")
    m8 = device_mirror(ads.store, "int8")
    bytes32 = m32.data.size * m32.bytes_per_value
    bytes16 = m16.data.size * m16.bytes_per_value
    bytes8 = m8.data.size * m8.bytes_per_value
    print(f"bf16 mirror ({res16.plan.executor}): "
          f"recall={recall_at_k(res16.ids, gt_ids):.2f} "
          f"(f32 path: {recall_at_k(ads32.ids, gt_ids):.2f})")
    print(f"  scan bytes/query: {bytes32/1e6:.1f} MB (f32) -> "
          f"{bytes16/1e6:.1f} MB (bf16, {bytes32/bytes16:.1f}x fewer) -> "
          f"{bytes8/1e6:.1f} MB (int8, {bytes32/bytes8:.1f}x fewer)")

    # --- cascaded multi-resolution scan: proj mirror -> int4 -> exact f32 -
    # cascade=(...) declares a stage ladder: a rank-32 PCA projection
    # mirror kills most candidates at 32 of 256 dims, the packed int4
    # mirror (0.5 B/dim) re-checks survivors at full dimensionality with a
    # quantization-inflated (still exact-safe) threshold, and an f32
    # re-rank over every remaining survivor keeps results exact.  Later
    # stages prefetch only the partitions with surviving lanes — at
    # (partition, d-tile) granularity, so a partition stops streaming at
    # the first d-tile where its last lane dies.  The cascade pays off
    # when IVF routing seeds a tight threshold (clustered data), so build
    # that shape here — on it the realized bytes/query land ~5.4x below
    # the one-level int8 fused scan at recall@10 == 1.0 (gated in
    # BENCH_cascade.json).
    #
    # Batches take a different executor: at B > 1 the planner dispatches
    # to cascade-batch, which runs every stage ONCE over the whole batch
    # on the MXU (shared (B, lanes) survivor bitmap, pow2-compacted union
    # gather) instead of looping queries on the host.  Ids and distances
    # are bitwise-equal to the per-query loop; at B=64 it sustains ~3.2x
    # the queries/s of the host loop (BENCH_cascade.json "batched").
    from repro.obs import metrics

    Xc, Qc = make_dataset(16_384, 256, "clustered", n_queries=8, seed=1)
    gtc, _ = ground_truth(Xc, Qc, k=10)
    casc_eng = VectorSearchEngine.build(
        Xc, index="ivf", pruner="adsampling", capacity=256, nlist=64
    )
    casc_spec = spec.replace(cascade=("proj32:int8", "int4", "f32"),
                             kernel="jnp")
    metrics.set_enabled(True)
    try:
        res_c = casc_eng.search(Qc, casc_spec)       # batch -> cascade-batch
        res_1 = casc_eng.search(Qc[0], casc_spec)    # single -> cascade-scan
        reg = metrics.get_registry()
        casc_bytes = reg.sum("repro_device_bytes_total",
                             executor=res_c.plan.executor) / len(Qc)
        surv = [reg.get("repro_cascade_stage_survivors", stage=str(si),
                        stage_name=st) / (len(Qc) + 1)
                for si, st in enumerate(casc_spec.cascade[:-1])]
    finally:
        metrics.set_enabled(False)
    int8_full = float(np.prod(casc_eng.store.data.shape))  # 1 B/value
    print(f"cascade {'->'.join(casc_spec.cascade)} "
          f"(batch: {res_c.plan.executor}, single: {res_1.plan.executor}): "
          f"recall={recall_at_k(res_c.ids, gtc):.2f}")
    print(f"  realized bytes/query: {casc_bytes/1e6:.2f} MB "
          f"(int8 mirror full scan: {int8_full/1e6:.2f} MB, "
          f"{int8_full/casc_bytes:.1f}x fewer); mean survivors/stage: "
          + ", ".join(f"{s:.0f}" for s in surv))

    # --- runtime telemetry: metrics registry + per-query trace spans ------
    # Off by default (zero cost); flip it on (or export REPRO_OBS=1) and
    # every search populates a process-wide registry and a per-call
    # QueryTrace of plan -> route -> scan -> rerank -> merge spans.
    from repro.obs import metrics

    metrics.set_enabled(True)
    try:
        res = ads.search(Q, spec.replace(nprobe=16, scan_dtype="bf16"))
        qt = res.trace
        spans = ", ".join(
            f"{s.name}={s.duration_s*1e3:.1f}ms" for s in qt.spans
        )
        print(f"trace #{qt.trace_id} ({qt.attrs['executor']}): {spans}")
        snap = ads.metrics()               # deterministic dict snapshot
        batches = snap["counters"]["repro_search_batches_total"]
        print(f"registry: search batches by executor = {batches}")
        ads.dump_trace("/tmp/quickstart_trace.json")  # open in ui.perfetto.dev
        print("Perfetto trace -> /tmp/quickstart_trace.json; "
              "Prometheus text via metrics.get_registry().prometheus_text()")
    finally:
        metrics.set_enabled(False)

    # --- tiered serving: stores bigger than HBM -------------------------
    # hbm_slots caps the device-resident quantized mirror at a fixed slot
    # pool of tile-aligned bucket extents; host-RAM f32 masters stay
    # authoritative.  Routing decides which bucket extents each batch
    # needs, prefetches them into the pool (LRU evicting cold buckets),
    # and the exact re-rank runs against the host masters — so recall
    # matches the fully-resident path while the device holds only the
    # routed working set.  Fine-grained buckets (nlist up, capacity down)
    # keep each extent small, so a cache 4x smaller than the mirror still
    # fits any query's routed demand; on a skewed (hot-cluster) workload
    # the warm hit rate stays high.  A two-level centroid tree (tree=True)
    # keeps the routing itself sub-linear in nlist.
    #
    # Cold misses upload asynchronously: BucketCache.ensure is split into
    # issue (evict + start the H2D copies, non-blocking) and wait (install
    # + block once per batch), so chunk N+1's uploads overlap chunk N's
    # scan through the depth-1 pipeline.  On multi-core hosts / device
    # backends a staging worker thread quantizes extents host-side so the
    # wire carries 1-2 bytes/dim instead of f32; on a single-core CPU
    # backend staging degrades to the fused device quantize (same total
    # work, one block per batch instead of one per miss).  Set
    # bc.sync_uploads = True to A/B against the fully synchronous path;
    # bench_tiered.py gates the cold-miss p50 ratio (<= 0.7 with real
    # parallelism, cost parity on one core).  The
    # repro_cache_upload_wait_us histogram and ..._overlap_ratio gauge
    # below show how much of each upload hid behind compute.
    tiered_eng = VectorSearchEngine.build(
        Xc, index="ivf", nlist=256, capacity=64, pruner="linear",
        tree=True,
    )
    Pt = tiered_eng.store.data.shape[0]
    tiered_spec = spec.replace(nprobe=16, scan_dtype="int8",
                               hbm_slots=Pt // 4)
    hot = Qc[:4]                 # a hot working set, like serving traffic
    gt_hot = gtc[:4]
    full = tiered_eng.search(hot, tiered_spec.replace(hbm_slots=None))
    metrics.set_enabled(True)
    try:
        reg = metrics.get_registry()
        res_t = tiered_eng.search(hot, tiered_spec)  # cold: prefetch fills
        h0 = reg.sum("repro_tiered_cache_events_total", event="hit")
        m0 = reg.sum("repro_tiered_cache_events_total", event="miss")
        res_t = tiered_eng.search(hot, tiered_spec)  # warm: set resident
        hits = reg.sum("repro_tiered_cache_events_total", event="hit") - h0
        miss = reg.sum("repro_tiered_cache_events_total", event="miss") - m0
        snap = reg.snapshot()
        up = snap["histograms"].get("repro_cache_upload_wait_us", {}).get("")
        overlap = snap["gauges"].get(
            "repro_cache_upload_overlap_ratio", {}).get("")
    finally:
        metrics.set_enabled(False)
    print(f"tiered ({res_t.plan.executor}, {tiered_spec.hbm_slots} of {Pt} "
          f"tiles resident): recall={recall_at_k(res_t.ids, gt_hot):.2f} "
          f"(fully-resident: {recall_at_k(full.ids, gt_hot):.2f}), "
          f"warm cache hit rate={hits / max(hits + miss, 1):.2f}, "
          f"routing cost {tiered_eng.ivf.routing_cost()} of "
          f"{tiered_eng.ivf.nlist} centroids/query")
    if up and up["count"]:
        print(f"  async uploads: {up['count']:.0f} waits, mean host block "
              f"{up['sum']/up['count']/1e3:.2f}ms, last overlap ratio "
              f"{overlap:.2f} (1.0 = copy fully hidden behind compute)")

    # --- online serving: continuous batching over the same engine ---------
    # VectorServer coalesces async submissions into pow2 compiled-shape
    # batches (warmup() pre-compiles every bucket, so a drifting arrival
    # rate mints no new executables), applies deadline/backpressure at the
    # admission queue, and runs store maintenance (repack) on a background
    # thread behind a version fence.  submit() returns a Future; queue
    # wait shows up as a "queue" span on the query's trace.
    from repro.serve import VectorServer

    metrics.set_enabled(True)
    try:
        serve_spec = spec.replace(executor="batch-matmul")
        with VectorServer(bond, spec=serve_spec, max_batch=16,
                          maintenance_interval_s=0.5) as server:
            server.warmup()
            futures = [server.submit(q) for q in Q]       # async fan-in
            ids0, _ = futures[0].result()
            new_ids = server.insert(X[:2] + 0.01).result()  # live mutation
            print(f"served {len(futures)} async queries "
                  f"(top-1 of q0 = {ids0[0]}), inserted ids {new_ids.tolist()}, "
                  f"compiles after warmup = {server.jit_compiles_since_warmup()}")
            snap = server.metrics()
            hist = snap["histograms"]["repro_serve_queue_wait_seconds"][""]
            print(f"queue wait: {hist['count']} queries, "
                  f"mean {hist['sum']/hist['count']*1e3:.2f}ms; depth gauge = "
                  f"{snap['gauges']['repro_serve_queue_depth']['']:.0f}")
            qt = bond.dump_trace()["traceEvents"]
            print(f"trace ring now holds served-query spans "
                  f"({sum(1 for e in qt if e['name'] == 'queue')} queue spans)")
    finally:
        metrics.set_enabled(False)


if __name__ == "__main__":
    main()
