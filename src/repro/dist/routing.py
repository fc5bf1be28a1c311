"""Bucket-routed distributed search: queries travel, tiles stay put.

The replicated-broadcast paths (``pdx_sharded``) send every query to every
shard and scan the whole striped store.  With a ``bucket`` placement
(``repro.dist.placement``) each shard *owns* a subset of the IVF buckets, so
a query only needs to visit the shards owning its top-``nprobe`` buckets —
the HARMONY-style routing the ROADMAP's "IVF bucket routing across hosts"
item calls for.  One batch flows through exactly two collectives:

1. **Route + exchange** — the router (``IVFIndex.route_batch``) ranks
   buckets per query; ``plan_routing`` turns that into a host-side exchange
   plan (which query goes to which owner shard, deduplicated).  Ragged
   per-shard query lists are padded to a static power-of-two *budget* (few
   distinct budgets => few retraces), queries and their selected bucket ids
   are packed into one buffer (int32 bucket ids bitcast to float32), and a
   single ``all_to_all`` delivers to each shard only the queries it owns
   buckets for.

2. **Masked local scan + hierarchical merge** — each shard scans *only its
   owned buckets* (its placement slice), masking each received query down to
   the buckets it actually selected, and keeps a shard-local top-k.  The
   per-shard (dists ‖ bitcast ids) candidate sets then cross the mesh in one
   packed ``all_gather`` (the PR 2 collective-packing trick), and the final
   per-query top-k merges only the candidate blocks from the shards that
   query was routed to.

Wire cost per batch: ``n² · budget · (D + nprobe)`` floats in the
all-to-all (budget shrinks with nprobe — fewer owner shards per query) plus
``n · n·budget · 2k`` floats in the all-gather, versus the broadcast path's
``n · B · D`` replicated queries + full-store scan on every shard.  Two
further byte levers ride on top:

* **Send-budget spill** — instead of padding every (src, dst) pair to the
  power-of-two ceiling of the *maximum* demand, ``plan_routing`` may split
  the exchange into two rounds ``(b1, b2)`` whenever ``b1 + b2`` moves
  fewer slots than the single padded round (high skew: one hot pair forces
  everyone to its ceiling).  Both rounds are slices of the same buffer and
  the split is static per plan, so the all-to-all count stays 1 or 2 with
  few distinct shapes.

* **Quantized shard scan** — with a reduced-precision device mirror
  (``spec.scan_dtype`` != "f32") each shard scans its *mirror* slice
  (bf16/int8, dequantized in-register by XLA) — 2x/4x fewer HBM bytes on
  the dominant term — and re-ranks its local top ``rerank_mult·k``
  candidates against its f32 master slice, so candidate distances are
  exact *before* they ever cross the mesh.  The wire deliberately stays
  f32 end to end: rounding queries in the all-to-all would make the
  re-rank exact relative to a perturbed query, and rounding candidate
  distances in the all-gather would swap cross-shard near-ties at the
  global k-boundary and hand rounded distances back to the caller — both
  were observed breaking id-parity with the f32 path on seed datasets,
  so the mirror's byte savings are taken where they are safe (the scan)
  and nowhere else.
"""
from __future__ import annotations

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.distance import batched_distance_matmul
from ..core.topk import TopK, rerank_positions, topk_init, topk_merge
from ..kernels.ref import dequantize_ref
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .placement import Placement

__all__ = [
    "RoutingPlan",
    "RoutedLaunch",
    "plan_routing",
    "build_send_buffer",
    "make_routed_fn",
    "prepare_routed",
    "launch_routed",
    "search_routed_bucket",
]

_INF = jnp.float32(jnp.inf)

# Sentinel bucket id for unused send slots: must match NO slot_bucket entry
# (pad slots carry -1, so -1 would wrongly select them).
_EMPTY_SEL = -2


def _pow2_at_least(x: int, lo: int = 1) -> int:
    c = lo
    while c < x:
        c *= 2
    return c


@dataclasses.dataclass(frozen=True)
class RoutingPlan:
    """Host-side exchange plan for one query batch.

    ``send_slot[s, t, j]`` — global query index source shard ``s`` puts in
    slot ``j`` of its message to shard ``t`` (-1 = unused pad slot).
    ``dest_shard``/``dest_slot`` (B, max_dest) — where each query's
    candidate blocks land after the all-gather (-1 pads).  ``src_of`` (B,)
    — the source shard each query originates on (contiguous split of the
    batch, mirroring how a (B, D) batch shards over the axis).
    """

    send_slot: np.ndarray
    dest_shard: np.ndarray
    dest_slot: np.ndarray
    src_of: np.ndarray
    budget: int       # static total slot count per (src, dst) = b1 + b2
    occupancy: int    # real (src, dst, slot) entries, for byte accounting
    round_budgets: tuple  # (b1, b2) all-to-all round widths; b2 == 0 means
                          # one round (balanced demand, no spill needed)


def plan_routing(
    sel: np.ndarray,
    bucket_shard: np.ndarray,
    bucket_parts: np.ndarray,
    n_shards: int,
) -> RoutingPlan:
    """Map each query's selected buckets onto owner shards.

    ``sel`` (B, nprobe) — ranked bucket ids per query.  Empty buckets own no
    partitions and are skipped (routing a query to their owner would move
    bytes for zero scan work).  Budgets are powers of two so shapes stay
    static across batches with similar routing pressure; when the max
    demand ``m`` fits in 3/4 of its pow2 ceiling, the exchange spills
    across TWO rounds ``(single/2, single/4)`` — 25% fewer padded slots
    than one round at the ceiling (e.g. demand 33 moves 48 slots per pair
    instead of 64).  Exactly two compiled shapes exist per demand octave
    (spilled or not) — a finer-grained spill would save more bytes at high
    skew but lets drifting demand mint a fresh executor shape per batch.
    """
    sel = np.asarray(sel)
    B = sel.shape[0]
    src_of = (np.arange(B, dtype=np.int64) * n_shards) // max(B, 1)
    # sel rows may carry -1 right-pads (two-level tree routing emits fewer
    # than nprobe buckets when the probed supers' children run short) —
    # drop them before the empty-bucket filter, which indexes bucket_parts
    dests = []
    for b in range(B):
        sb = sel[b][sel[b] >= 0]
        dests.append(np.unique(bucket_shard[sb[bucket_parts[sb] > 0]]))
    max_dest = min(sel.shape[1], n_shards)
    counts = np.zeros((n_shards, n_shards), np.int64)
    for b, ds in enumerate(dests):
        counts[src_of[b], ds] += 1
    m = max(int(counts.max(initial=0)), 1)
    single = _pow2_at_least(m)
    if single >= 4 and m <= 3 * single // 4:
        b1, b2 = single // 2, single // 4
    else:
        b1, b2 = single, 0
    budget = b1 + b2

    send_slot = np.full((n_shards, n_shards, budget), -1, np.int32)
    dest_shard = np.full((B, max_dest), -1, np.int32)
    dest_slot = np.full((B, max_dest), -1, np.int32)
    fill = np.zeros((n_shards, n_shards), np.int64)
    for b, ds in enumerate(dests):
        s = src_of[b]
        for j, t in enumerate(ds):
            slot = fill[s, t]
            fill[s, t] += 1
            send_slot[s, t, slot] = b
            dest_shard[b, j] = t
            dest_slot[b, j] = slot
    rp = RoutingPlan(
        send_slot=send_slot, dest_shard=dest_shard, dest_slot=dest_slot,
        src_of=src_of.astype(np.int32), budget=budget,
        occupancy=int(fill.sum()), round_budgets=(b1, b2),
    )
    if _metrics.enabled():
        # the histogram's log2 buckets ARE the demand octaves: each compiled
        # budget shape serves one bucket, so the bucket counts show exactly
        # how batches spread over executor shapes
        _metrics.observe("repro_routing_demand", float(m))
        _metrics.counter(
            "repro_routing_spill_rounds_total", rounds=2 if b2 else 1
        )
        _metrics.gauge(
            "repro_routing_slot_occupancy",
            rp.occupancy / max(n_shards * n_shards * budget, 1),
        )
    return rp


def build_send_buffer(
    Q: np.ndarray, sel: np.ndarray, rp: RoutingPlan
) -> np.ndarray:
    """Pack (queries ‖ bitcast selected-bucket ids) into the single
    (n, n, budget, D + nprobe) float32 all-to-all payload, covering both
    exchange rounds (slots ``[:b1]`` travel in round 1, the spill in
    round 2)."""
    Q = np.asarray(Q, np.float32)
    sel = np.asarray(sel, np.int32)
    n = rp.send_slot.shape[0]
    D, nprobe = Q.shape[1], sel.shape[1]
    send_q = np.zeros((n, n, rp.budget, D), np.float32)
    send_sel = np.full((n, n, rp.budget, nprobe), _EMPTY_SEL, np.int32)
    occ = rp.send_slot >= 0
    send_q[occ] = Q[rp.send_slot[occ]]
    send_sel[occ] = sel[rp.send_slot[occ]]
    return np.concatenate([send_q, send_sel.view(np.float32)], axis=-1)


# jitted routed executors keyed on their static configuration; every array
# (send buffer, tiles, routing indices) is a traced ARGUMENT, so one cache
# entry serves every batch / placement with the same shapes — repeated
# searches hit the jit executable instead of re-tracing the shard_map.
_ROUTED_CACHE: "collections.OrderedDict[tuple, object]" = (
    collections.OrderedDict()
)
_ROUTED_CACHE_MAX = 8


def _exchange(buf0, axis: str, rounds: tuple):
    """The query exchange: one all_to_all per non-empty round, slicing the
    shared (n, budget, W) buffer at ``b1``.  Concatenating the received
    rounds reproduces exactly the single-round layout (all_to_all permutes
    only the shard axis), so everything downstream is round-agnostic."""
    b1, b2 = rounds
    if not b2:
        return jax.lax.all_to_all(buf0, axis, 0, 0, tiled=True)
    r1 = jax.lax.all_to_all(buf0[:, :b1], axis, 0, 0, tiled=True)
    r2 = jax.lax.all_to_all(buf0[:, b1:], axis, 0, 0, tiled=True)
    return jnp.concatenate([r1, r2], axis=1)


def _routed_exec(mesh, axis: str, D: int, nprobe: int, k: int, metric: str,
                 rounds: tuple, quantized: bool, rk: int,
                 packed: bool = False, dim: int | None = None):
    key = (mesh, axis, D, nprobe, k, metric, rounds, quantized, rk,
           packed, dim)
    if key in _ROUTED_CACHE:
        _ROUTED_CACHE.move_to_end(key)
        _metrics.counter(
            "repro_cache_events_total", cache="routed", event="hit"
        )
        return _ROUTED_CACHE[key]
    _metrics.counter("repro_cache_events_total", cache="routed", event="miss")

    def local(buf, d_sh, i_sh, pb_sh, dest_shard, dest_slot, src_of,
              qd_sh, scale, offset):
        # buf local: (1, n, budget, D + nprobe) — my messages, one per dest.
        n, budget = buf.shape[1], buf.shape[2]
        B = dest_shard.shape[0]
        recv = _exchange(buf[0], axis, rounds)
        Bl = n * budget  # received queries, flat index = src * budget + slot
        Qr = recv[..., :D].reshape(Bl, D)
        selr = jax.lax.bitcast_convert_type(
            recv[..., D:], jnp.int32
        ).reshape(Bl, nprobe)
        # query q may scan local partition p iff p's bucket is one q selected
        allowed = (selr[:, :, None] == pb_sh[None, None, :]).any(axis=1)

        if not quantized:
            def body(state, inp):
                tile, tids, allow_p = inp  # (D, C), (C,), (Bl,)
                dmat = batched_distance_matmul(tile, Qr, metric)  # (Bl, C)
                dmat = jnp.where(allow_p[:, None], dmat, _INF)
                return (
                    jax.vmap(topk_merge, (0, 0, None))(state, dmat, tids),
                    None,
                )

            init = jax.vmap(lambda _: topk_init(k))(jnp.arange(Bl))
            res, _ = jax.lax.scan(body, init, (d_sh, i_sh, allowed.T))
        else:
            # mirror scan at reduced precision -> local top-rk positions,
            # then exact f32 re-rank against the MASTER slice — candidate
            # distances are exact before they ever cross the mesh
            W, _, C = qd_sh.shape
            pos = jnp.arange(W * C, dtype=jnp.int32).reshape(W, C)
            pos = jnp.where(i_sh >= 0, pos, -1)

            def body(state, inp):
                tileq, tpos, allow_p = inp
                # packed int4 unpacks in-body (two nibbles/byte along D);
                # int8/bf16 dequantize via the same reference op
                t32 = dequantize_ref(
                    tileq, scale, offset, packed=packed, dim=dim
                )
                dmat = batched_distance_matmul(t32, Qr, metric)
                dmat = jnp.where(allow_p[:, None], dmat, _INF)
                return (
                    jax.vmap(topk_merge, (0, 0, None))(state, dmat, tpos),
                    None,
                )

            init = jax.vmap(lambda _: topk_init(rk))(jnp.arange(Bl))
            cand, _ = jax.lax.scan(body, init, (qd_sh, pos, allowed.T))
            # exact f32 re-rank against the local MASTER slice
            res = rerank_positions(d_sh, i_sh, Qr, cand, k, metric)

        # candidate distances stay f32 on the wire even for quantized
        # scans: the hierarchical merge decides the global k-boundary, and
        # a rounded wire would both swap cross-shard near-ties there and
        # round the distances the caller gets back — exactness is the
        # on-shard re-rank's whole contract
        wire = jnp.concatenate(
            [res.dists,
             jax.lax.bitcast_convert_type(res.ids, jnp.float32)],
            axis=1,
        )  # (Bl, 2k)
        allp = jax.lax.all_gather(wire, axis)  # (n_dst, Bl, 2k)

        # hierarchical merge (replicated): per query, only the candidate
        # blocks from the shards it was routed to.
        pad = dest_shard < 0                                     # (B, max_dest)
        t = jnp.maximum(dest_shard, 0)
        row = src_of[:, None] * budget + jnp.maximum(dest_slot, 0)
        cand = allp[t, row]                                      # (B, md, 2k)
        cd = cand[..., :k]
        ci = jax.lax.bitcast_convert_type(cand[..., k:], jnp.int32)
        cd = jnp.where(pad[:, :, None], _INF, cd).reshape(B, -1)
        ci = jnp.where(pad[:, :, None], -1, ci).reshape(B, -1)
        merge = lambda dd, ii: topk_merge(topk_init(k), dd, ii)  # noqa: E731
        return jax.vmap(merge)(cd, ci)

    fn = jax.jit(shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(), P(), P(),
                  P(axis), P(), P()),
        out_specs=TopK(dists=P(), ids=P()),
        check_vma=False,
    ))
    _ROUTED_CACHE[key] = fn
    while len(_ROUTED_CACHE) > _ROUTED_CACHE_MAX:
        _ROUTED_CACHE.popitem(last=False)
    return fn


def make_routed_fn(mesh, placement: Placement, rp: RoutingPlan, D: int,
                   nprobe: int, k: int, metric: str = "l2",
                   mirror=None, rerank_mult: int = 4):
    """Bind the cached jitted routed executor to one (placement, routing
    plan): send_buffer -> (B, k) TopK.

    One all_to_all per exchange round (two only when the plan spilled a
    skewed budget) plus ONE packed all-gather (candidate merge) per call —
    independent of B and nprobe; ``collective_counts`` gates this in tests.
    With ``mirror`` (a ``core.layout.DeviceMirror``) each shard scans its
    arranged mirror slice and re-ranks locally against its f32 masters.
    """
    quantized = mirror is not None and mirror.dtype != "f32"
    rk = min(max(rerank_mult * k, k), placement.num_slots *
             placement.data.shape[2]) if quantized else k
    fn = _routed_exec(
        mesh, placement.axis, D, nprobe, k, metric, rp.round_budgets,
        quantized, rk,
        packed=mirror.packed if quantized else False,
        dim=mirror.dim if quantized else None,
    )
    slot_bucket = jnp.asarray(placement.slot_bucket, jnp.int32)
    dest_shard = jnp.asarray(rp.dest_shard)
    dest_slot = jnp.asarray(rp.dest_slot)
    src_of = jnp.asarray(rp.src_of)
    if quantized:
        qtiles = placement.arranged_mirror(mirror)
        scale, offset = mirror.scale, mirror.offset
    else:  # unused by the f32 body; tiny placeholders keep the arity fixed
        D_ = placement.data.shape[1]
        qtiles = placement.data
        scale = jnp.ones((D_,), jnp.float32)
        offset = jnp.zeros((D_,), jnp.float32)
    return lambda buf: fn(
        buf, placement.data, placement.ids, slot_bucket,
        dest_shard, dest_slot, src_of, qtiles, scale, offset,
    )


@dataclasses.dataclass
class RoutedLaunch:
    """Host-side product of ``prepare_routed``: everything needed to fire
    the device half of one routed batch.  Splitting lets a serving loop
    overlap batch N+1's host work (``plan_routing`` + send-buffer packing +
    executor-cache lookup) with batch N's device collectives — the
    double-buffering in ``repro.serve.vector``."""

    fn: object           # bound routed executor: send buffer -> (B, k) TopK
    buf: jax.Array       # packed send buffer, already on device
    buf_shape: tuple     # host buffer shape (compile-collectives cache key)
    rp: RoutingPlan
    n_shards: int
    D: int
    C: int
    num_slots: int
    nprobe: int
    k: int
    metric: str
    quantized: bool
    mirror_dtype: str
    mirror_bpv: float   # 0.5 for packed int4 — bytes, not whole bytes
    rerank_mult: int


def prepare_routed(
    mesh,
    placement: Placement,
    Q: jax.Array,
    sel: np.ndarray,
    k: int,
    *,
    metric: str = "l2",
    mirror=None,
    rerank_mult: int = 4,
) -> RoutedLaunch:
    """The HOST half of a routed batch search: exchange planning, send-
    buffer packing, executor-cache binding, and the (async) device upload.
    No collective is issued here — ``launch_routed`` fires the exchange.

    ``Q`` (B, D) — pruner-transformed queries; ``sel`` (B, nprobe) — ranked
    bucket ids per query (``IVFIndex.route_batch``)."""
    if placement.kind != "bucket":
        raise ValueError(
            f"routed search needs a 'bucket' placement, got {placement.kind!r}"
        )
    Qnp = np.asarray(Q, np.float32)
    selnp = np.asarray(sel, np.int32)
    quantized = mirror is not None and mirror.dtype != "f32"
    with _trace.span("route", nprobe=selnp.shape[1],
                     n_shards=placement.n_shards):
        rp = plan_routing(
            selnp, placement.bucket_shard, placement.bucket_parts,
            placement.n_shards,
        )
        buf = build_send_buffer(Qnp, selnp, rp)
        fn = make_routed_fn(
            mesh, placement, rp, Qnp.shape[1], selnp.shape[1], k, metric,
            mirror=mirror if quantized else None, rerank_mult=rerank_mult,
        )
    return RoutedLaunch(
        fn=fn, buf=jnp.asarray(buf), buf_shape=buf.shape, rp=rp,
        n_shards=placement.n_shards, D=Qnp.shape[1],
        C=placement.data.shape[2], num_slots=placement.num_slots,
        nprobe=selnp.shape[1], k=k, metric=metric, quantized=quantized,
        mirror_dtype=mirror.dtype if quantized else "f32",
        mirror_bpv=mirror.bytes_per_value if quantized else 4,
        rerank_mult=rerank_mult,
    )


def launch_routed(launch: RoutedLaunch) -> TopK:
    """The DEVICE half: issue the all-to-all exchange + masked shard scan +
    packed all-gather merge for a prepared batch; returns the replicated
    (B, k) TopK.  Also the metrics point — bytes/collectives are recorded
    when the exchange actually fires, not when it is planned."""
    if _metrics.enabled():
        from ..obs import meters as _meters

        rounds = 2 if launch.rp.round_budgets[1] else 1
        _meters.count_issued("routed_bucket", all_to_all=rounds, all_gather=1)
        comps = _meters.routed_batch_bytes(
            launch.rp, n_shards=launch.n_shards, D=launch.D,
            C=launch.C, num_slots=launch.num_slots,
            nprobe=launch.nprobe, k=launch.k,
            bytes_per_value=launch.mirror_bpv,
            rerank_mult=launch.rerank_mult, quantized=launch.quantized,
        )
        _meters.record_device_bytes(
            "routed_bucket", launch.mirror_dtype, comps
        )
        # compile-time gauge: count the collectives in the traced jaxpr
        # once per executor shape; parity with the issued counters above is
        # a CI invariant (benchmarks/bench_obs.py)
        _meters.record_compile_collectives(
            "routed_bucket",
            (launch.buf_shape, launch.rp.round_budgets, launch.quantized,
             launch.k, launch.metric, launch.n_shards),
            launch.fn, launch.buf,
        )
    if launch.quantized:
        # the exact f32 re-rank runs fused on-shard, pre-collective — a
        # zero-width annotation span marks it in the trace
        with _trace.span("rerank", fused="on-shard",
                         rk=launch.rerank_mult * launch.k):
            pass
    return _trace.fence(launch.fn(launch.buf))


def search_routed_bucket(
    mesh,
    placement: Placement,
    Q: jax.Array,
    sel: np.ndarray,
    k: int,
    *,
    metric: str = "l2",
    mirror=None,
    rerank_mult: int = 4,
) -> TopK:
    """Routed batch search over a ``bucket`` placement — the synchronous
    composition ``launch_routed(prepare_routed(...))``.

    Exact over the union of each query's selected buckets: the masked scan
    computes full distances (never prunes), so with nprobe == nlist this
    equals the exact full scan.  With a reduced-precision ``mirror`` the
    shard scan streams mirror-width bytes; the on-shard f32 re-rank keeps
    the merged candidates exact, and the wire stays f32 (see the module
    docstring for why rounding it breaks the k-boundary).  Returns a
    replicated (B, k) TopK.
    """
    return launch_routed(prepare_routed(
        mesh, placement, Q, sel, k, metric=metric, mirror=mirror,
        rerank_mult=rerank_mult,
    ))
