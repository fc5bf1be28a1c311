"""Distributed PDXearch over a device mesh — the natural decompositions of
the dimension-major layout, all expressed against a ``Placement``
(``repro.dist.placement``) that owns the tile->shard mapping:

* ``search_block_sharded`` — partitions (PDX blocks) stripe over the ``data``
  axis (a ``block`` placement): each device runs the masked jitted PDXearch
  on its local tiles, then the per-shard top-k sets are all-gathered and
  merged.  Exact for exact pruners (wire cost: ``n_dev * k`` floats+ids per
  query).

* ``search_dim_sharded`` — *dimension slices* shard over the ``model`` axis
  while the tiles replicate (a ``replicated`` placement): each device
  accumulates partial distances over its contiguous row slab of every tile
  (a dimension shard of a PDX tile is contiguous — paper Fig. 1), one psum
  completes the distances, then a single top-k finishes.  Exact for all
  metrics whose distance decomposes over dimensions (l2 / l1 / ip).

* ``search_batch_block_sharded`` — the batched distributed search: the MXU
  batch scan (``core.pdxearch.search_batch_matmul``) runs on each device's
  partition shard, then the per-shard (B, k) candidate sets cross the mesh
  in ONE packed all-gather per query *batch* (dists and bitcast ids share
  the collective), amortizing the merge latency that the per-query path
  pays B times.  The planner (``repro.core.plan``) picks this automatically
  when a mesh and B > 1 are present.

Padding to mesh divisibility lives in ``Placement.block`` (the former
``pad_partitions_to_shards``, kept below as a thin compatibility wrapper):
executors never re-derive striping themselves.  The bucket-*routed* search
— queries traveling to the shards that own their IVF buckets instead of the
store being mirrored — lives in ``repro.dist.routing`` on top of a
``bucket`` placement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.pdxearch import (
    _pdxearch_jit_impl,
    _pdxearch_jit_stats_impl,
    make_boundaries,
    search_batch_matmul,
)
from ..core.distance import batched_distance_matmul, pdx_distance
from ..core.pruners import Pruner, make_plain_pruner
from ..core.topk import TopK, rerank_positions, topk_init, topk_merge
from ..kernels.ref import dequantize_ref
from .placement import Placement

__all__ = [
    "pad_partitions_to_shards",
    "search_block_sharded",
    "search_dim_sharded",
    "search_batch_block_sharded",
    "collective_counts",
]


def pad_partitions_to_shards(
    data: jax.Array, ids: jax.Array, n_shards: int
) -> tuple[jax.Array, jax.Array]:
    """Round the partition axis up to a multiple of ``n_shards`` with empty
    (all-``PAD_VALUE``, ids ``-1``) tiles.

    Compatibility wrapper: padding is owned by ``Placement.block`` now; this
    keeps the old array-in/array-out shape for direct callers.  Padding tiles
    rank nothing into a top-k (the pad sentinel is monotonically far away and
    ``topk_merge`` discards ids < 0), so the sharded result stays
    bit-identical to the unpadded scan.
    """
    pl = Placement.block(data, ids, n_shards)
    return pl.data, pl.ids


def _require(**named) -> None:
    """Explicit required-argument check: ``data``/``ids`` became optional so
    callers can pass a prebuilt ``placement=`` instead, but the query and k
    are always required — fail here with a clear TypeError rather than
    deep inside a trace."""
    for name, val in named.items():
        if val is None:
            raise TypeError(f"missing required argument: {name!r}")


def _block_placement(
    mesh, data, ids, axis: str, placement: Placement | None
) -> Placement:
    """Resolve the tile placement for a block-sharded executor: callers pass
    either raw (data, ids) arrays — striped + padded here — or a prebuilt
    (typically cached, see ``core.plan``) ``block``/``bucket`` placement."""
    if placement is None:
        return Placement.block(data, ids, mesh.shape[axis], axis=axis)
    if placement.n_shards != mesh.shape[axis]:
        raise ValueError(
            f"placement built for {placement.n_shards} shards, mesh axis "
            f"'{axis}' has {mesh.shape[axis]}"
        )
    return placement


def search_block_sharded(
    mesh,
    data: jax.Array | None = None,
    ids: jax.Array | None = None,
    q: jax.Array | None = None,
    k: int | None = None,
    *,
    metric: str = "l2",
    pruner: Pruner | None = None,
    schedule: str = "adaptive",
    delta_d: int = 32,
    axis: str = "data",
    placement: Placement | None = None,
    stats=None,
) -> TopK:
    """Partition-sharded PDXearch: the placement's (P', D, C) tiles and
    (P', C) ids shard their leading (partition) dim over ``axis``; the query
    is replicated.  Returns a replicated TopK.

    With a ``SearchStats`` in ``stats``, each shard runs the stats-carrying
    masked impl, the per-shard computed-values scalars psum across the
    mesh, and the totals land in ``stats`` — pruning power stays observable
    on the distributed path at the cost of one extra replicated scalar."""
    _require(q=q, k=k)
    pruner = pruner or make_plain_pruner()
    pl = _block_placement(mesh, data, ids, axis, placement)
    data, ids = pl.data, pl.ids
    bounds = make_boundaries(data.shape[1], schedule, delta_d)
    with_stats = stats is not None

    def local(d_sh, i_sh, q_rep):
        qt = pruner.transform_query(q_rep.astype(jnp.float32))
        perm = (
            pruner.dim_order(qt)
            if pruner.dim_order is not None
            else jnp.arange(d_sh.shape[1], dtype=jnp.int32)
        )
        if with_stats:
            res, computed = _pdxearch_jit_stats_impl(
                d_sh, i_sh, qt, perm, k, metric, bounds, pruner.keep_mask
            )
            computed = jax.lax.psum(computed, axis)
        else:
            res = _pdxearch_jit_impl(
                d_sh, i_sh, qt, perm, k, metric, bounds, pruner.keep_mask
            )
        all_d = jax.lax.all_gather(res.dists, axis, tiled=True)
        all_i = jax.lax.all_gather(res.ids, axis, tiled=True)
        merged = topk_merge(topk_init(k), all_d, all_i)
        return (merged, computed) if with_stats else merged

    out_specs = (
        (TopK(dists=P(), ids=P()), P()) if with_stats
        else TopK(dists=P(), ids=P())
    )
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=out_specs,
        check_vma=False,
    )
    out = fn(data, ids, q)
    if not with_stats:
        return out
    res, computed = out
    D = data.shape[1]
    total = float(jnp.sum(ids >= 0)) * D
    computed = float(computed)
    stats.values_total += total
    stats.values_computed += computed
    stats.values_avoided += total - computed
    stats.partitions_visited += data.shape[0]
    return res


def search_dim_sharded(
    mesh,
    data: jax.Array | None = None,
    ids: jax.Array | None = None,
    q: jax.Array | None = None,
    k: int | None = None,
    *,
    metric: str = "l2",
    axis: str = "model",
    placement: Placement | None = None,
) -> TopK:
    """Dimension-sharded exact search: tiles replicate (a ``replicated``
    placement) while each (P, D, C) tile's D axis shards over ``axis`` (the
    query shards alongside), partial distances are psum'd, and one top-k
    over all candidates finishes the query."""
    _require(q=q, k=k)
    n_shards = mesh.shape[axis]
    if placement is None:
        placement = Placement.replicated(data, ids, n_shards, axis=axis)
    data, ids = placement.data, placement.ids
    if data.shape[1] % n_shards:
        raise ValueError(
            f"D={data.shape[1]} not divisible over {n_shards} '{axis}' shards"
        )

    def local(d_sh, q_sh):
        part = jax.vmap(lambda t: pdx_distance(t, q_sh, metric))(d_sh)
        return jax.lax.psum(part, axis)  # (P, C) full distances, replicated

    dmat = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )(data, q.astype(jnp.float32))
    return topk_merge(topk_init(k), dmat.reshape(-1), ids.reshape(-1))


def search_batch_block_sharded(
    mesh,
    data: jax.Array | None = None,
    ids: jax.Array | None = None,
    Q: jax.Array | None = None,
    k: int | None = None,
    *,
    metric: str = "l2",
    axis: str = "data",
    placement: Placement | None = None,
    mirror=None,
    rerank_mult: int = 4,
) -> TopK:
    """Batched block-sharded exact search: the placement's tiles stripe
    partitions over ``axis``; the (B, D) query batch is replicated.  Each
    device scans its shard with the MXU batch kernel, then the per-shard
    (B, k) top-k sets are exchanged in a single all-gather for the whole
    batch — dists and ids are packed into one (B, 2k) buffer (int32 ids
    bitcast to float32, bit-exact) so exactly ONE collective crosses the
    mesh per batch, versus 2·B for B per-query searches.

    With a reduced-precision ``mirror`` (``core.layout.DeviceMirror``) each
    shard scans its *arranged mirror* slice instead (bf16/int8 bytes from
    HBM, dequantized in-register) and re-ranks its local top
    ``rerank_mult * k`` candidates against its f32 master slice before the
    collective — still exactly ONE all-gather, carrying exact f32
    candidate distances (a rounded wire would swap cross-shard near-ties
    at the global k-boundary).  Returns a replicated batched TopK with
    (B, k) leaves."""
    _require(Q=Q, k=k)
    pl = _block_placement(mesh, data, ids, axis, placement)
    data, ids = pl.data, pl.ids
    n_shards = pl.n_shards
    if Q.ndim != 2:
        raise ValueError(f"Q must be (B, D), got shape {Q.shape}")
    quantized = mirror is not None and mirror.dtype != "f32"
    if not quantized:

        def local(d_sh, i_sh, Q_rep):
            B = Q_rep.shape[0]
            res = search_batch_matmul(d_sh, i_sh, Q_rep, k, metric)  # (B, k)
            packed = jnp.concatenate(
                [res.dists,
                 jax.lax.bitcast_convert_type(res.ids, jnp.float32)],
                axis=1,
            )  # (B, 2k)
            allp = jax.lax.all_gather(packed, axis, axis=1, tiled=True)
            allp = allp.reshape(B, n_shards, 2 * k)
            all_d = allp[:, :, :k].reshape(B, n_shards * k)
            all_i = jax.lax.bitcast_convert_type(
                allp[:, :, k:], jnp.int32
            ).reshape(B, n_shards * k)
            merge = lambda dd, ii: topk_merge(topk_init(k), dd, ii)  # noqa: E731
            return jax.vmap(merge)(all_d, all_i)

        fn = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P()),
            out_specs=TopK(dists=P(), ids=P()),
            check_vma=False,
        )
        return fn(data, ids, Q.astype(jnp.float32))

    qtiles = pl.arranged_mirror(mirror)
    rk = min(max(rerank_mult * k, k), qtiles.shape[0] * qtiles.shape[2])
    scale, offset = mirror.scale, mirror.offset
    # packed int4 mirrors unpack in-body (two nibbles per byte along D) —
    # no int8 cap: the shard scan streams the 0.5-byte tiles directly
    m_packed, m_dim = mirror.packed, mirror.dim

    def local_q(d_sh, i_sh, qd_sh, Q_rep):
        B = Q_rep.shape[0]
        W, _, C = qd_sh.shape
        pos = jnp.arange(W * C, dtype=jnp.int32).reshape(W, C)
        pos = jnp.where(i_sh >= 0, pos, -1)

        def body(state, inp):
            tileq, tpos = inp
            t32 = dequantize_ref(
                tileq, scale, offset, packed=m_packed, dim=m_dim
            )
            dmat = batched_distance_matmul(t32, Q_rep, metric)  # (B, C)
            return jax.vmap(topk_merge, (0, 0, None))(state, dmat, tpos), None

        init = jax.vmap(lambda _: topk_init(rk))(jnp.arange(B))
        cand, _ = jax.lax.scan(body, init, (qd_sh, pos))
        # exact f32 re-rank against the local MASTER slice, pre-collective
        res = rerank_positions(d_sh, i_sh, Q_rep, cand, k, metric)
        merge = lambda d_, i_: topk_merge(topk_init(k), d_, i_)  # noqa: E731

        # candidate distances stay f32 on the wire: the hierarchical merge
        # decides the global k-boundary, and a rounded wire (bf16) both
        # swaps cross-shard near-ties there and rounds the distances the
        # caller gets back — exactness is the re-rank's whole contract
        packed = jnp.concatenate(
            [res.dists,
             jax.lax.bitcast_convert_type(res.ids, jnp.float32)],
            axis=1,
        )  # (B, 2k)
        allp = jax.lax.all_gather(packed, axis, axis=1, tiled=True)
        allp = allp.reshape(B, n_shards, 2 * k)
        all_d = allp[:, :, :k].reshape(B, n_shards * k)
        all_i = jax.lax.bitcast_convert_type(
            allp[:, :, k:], jnp.int32
        ).reshape(B, n_shards * k)
        return jax.vmap(merge)(all_d, all_i)

    fn = shard_map(
        local_q,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=TopK(dists=P(), ids=P()),
        check_vma=False,
    )
    return fn(data, ids, qtiles, Q.astype(jnp.float32))


# The jaxpr-walking collective meter moved to ``repro.obs.meters`` (it is
# telemetry, consumed by the registry's compile-time gauges as well as by
# tests); re-exported here because tests/benches import it from this module.
from ..obs.meters import _COLLECTIVES, collective_counts  # noqa: E402,F401
