"""IVF index over PDX-resident buckets (paper Figure 2: buckets ≡ blocks).

Centroids themselves are stored in PDX layout so the find-nearest-buckets
phase uses the same dimension-major kernels (paper Table 7 note: "centroids
are also stored with PDX").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.distance import nary_distance, pdx_distance
from ..core.layout import (
    PDXStore,
    build_bucketed_store,
    build_flat_store,
    device_mirror,
)
from ..core.pdxearch import SearchStats, pdxearch
from ..core.pruners import Pruner
from ..core.topk import TopK
from ..kernels.ref import dequantize_ref
from ..obs import metrics as _metrics
from .kmeans import build_centroid_tree, kmeans

__all__ = ["IVFIndex", "build_ivf"]

#: ``build_ivf(tree="auto")`` switches the flat centroid scan to the
#: two-level tree at this nlist — below it the flat single-dispatch scan is
#: both cheaper and tie-stable, above it sub-linear routing wins.
TREE_AUTO_NLIST = 4096


def _rank_centroids_impl(cdata, q, nlist: int, metric: str):
    """One dimension-major scan of ALL centroid tiles -> ascending bucket
    order.  vmap over the (Pc, D, C) tile stack replaces the old
    per-partition Python loop, and the argsort happens on device so the
    whole ranking is a single dispatch with one host sync at the caller."""
    d = jax.vmap(lambda tile: pdx_distance(tile, q, metric))(cdata)
    return jnp.argsort(d.reshape(-1)[:nlist])


_rank_centroids = jax.jit(
    _rank_centroids_impl, static_argnames=("metric", "nlist")
)


@functools.partial(jax.jit, static_argnames=("metric", "nlist"))
def _rank_centroids_batch(
    cdata: jax.Array, Q: jax.Array, nlist: int, metric: str
):
    """``_rank_centroids`` vmapped over a (B, D) query batch -> (B, nlist)
    ascending bucket orders in one dispatch.  Sharing the single-query body
    keeps batched and per-query routing agreeing on bucket ranking by
    construction."""
    return jax.vmap(
        lambda q: _rank_centroids_impl(cdata, q, nlist, metric)
    )(Q)


@functools.partial(
    jax.jit, static_argnames=("metric", "nlist", "packed", "dim")
)
def _rank_centroids_batch_mirror(
    cdata, Q, nlist: int, metric: str, scale, offset,
    packed: bool, dim: int | None,
):
    """Quantized-mirror bucket ranking: the int8/int4 centroid tiles
    dequantize in-register (XLA fuses the affine into the scan) and the
    exact ``_rank_centroids_impl`` arithmetic runs on the result, so
    single-query and batched routing still agree by construction.  Bucket
    *order* near centroid-distance ties may differ from f32 routing — the
    reason ``SearchSpec.route_dtype`` defaults to "f32"."""
    T32 = dequantize_ref(cdata, scale, offset, dim_axis=1,
                         packed=packed, dim=dim)
    return jax.vmap(
        lambda q: _rank_centroids_impl(T32, q, nlist, metric)
    )(Q)


@functools.partial(
    jax.jit, static_argnames=("metric", "nlist", "nprobe_super")
)
def _rank_centroids_tree(
    centroids: jax.Array,      # (K, D) horizontal f32
    supers: jax.Array,         # (SK, D) super-centroids
    children: jax.Array,       # (SK, M) int32 child lists, -1 right-pad
    Q: jax.Array,              # (B, D)
    nlist: int,
    metric: str,
    nprobe_super: int,
):
    """Two-level bucket ranking: rank SK super-centroids, keep the best
    ``nprobe_super``, then rank only *their* children.  Visits
    ``SK + nprobe_super * M`` centroids per query instead of nlist.

    Returns (B, nlist) int32 bucket orders, best first, right-padded with
    -1: unlike the flat argsort, a query only ranks the candidate set under
    its selected super-centroids, so consumers (``route``,
    ``partition_order``, ``plan_routing``) skip ids < 0."""
    SK, M = children.shape

    def one(q):
        ds = nary_distance(supers, q, metric)                 # (SK,)
        _, top = jax.lax.top_k(-ds, nprobe_super)             # best supers
        cand = children[top].reshape(-1)                      # (nps*M,)
        valid = cand >= 0
        dc = nary_distance(centroids[jnp.where(valid, cand, 0)], q, metric)
        dc = jnp.where(valid, dc, jnp.inf)                    # pads last
        order = jnp.argsort(dc)
        ranked = jnp.where(
            jnp.isfinite(dc[order]), cand[order], -1
        ).astype(jnp.int32)
        out = jnp.full((nlist,), -1, jnp.int32)
        n = min(int(ranked.shape[0]), nlist)
        # Children partition [0, nlist): ranked holds <= nlist valid ids,
        # and the sort packs them first, so truncation only drops pads.
        return out.at[:n].set(ranked[:n])

    return jax.vmap(one)(Q)


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _probe_mask(order, part_bucket, offsets, counts, nprobe: int):
    """(B, nlist) bucket orders (-1 right-pads) -> the (B, P) mask of the
    partitions of each query's first ``nprobe`` buckets, and (B,) the first
    partition of its nearest probed non-empty bucket (-1: none).
    ``part_bucket`` (P,) names each partition's bucket (-1: none)."""
    B, nlist = order.shape
    sel = order[:, :nprobe]
    ok = sel >= 0
    hit = jnp.zeros((B, nlist), bool).at[
        jnp.arange(B)[:, None], jnp.where(ok, sel, nlist)
    ].set(True, mode="drop")
    mask = hit[:, jnp.maximum(part_bucket, 0)] & (part_bucket >= 0)[None, :]
    full = ok & (counts[jnp.maximum(sel, 0)] > 0)
    b0 = jnp.take_along_axis(sel, jnp.argmax(full, axis=1)[:, None], 1)[:, 0]
    start = jnp.where(jnp.any(full, axis=1), offsets[jnp.maximum(b0, 0)], -1)
    return mask, start.astype(jnp.int32)


@jax.jit
def _nearest_centroid(centroids: jax.Array, X: jax.Array) -> jax.Array:
    """(K, D), (N, D) -> (N,) nearest-centroid bucket per row (L2, matching
    the k-means training objective); used for centroid assignment on insert."""
    cross = X @ centroids.T                       # (N, K) — MXU
    cn = jnp.sum(centroids * centroids, axis=1)   # (K,)
    return jnp.argmin(cn[None, :] - 2.0 * cross, axis=1).astype(jnp.int32)


@dataclasses.dataclass
class IVFIndex:
    store: PDXStore                 # bucket-contiguous PDX partitions
    centroid_store: PDXStore        # centroids, PDX layout (for bucket ranking)
    centroids: jax.Array            # (K, D) horizontal copy (k-means updates)
    part_offsets: np.ndarray        # (K,) first partition id of each bucket
    part_counts: np.ndarray         # (K,) partitions per bucket
    nlist: int
    # Two-level routing tree (None -> flat scan).  ``super_centroids`` is
    # (SK, D); ``super_children`` is the (SK, M) -1-padded child table from
    # ``kmeans.build_centroid_tree``; ``nprobe_super`` is how many supers a
    # query descends into.
    super_centroids: Optional[jax.Array] = None
    super_children: Optional[jax.Array] = None
    nprobe_super: int = 0
    # device (part_bucket, offsets, counts) and the host arrays they copy
    _dev_buckets: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @property
    def tree_enabled(self) -> bool:
        return self.super_centroids is not None

    def routing_cost(self) -> int:
        """Centroids ranked per query: nlist for the flat scan, the
        sub-linear ``SK + nprobe_super * M`` bound for the tree (the bench
        asserts this stays < nlist)."""
        if not self.tree_enabled:
            return self.nlist
        SK, M = self.super_children.shape
        return int(SK + self.nprobe_super * M)

    def attach_tree(
        self,
        super_k: Optional[int] = None,
        nprobe_super: Optional[int] = None,
        *,
        seed: int = 0,
    ) -> None:
        """(Re)build the two-level tree over the CURRENT centroids — also
        the recalibration hook: after BSA re-projects centroids the tree
        must be re-clustered in the rotated space."""
        if super_k is None:
            super_k = max(2, int(np.ceil(np.sqrt(self.nlist))))
        sc, children = build_centroid_tree(
            np.asarray(self.centroids), super_k, seed=seed
        )
        self.super_centroids = jnp.asarray(sc)
        self.super_children = jnp.asarray(children)
        if nprobe_super is None:
            nprobe_super = max(2, sc.shape[0] // 4)
        self.nprobe_super = int(min(max(nprobe_super, 1), sc.shape[0]))

    def _ranked_batch(
        self, Q: jax.Array, metric: str, dtype: str
    ) -> jax.Array:
        """(B, D) queries -> (B, nlist) ascending bucket orders, scanning
        the centroid tiles at ``dtype`` width (the data scan's dtype policy
        applied to routing; see ``core.layout``).  Records the routing scan
        bytes so ``BENCH_routing.json``/dashboards see the shrink.

        With a tree attached the orders come from the two-level descent
        instead of the flat scan and carry -1 right-pads (only the
        candidate set under each query's super-centroids is ranked); the
        tree ranks f32 centroids at both levels — its byte shrink comes
        from visiting ``routing_cost() << nlist`` centroids, not from a
        narrower dtype."""
        if self.tree_enabled:
            order = _rank_centroids_tree(
                self.centroids, self.super_centroids, self.super_children,
                Q, self.nlist, metric, self.nprobe_super,
            )
            if _metrics.enabled():
                _metrics.counter(
                    "repro_device_bytes_total",
                    float(Q.shape[0]) * self.routing_cost()
                    * self.centroids.shape[1] * 4.0,
                    executor="route", component="scan", dtype="f32",
                )
            return order
        if dtype == "f32":
            order = _rank_centroids_batch(
                self.centroid_store.data, Q, self.nlist, metric
            )
            bpv = 4.0
        else:
            m = device_mirror(self.centroid_store, dtype)
            sc = m.scale if m.quantized else None
            off = m.offset if m.quantized else None
            order = _rank_centroids_batch_mirror(
                m.data, Q, self.nlist, metric, sc, off, m.packed, m.dim
            )
            bpv = m.bytes_per_value
        if _metrics.enabled():
            Pc, Dc, Cc = self.centroid_store.data.shape
            _metrics.counter(
                "repro_device_bytes_total",
                float(Q.shape[0]) * Pc * Dc * Cc * bpv,
                executor="route", component="scan", dtype=dtype,
            )
        return order

    def probes(self, nprobe: Optional[int]) -> int:
        """Buckets a query probes at ``SearchSpec.nprobe``: every one for
        None, else at most ``nprobe``."""
        return self.nlist if nprobe is None else min(int(nprobe), self.nlist)

    def _bucket_arrays(self) -> tuple:
        """Device copies of the bucket -> partition map, refreshed when the
        engine hands the index new ``part_offsets`` / ``part_counts``."""
        P = self.store.num_partitions
        cached = self._dev_buckets
        if (cached is None or cached[0] is not self.part_offsets
                or cached[1] is not self.part_counts or cached[2] != P):
            counts = np.asarray(self.part_counts, np.int64)
            pb = np.full(P, -1, np.int32)
            for b, (o, c) in enumerate(zip(self.part_offsets, counts)):
                pb[o:o + c] = b
            dev = (jnp.asarray(pb), jnp.asarray(self.part_offsets, jnp.int32),
                   jnp.asarray(counts, jnp.int32))
            cached = (self.part_offsets, self.part_counts, P, dev)
            self._dev_buckets = cached
        return cached[3]

    def probe_mask(
        self, Qt: jax.Array, nprobe: int, metric: str = "l2",
        dtype: str = "f32",
    ) -> tuple[jax.Array, jax.Array]:
        """Device-side routing of a (B, D) batch of (already pruner-
        transformed) queries: the (B, P) bool mask of the partitions of
        each query's ``nprobe`` nearest buckets, and (B,) int32 the first
        partition of its nearest probed non-empty bucket (-1: none).  The
        centroids are ranked at ``dtype`` as ``route_batch`` ranks them;
        nothing is read back to the host."""
        order = self._ranked_batch(
            jnp.atleast_2d(jnp.asarray(Qt, jnp.float32)), metric, dtype
        )
        return _probe_mask(order, *self._bucket_arrays(),
                           nprobe=self.probes(nprobe))

    def rank_buckets(
        self, q: jax.Array, metric: str = "l2", dtype: str = "f32"
    ) -> np.ndarray:
        """Distance of q to every centroid -> bucket ids sorted ascending.
        ``dtype`` scans a quantized centroid mirror instead of f32."""
        return np.asarray(
            self._ranked_batch(
                jnp.asarray(q, jnp.float32)[None], metric, dtype
            )[0]
        )

    def assign(self, X: np.ndarray) -> np.ndarray:
        """(N, D) rows -> (N,) bucket assignments (nearest centroid).  This
        is the insert path of a mutable store: rows are bucket-assigned at
        insert time so a later repack can drain them bucket-contiguously."""
        X = jnp.atleast_2d(jnp.asarray(X, jnp.float32))
        return np.asarray(_nearest_centroid(self.centroids, X))

    def partition_order(
        self, bucket_order: np.ndarray, nprobe: Optional[int]
    ) -> np.ndarray:
        sel = bucket_order[: self.probes(nprobe)]
        parts = [
            np.arange(
                self.part_offsets[b], self.part_offsets[b] + self.part_counts[b]
            )
            for b in sel
            if b >= 0  # tree orders right-pad with -1
        ]
        return np.concatenate(parts) if parts else np.zeros(0, np.int64)

    def route_batch(
        self, Qt: jax.Array, nprobe: Optional[int], metric: str = "l2",
        dtype: str = "f32",
    ) -> np.ndarray:
        """Query routing for the distributed bucket-routed executor: rank
        buckets for a whole (B, D) batch of (already pruner-transformed)
        queries -> (B, probes(nprobe)) bucket ids, best first.  The
        caller (``repro.dist.routing``) maps buckets onto owner shards via
        the placement and exchanges queries with one all-to-all.  ``dtype``
        runs the centroid scan over a quantized mirror (host-side, pre-
        collective: the exchange plan and collective count are unchanged)."""
        Qt = jnp.atleast_2d(jnp.asarray(Qt, jnp.float32))
        order = self._ranked_batch(Qt, metric, dtype)
        return np.asarray(order[:, : self.probes(nprobe)])

    def route(
        self, qt: jax.Array, nprobe: Optional[int], metric: str = "l2",
        dtype: str = "f32",
    ) -> tuple[np.ndarray, int]:
        """Query routing for the planner's adaptive executor: rank buckets
        by centroid distance of the (already pruner-transformed) query and
        return ``(partition visit order, start_parts)`` — START linear-scans
        every partition of the nearest *non-empty* bucket to seed the top-k
        threshold (empty buckets own zero partitions and zero scan work)."""
        border = self.rank_buckets(qt, metric, dtype)
        order = self.partition_order(border, nprobe)
        start_parts = 0
        for b in border[: self.probes(nprobe)]:
            if b >= 0 and self.part_counts[b] > 0:
                start_parts = int(self.part_counts[b])
                break
        return order, start_parts

    def search(
        self,
        q: jax.Array,
        k: int,
        pruner: Pruner,
        *,
        nprobe: int = 8,
        metric: str = "l2",
        schedule: str = "adaptive",
        delta_d: int = 32,
        sel_frac: float = 0.2,
        group: int = 8,
        stats: Optional[SearchStats] = None,
    ) -> TopK:
        """Compatibility wrapper around ``route`` + ``pdxearch``.  Engine
        code goes through ``repro.core.plan``, which calls ``route`` and
        owns the executor choice; this stays for direct index users."""
        qt = pruner.transform_query(jnp.asarray(q, jnp.float32))
        order, start_parts = self.route(qt, nprobe, metric)
        return pdxearch(
            self.store,
            q,
            k,
            pruner,
            metric=metric,
            schedule=schedule,
            delta_d=delta_d,
            sel_frac=sel_frac,
            group=group,
            pid_order=order,
            start_parts=start_parts,
            stats=stats,
        )


def build_ivf(
    X: np.ndarray,
    nlist: int,
    *,
    capacity: int = 1024,
    kmeans_iters: int = 10,
    seed: int = 0,
    precomputed: Optional[tuple[np.ndarray, np.ndarray]] = None,
    tree: bool | str = "auto",
    super_k: Optional[int] = None,
    nprobe_super: Optional[int] = None,
) -> IVFIndex:
    """Train k-means (or take precomputed (centroids, assignments) so
    competitors share identical buckets, as the paper does) and pack buckets
    into PDX partitions.

    ``tree`` controls the two-level centroid routing tree: ``True`` builds
    it, ``False`` keeps the flat scan, ``"auto"`` builds it once nlist
    reaches ``TREE_AUTO_NLIST``.  ``super_k`` defaults to ~sqrt(nlist),
    ``nprobe_super`` to super_k // 4."""
    X = np.asarray(X, np.float32)
    if precomputed is not None:
        centroids, assignments = precomputed
    else:
        centroids, assignments = kmeans(X, nlist, iters=kmeans_iters, seed=seed)
    store, offsets, nparts = build_bucketed_store(X, assignments, nlist, capacity)
    cstore = build_flat_store(centroids, capacity=min(1024, max(64, nlist)))
    ivf = IVFIndex(
        store=store,
        centroid_store=cstore,
        centroids=jnp.asarray(centroids),
        part_offsets=offsets,
        part_counts=nparts,
        nlist=nlist,
    )
    want_tree = tree is True or (tree == "auto" and nlist >= TREE_AUTO_NLIST)
    if want_tree:
        ivf.attach_tree(super_k, nprobe_super, seed=seed)
    return ivf
