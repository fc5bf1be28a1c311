"""PDX (Partition Dimensions Across) layout — the paper's core data structure.

A PDX *partition* stores up to ``capacity`` vectors dimension-major as a
``(D, capacity)`` tile, so a dimension slice ``data[d0:d1, :]`` is one
contiguous stretch per dimension (the paper's Figure 1).  Partitions map to
IVF buckets (approximate search) or horizontal slabs (exact search).

On TPU the trailing (vector) axis maps onto the 128-wide lane dimension, which
is why capacities here default to lane multiples; the paper's CPU-optimal
64-vector micro-block becomes a kernel tiling detail (see repro.kernels).

Build-time code is NumPy (offline, like index construction in FAISS); the
resulting arrays are device arrays consumed by jitted search code.

Two store flavours share the tile format:

Both flavours keep **f32 masters** and expose reduced-precision **device
mirrors** (``device_mirror(store, "bf16"|"int8")``): the scan hot path is
bandwidth-bound (paper Section 7), so the planner streams 1-2 bytes per
dimension value and re-ranks the surviving candidates against the f32
masters for exact returned distances.  Mirrors cache per ``tiles_version``
exactly like the f32 upload — see the dtype-policy block below.

* ``PDXStore`` — frozen build artifact (a dataclass of device arrays).
* ``MutablePDXStore`` — the versioned, mutable serving store (the paper's
  closing pitch: PDX "can work on vector data as-is ... attractive for
  vector databases with frequent updates").  It keeps NumPy master copies
  of the tiles plus a horizontal *write-head* buffer that absorbs inserts
  (scanned exactly, unpruned, until flushed), per-partition free-slot
  bitmaps (slots whose ``ids == -1`` are reusable), tombstoning deletes
  (slot poisoned to ``PAD_VALUE`` so it can never enter a top-k), and a
  ``repack()`` step that drains tombstones and the write-head back into
  lane-aligned, bucket-contiguous tiles.  ``store.version`` increases
  monotonically with every mutation; executors key their jit caches on it.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import os
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics

__all__ = [
    "PDXPartition",
    "PDXStore",
    "MutablePDXStore",
    "DeviceMirror",
    "ProjectionMirror",
    "BucketCache",
    "SCAN_DTYPES",
    "device_mirror",
    "projection_mirror",
    "unpack_int4",
    "build_flat_store",
    "build_bucketed_store",
    "pdx_to_nary",
]

# Sentinel padding value: a coordinate far from any real data so padded slots
# can never enter a top-k result (distances are monotone increasing in L2/L1).
PAD_VALUE = np.float32(3.0e18)

# ==========================================================================
# Dtype policy — quantized device mirrors.
#
# Masters stay f32 NumPy/device arrays (exactness lives there: the planner
# re-ranks candidates against them whenever the scan ran reduced-precision).
# The *device mirror* the scan executors actually stream is materialized at
# one of three precisions; the paper's Section 7 point is that the scan is
# bandwidth-bound, so bytes-per-dimension-value is the lever:
#
#   f32   4 B/value — the master tiles themselves (today's behavior).
#   bf16  2 B/value — plain downcast; same exponent range as f32, so the
#         PAD_VALUE sentinel keeps its monotone hugeness.
#   int8  1 B/value — per-dimension affine quantization
#         q = clip(round((x - offset_d) / scale_d), -127, 127) with
#         offset_d = dim_means[d] (the running moments the mutable store
#         already maintains, so a repack re-centers the codebook for free)
#         and scale_d sized to the *observed* max deviation of dimension d
#         over live slots — one masked pass at mirror build.  A k·sigma
#         range from dim_vars alone clips heavy tails (skewed datasets,
#         rows correlated with a pruner rotation) hard enough to corrupt
#         candidate selection, so the range is measured, not assumed; the
#         moments still provide the centering.  PAD columns quantize to
#         garbage by construction; every quantized consumer masks lanes
#         with ``ids < 0``.
#   int4  0.5 B/value — the same per-dimension affine, 15 levels
#         (clip to ±7), two values packed per byte along the dimension
#         axis: byte ``d`` of a packed tile holds dimension ``2d`` in its
#         low nibble and ``2d + 1`` in its high nibble, biased by +8 so
#         the payload is an unsigned nibble.  Consumers unpack in-register
#         (``kernels.pdx_scan``) or via ``unpack_int4``; ``data.shape[1]``
#         is ceil(D/2), so int4 consumers must take D from ``mirror.dim``.
#
# Mirrors are cached on the store keyed on ``tiles_version`` (like the f32
# upload): head-only inserts never re-quantize, a repack/flush invalidates.
# ==========================================================================
SCAN_DTYPES = ("f32", "bf16", "int8", "int4")
_BYTES_PER_VALUE = {"f32": 4, "bf16": 2, "int8": 1, "int4": 0.5}


@dataclasses.dataclass(frozen=True)
class DeviceMirror:
    """One device-resident copy of a store's sealed tiles at a scan dtype.

    ``data`` is (P, D, C) in the mirror dtype — (P, ceil(D/2), C) uint8 for
    the packed "int4" mirror, whose logical D is ``dim``; ``scale``/
    ``offset`` are the (D,) f32 dequantization vectors (ones/zeros for f32
    and bf16, so every consumer can apply ``x * scale + offset``
    unconditionally)."""

    dtype: str           # "f32" | "bf16" | "int8" | "int4"
    data: jax.Array      # (P, D, C) mirror-dtype tiles (packed for int4)
    scale: jax.Array     # (D,) f32
    offset: jax.Array    # (D,) f32
    tiles_version: int
    dim: int = 0         # logical D (== data.shape[1] except when packed)

    @property
    def bytes_per_value(self) -> float:
        return _BYTES_PER_VALUE[self.dtype]

    @property
    def packed(self) -> bool:
        return self.dtype == "int4"

    @property
    def quantized(self) -> bool:
        return self.dtype in ("int8", "int4")


@jax.jit
def _quantize_int8(data, ids, means):
    live = (ids >= 0)[:, None, :]  # (P, 1, C)
    dev = jnp.abs(data - means[None, :, None])
    absmax = jnp.max(jnp.where(live, dev, 0.0), axis=(0, 2))  # (D,)
    scale = jnp.maximum(absmax, 1e-6) / 127.0
    offset = means
    q = jnp.round((data - offset[None, :, None]) / scale[None, :, None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale, offset


@jax.jit
def _quantize_int4(data, ids, means):
    """Same observed-range affine as int8 at 15 levels, packed 2-per-byte
    along D (low nibble = even dim, high nibble = odd dim, +8 bias).  Odd D
    pads one zero-level nibble; dequantizing it yields exactly ``offset``
    of a dimension no consumer reads (ops pad q/scale/offset to match)."""
    live = (ids >= 0)[:, None, :]
    dev = jnp.abs(data - means[None, :, None])
    absmax = jnp.max(jnp.where(live, dev, 0.0), axis=(0, 2))
    scale = jnp.maximum(absmax, 1e-6) / 7.0
    offset = means
    return _quantize_extent_int4(data, scale, offset), scale, offset


def _pack_int4(q: jax.Array) -> jax.Array:
    """(m, D, C) integral f32 levels in [-7, 7] -> (m, ceil(D/2), C) bytes.
    The +8-biased nibbles are formed in 8 bits: an int32 copy of the levels
    would be as large as the f32 tiles themselves."""
    qb = (q + 8).astype(jnp.uint8)
    if qb.shape[1] % 2:  # zero level -> nibble 8
        qb = jnp.pad(qb, ((0, 0), (0, 1), (0, 0)), constant_values=8)
    return qb[:, 0::2, :] | (qb[:, 1::2, :] << 4)


def unpack_int4(packed: jax.Array, dim_axis: int = 0,
                dim: Optional[int] = None) -> jax.Array:
    """Packed int4 tile -> int8 quantization levels in [-7, 7].

    ``dim_axis`` is the packed-dimension axis (0 for a (Dp, V) tile, 1 for
    (P, Dp, V) stacks); the result doubles that axis, sliced back to
    ``dim`` when given (odd logical D)."""
    p = packed.astype(jnp.int32)
    lo = (p & 0xF) - 8
    hi = (p >> 4) - 8
    full = jnp.stack([lo, hi], axis=dim_axis + 1)
    shape = list(packed.shape)
    shape[dim_axis] *= 2
    full = full.reshape(shape)
    if dim is not None and dim != shape[dim_axis]:
        full = jax.lax.slice_in_dim(full, 0, dim, axis=dim_axis)
    return full.astype(jnp.int8)


def device_mirror(store, dtype: str = "f32") -> DeviceMirror:
    """The store's device mirror at ``dtype``, cached per ``tiles_version``.

    Works on frozen and mutable stores alike (frozen stores are version 0
    forever and keep hitting one entry per dtype); stale-version entries are
    evicted so churn never pins dead quantized tiles on device."""
    if dtype not in SCAN_DTYPES:
        raise ValueError(f"scan dtype must be one of {SCAN_DTYPES}, got {dtype!r}")
    version = getattr(store, "tiles_version", 0)
    cache = getattr(store, "_mirror_cache", None)
    if cache is None:
        cache = {}
        try:
            store._mirror_cache = cache
        except AttributeError:  # exotic frozen store: build uncached
            pass
    key = (dtype, version)
    mirror = cache.get(key)
    _metrics.counter(
        "repro_cache_events_total", cache="mirror",
        event="hit" if mirror is not None else "miss",
    )
    if mirror is None:
        _metrics.counter("repro_mirror_builds_total", dtype=dtype)
        data = store.data  # triggers the mutable store's lazy f32 sync
        D = data.shape[1]
        if dtype == "f32":
            mdata = data
            scale = jnp.ones((D,), jnp.float32)
            offset = jnp.zeros((D,), jnp.float32)
        elif dtype == "bf16":
            mdata = data.astype(jnp.bfloat16)
            scale = jnp.ones((D,), jnp.float32)
            offset = jnp.zeros((D,), jnp.float32)
        elif dtype == "int8":
            means = jnp.asarray(store.dim_means, jnp.float32)
            mdata, scale, offset = _quantize_int8(data, store.ids, means)
        else:  # int4 (packed two-per-byte)
            means = jnp.asarray(store.dim_means, jnp.float32)
            mdata, scale, offset = _quantize_int4(data, store.ids, means)
        mirror = DeviceMirror(
            dtype=dtype, data=mdata, scale=scale, offset=offset,
            tiles_version=version, dim=D,
        )
        for stale in [kk for kk in cache if kk[1] != version]:
            del cache[stale]
        cache[key] = mirror
    return mirror


@dataclasses.dataclass(frozen=True)
class ProjectionMirror:
    """A skinny learned-projection copy of the sealed tiles (LeanVec-style).

    ``data`` is (P, rank, C) in the mirror dtype — packed (P, ceil(rank/2),
    C) uint8 for int4 — holding the tiles projected onto the top-``rank``
    PCA components of the collection.  Because the components are
    orthonormal, the projected squared L2 distance **lower-bounds** the full
    distance for every query, so a plain ``proj_dist <= thr`` keep test is
    exact-safe regardless of which pruner runs the later full-dimension
    stages.  Same consumer contract as ``DeviceMirror``: ``x * scale +
    offset`` dequantizes, lanes with ``ids < 0`` are garbage, ``dim`` is the
    logical projected dimensionality (= rank)."""

    dtype: str             # "f32" | "bf16" | "int8" | "int4"
    data: jax.Array        # (P, rank, C) projected tiles (packed for int4)
    scale: jax.Array       # (rank,) f32
    offset: jax.Array      # (rank,) f32
    components: jax.Array  # (D, rank) f32 orthonormal columns: q_proj = q @ C
    tiles_version: int
    dim: int               # logical projected dimensionality == rank

    @property
    def rank(self) -> int:
        return self.dim

    @property
    def bytes_per_value(self) -> float:
        return _BYTES_PER_VALUE[self.dtype]

    @property
    def packed(self) -> bool:
        return self.dtype == "int4"

    @property
    def quantized(self) -> bool:
        return self.dtype in ("int8", "int4")


def projection_mirror(store, rank: int, dtype: str = "f32") -> ProjectionMirror:
    """The store's rank-``rank`` PCA projection mirror, cached like
    ``device_mirror`` per ``tiles_version``.

    PCA components come from the same machinery BSA uses
    (``core.pruners.pca_components``) fit on the live rows; they are shared
    across dtype/rank variants of one tiles_version (fitting dominates the
    build).  Projected tiles are quantized with the standard per-dimension
    affine recipe when ``dtype`` asks for it, with the projected collection
    means as the quantization centers."""
    if dtype not in SCAN_DTYPES:
        raise ValueError(f"scan dtype must be one of {SCAN_DTYPES}, got {dtype!r}")
    D = store.dim
    if not 1 <= rank <= D:
        raise ValueError(f"projection rank must be in [1, {D}], got {rank}")
    version = getattr(store, "tiles_version", 0)
    cache = getattr(store, "_proj_cache", None)
    if cache is None:
        cache = {}
        try:
            store._proj_cache = cache
        except AttributeError:
            pass
    key = (rank, dtype, version)
    mirror = cache.get(key)
    _metrics.counter(
        "repro_cache_events_total", cache="proj_mirror",
        event="hit" if mirror is not None else "miss",
    )
    if mirror is None:
        _metrics.counter("repro_mirror_builds_total", dtype=f"proj:{dtype}")
        comps = cache.get(("comps", version))
        if comps is None:
            from .pruners import pca_components  # deferred: pruners is a leaf

            sample = pdx_to_nary(store)[:65536]
            if len(sample) < 2:  # degenerate: identity "projection"
                comps = np.eye(D, dtype=np.float32)
            else:
                comps, _ = pca_components(sample)
            cache[("comps", version)] = comps
        Cj = jnp.asarray(comps[:, :rank])  # (D, rank)
        data = store.data  # triggers the mutable store's lazy f32 sync
        hi = jax.lax.Precision.HIGHEST  # same f32 projection as the queries
        proj = jnp.einsum("dr,pdc->prc", Cj, data, precision=hi)
        means = jnp.matmul(
            Cj.T, jnp.asarray(store.dim_means, jnp.float32), precision=hi
        )  # (rank,)
        if dtype == "f32":
            mdata = proj
            scale = jnp.ones((rank,), jnp.float32)
            offset = jnp.zeros((rank,), jnp.float32)
        elif dtype == "bf16":
            mdata = proj.astype(jnp.bfloat16)
            scale = jnp.ones((rank,), jnp.float32)
            offset = jnp.zeros((rank,), jnp.float32)
        elif dtype == "int8":
            mdata, scale, offset = _quantize_int8(proj, store.ids, means)
        else:  # int4
            mdata, scale, offset = _quantize_int4(proj, store.ids, means)
        mirror = ProjectionMirror(
            dtype=dtype, data=mdata, scale=scale, offset=offset,
            components=Cj, tiles_version=version, dim=rank,
        )
        for stale in [
            kk for kk in cache if kk[0] != "comps" and kk[2] != version
        ]:
            del cache[stale]
        if ("comps", version) in cache:
            for stale in [
                kk for kk in cache if kk[0] == "comps" and kk[1] != version
            ]:
                del cache[stale]
        cache[key] = mirror
    return mirror


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PDXPartition:
    """One PDX partition: ``data[d, i]`` = dimension ``d`` of vector ``i``."""

    data: jax.Array        # (D, capacity) float
    ids: jax.Array         # (capacity,) int32 original row ids, -1 for padding
    count: int             # number of valid vectors (static, build-time)

    def tree_flatten(self):
        return (self.data, self.ids), (self.count,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, ids = children
        return cls(data=data, ids=ids, count=aux[0])

    @property
    def dim(self) -> int:
        return self.data.shape[0]

    @property
    def capacity(self) -> int:
        return self.data.shape[1]


@dataclasses.dataclass
class PDXStore:
    """A collection of equal-capacity PDX partitions, batched into one array.

    ``data``   (P, D, C)  dimension-major tiles
    ``ids``    (P, C)     original row ids (-1 padding)
    ``counts`` (P,)       valid vectors per partition
    ``dim_means`` (D,)    collection-wide per-dimension means (BOND metadata)
    ``dim_vars``  (D,)    per-dimension variances (BSA block metadata)
    """

    data: jax.Array
    ids: jax.Array
    counts: jax.Array
    dim_means: jax.Array
    dim_vars: jax.Array

    @property
    def num_partitions(self) -> int:
        return self.data.shape[0]

    @property
    def dim(self) -> int:
        return self.data.shape[1]

    @property
    def capacity(self) -> int:
        return self.data.shape[2]

    @property
    def num_vectors(self) -> int:
        return int(np.sum(np.asarray(self.counts)))

    def partition(self, p: int) -> PDXPartition:
        return PDXPartition(
            data=self.data[p], ids=self.ids[p], count=int(self.counts[p])
        )


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pack_groups(
    X: np.ndarray,
    groups: Sequence[np.ndarray],
    capacity: int,
    row_ids: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack row-id groups into (P, D, C) dimension-major tiles.

    Empty groups emit NO partition (an empty IVF bucket must cost zero scan
    work — a full all-``PAD_VALUE`` tile is pure wasted DMA + FLOPs).
    ``row_ids`` maps a row index to its stored id (default: the row index
    itself; mutable-store repacks pass the surviving sparse ids).
    """
    n, d = X.shape
    parts_data, parts_ids, parts_counts = [], [], []
    for rows in groups:
        rows = np.asarray(rows, dtype=np.int64)
        for lo in range(0, len(rows), capacity):
            chunk = rows[lo : lo + capacity]
            tile = np.full((d, capacity), PAD_VALUE, dtype=X.dtype)
            ids = np.full((capacity,), -1, dtype=np.int32)
            tile[:, : len(chunk)] = X[chunk].T
            ids[: len(chunk)] = chunk if row_ids is None else row_ids[chunk]
            parts_data.append(tile)
            parts_ids.append(ids)
            parts_counts.append(len(chunk))
    if not parts_data:  # fully empty collection: one all-pad placeholder
        parts_data.append(np.full((d, capacity), PAD_VALUE, dtype=X.dtype))
        parts_ids.append(np.full((capacity,), -1, dtype=np.int32))
        parts_counts.append(0)
    return (
        np.stack(parts_data),
        np.stack(parts_ids),
        np.asarray(parts_counts, dtype=np.int32),
    )


def _store_from_packed(
    X: np.ndarray, data: np.ndarray, ids: np.ndarray, counts: np.ndarray
) -> PDXStore:
    return PDXStore(
        data=jnp.asarray(data),
        ids=jnp.asarray(ids),
        counts=jnp.asarray(counts),
        dim_means=jnp.asarray(X.mean(axis=0)),
        dim_vars=jnp.asarray(X.var(axis=0)),
    )


def build_flat_store(X: np.ndarray, capacity: int = 1024) -> PDXStore:
    """Exact-search store: horizontal slabs of ``capacity`` vectors.

    The paper uses 10K-vector partitions for exact search (Section 6.5); we
    default to a lane-friendly 1024 and let callers pick the paper's value.
    """
    X = np.asarray(X, dtype=np.float32)
    n = X.shape[0]
    groups = [np.arange(lo, min(lo + capacity, n)) for lo in range(0, n, capacity)]
    return _store_from_packed(X, *_pack_groups(X, groups, capacity))


def build_bucketed_store(
    X: np.ndarray, assignments: np.ndarray, num_buckets: int, capacity: int
) -> tuple[PDXStore, np.ndarray, np.ndarray]:
    """IVF-style store: one group per bucket, split into capacity-sized tiles.

    Returns (store, part_offsets, part_counts_per_bucket):
      partitions ``part_offsets[b] : part_offsets[b] + nparts[b]`` belong to
      bucket ``b`` (partitions are laid out bucket-contiguously, mirroring the
      paper's Figure 2 where IVF buckets map onto PDX blocks).
    """
    X = np.asarray(X, dtype=np.float32)
    assignments = np.asarray(assignments)
    groups, nparts = [], np.zeros(num_buckets, dtype=np.int64)
    for b in range(num_buckets):
        rows = np.nonzero(assignments == b)[0]
        groups.append(rows)
        # empty bucket => zero partitions => zero scan work (its offset simply
        # equals the next bucket's; partition_order yields an empty range)
        nparts[b] = _round_up(len(rows), capacity) // capacity
    data, ids, counts = _pack_groups(X, groups, capacity)
    offsets = np.concatenate([[0], np.cumsum(nparts)[:-1]])
    return _store_from_packed(X, data, ids, counts), offsets, nparts


def pdx_to_nary(store) -> np.ndarray:
    """Inverse transposition (round-trip oracle for tests).

    Works on frozen and mutable stores alike: live slots may sit anywhere in
    a tile (tombstones leave holes) and ids may be sparse (deleted ids are
    never reused), so row ``r`` of the output is the live vector with the
    ``r``-th smallest id.  For a freshly built store ids are dense 0..n-1 and
    this is the exact inverse of the build transposition.  Unflushed
    write-head rows of a ``MutablePDXStore`` are included.
    """
    data = np.asarray(store.data)
    ids = np.asarray(store.ids)
    live = ids >= 0  # (P, C)
    all_ids = [ids[live]]
    all_vecs = [np.swapaxes(data, 1, 2)[live]]  # (n_live, D)
    if hasattr(store, "head_live"):
        hids, hvecs = store.head_live()
        all_ids.append(hids)
        all_vecs.append(hvecs)
    flat_ids = np.concatenate(all_ids)
    flat_vecs = np.concatenate(all_vecs) if flat_ids.size else np.zeros(
        (0, store.dim), dtype=data.dtype
    )
    order = np.argsort(flat_ids, kind="stable")
    return np.ascontiguousarray(flat_vecs[order])


# ==========================================================================
# Tiered bucket cache — the beyond-HBM device working set.
#
# ``device_mirror`` materializes the WHOLE store at the scan dtype, which
# caps collection size at device HBM.  ``BucketCache`` keeps the f32 masters
# authoritative in host RAM and manages a fixed pool of tile-sized device
# slots as a bucket-granular cache: routing tells it which IVF buckets a
# batch will scan (``ensure``), cold buckets are LRU-evicted, and the
# requested buckets' tile extents are quantized host-side and uploaded.
# Quantization parameters are computed ONCE per store generation over all
# live masters with NumPy arithmetic that matches ``_quantize_int8``/
# ``_quantize_int4`` op-for-op, so a cached bucket's tiles are bitwise
# identical to the fully-resident mirror's — eviction/readmission can never
# change a candidate set.  ``generation`` tags every entry with the store's
# ``tiles_version``; any sealed-tile mutation invalidates the whole pool
# exactly like the mirror cache.
# ==========================================================================
@jax.jit
def _quantize_extent_int8(x, scale, offset):
    """(m, D, C) f32 tile extent -> int8 levels at the GIVEN per-dim affine
    (the cache's per-generation global params) — same rounding/clip ops as
    ``_quantize_int8`` so cached and fully-resident tiles match bitwise."""
    q = jnp.round((x - offset[None, :, None]) / scale[None, :, None])
    return jnp.clip(q, -127, 127).astype(jnp.int8)


@jax.jit
def _quantize_extent_int4(x, scale, offset):
    def tiles(t):
        return _pack_int4(jnp.clip(
            jnp.round((t - offset[None, :, None]) / scale[None, :, None]),
            -7, 7,
        ))

    # a few tiles per step: packing along D makes XLA:TPU materialize its
    # operands, which for the whole store would take gigabytes of HBM.  The
    # step divides m, so the split is a free reshape, never a sliced copy.
    m = x.shape[0]
    step = next(s for s in (8, 4, 2, 1) if m % s == 0)
    out = jax.lax.map(tiles, x.reshape(m // step, step, *x.shape[1:]))
    return out.reshape(m, *out.shape[2:])


def _locked(fn):
    """Serialize a ``BucketCache`` entry point on the instance RLock."""
    @functools.wraps(fn)
    def inner(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)
    return inner


# Single shared staging worker for async uploads: ``issue`` hands it the
# f32 extent copy, it quantizes + starts the device transfer off the query
# thread (NumPy ufuncs release the GIL, so staging genuinely overlaps the
# scan the query thread is driving).  One worker everywhere keeps upload
# ordering trivially FIFO and matches the depth-1 ticket discipline.
_stager: Optional[concurrent.futures.ThreadPoolExecutor] = None
_stager_lock = threading.Lock()


def _stage_pool() -> concurrent.futures.ThreadPoolExecutor:
    global _stager
    if _stager is None:
        with _stager_lock:
            if _stager is None:
                _stager = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="bucket-cache-stager"
                )
    return _stager


class _UploadTicket:
    """In-flight async upload batch from ``BucketCache.issue``: the
    admission stats, the in-flight staged tiles (a Future from the staging
    worker per missed extent, or an already-transferred device array on
    the legacy sync path), the issue timestamp, and the request (for a
    stale-generation redo).  ``BucketCache.wait`` installs it into the
    pool.  Holding the pending entries here until ``wait`` is the depth-1
    double buffer: upload batch N's staging stays alive while batch N+1
    is being staged, and never deeper — ``issue`` drains any outstanding
    ticket first."""

    __slots__ = (
        "stats", "pending", "buckets", "parts", "t_issue", "generation",
        "done",
    )

    def __init__(self, stats, pending, buckets, parts, t_issue, generation):
        self.stats = stats
        self.pending = pending    # [(slots np, tile Future|dev, ids dev)]
        self.buckets = buckets
        self.parts = parts
        self.t_issue = t_issue
        self.generation = generation
        self.done = False


def _host_quant_params(
    data: np.ndarray, ids: np.ndarray, means: np.ndarray, dtype: str
) -> tuple[np.ndarray, np.ndarray]:
    """Per-dimension (scale, offset) over the live host masters, float32
    arithmetic mirroring the jitted quantizers: offset = dim means, scale =
    live-masked absmax / 127 (int8) or / 7 (int4).  abs/sub/max/div are all
    exactly-rounded IEEE ops, so this equals the on-device computation."""
    D = data.shape[1]
    if dtype in ("f32", "bf16"):
        return np.ones((D,), np.float32), np.zeros((D,), np.float32)
    means = np.asarray(means, np.float32)
    live = (ids >= 0)[:, None, :]
    dev = np.abs(data - means[None, :, None]).astype(np.float32)
    absmax = np.max(np.where(live, dev, np.float32(0.0)), axis=(0, 2))
    # XLA strength-reduces the quantizers' ``/ denom`` to ``* (1/denom)``;
    # multiply by the f32 reciprocal here too or the scales drift one ulp.
    rdenom = np.float32(1.0 / (127.0 if dtype == "int8" else 7.0))
    scale = np.maximum(absmax, np.float32(1e-6)) * rdenom
    return scale.astype(np.float32), means


class BucketCache:
    """Fixed slot-pool device cache of bucket tile extents (see block
    comment above).

    ``capacity_slots`` tiles are pre-allocated once; each resident IVF
    bucket owns the contiguous run of its partitions inside the pool (tile-
    aligned extents, any slot order — the scan masks by ``slot_bucket``, it
    never assumes pool adjacency).  ``n_regions`` > 1 splits the pool into
    equal contiguous regions with independent free lists + LRU chains; the
    routed executor aligns regions with ``Placement.bucket_shard`` so each
    device shard caches exactly the buckets it owns and pool uploads land in
    that shard's slice of the sharded pool array.

    Concurrency: pool updates are functional (``array.at[slots].set``), so
    an in-flight device scan that captured the previous pool array snapshot
    keeps scanning consistent tiles while ``ensure`` builds the next one —
    this is what lets the serve executor overlap batch N+1's uploads with
    batch N's scan without a device-side lock.
    """

    def __init__(
        self,
        store,
        *,
        capacity_slots: int,
        dtype: str = "int8",
        n_regions: int = 1,
        bucket_region: Optional[np.ndarray] = None,
        part_offsets: Optional[np.ndarray] = None,
        part_counts: Optional[np.ndarray] = None,
    ):
        if dtype not in SCAN_DTYPES:
            raise ValueError(
                f"scan dtype must be one of {SCAN_DTYPES}, got {dtype!r}"
            )
        if capacity_slots < 1:
            raise ValueError(f"capacity_slots must be >= 1, got {capacity_slots}")
        if n_regions < 1:
            raise ValueError(f"n_regions must be >= 1, got {n_regions}")
        self.store = store
        self.dtype = dtype
        self.n_regions = int(n_regions)
        self.region_slots = max(capacity_slots // self.n_regions, 1)
        self.capacity_slots = self.region_slots * self.n_regions
        if bucket_region is None:
            self._bucket_region = None  # every bucket -> region 0
        else:
            self._bucket_region = np.asarray(bucket_region, np.int64)
        # frozen stores carry no bucket structure of their own; the builder
        # (IVF) passes the extent table explicitly.
        self._static_extent = None
        if part_offsets is not None:
            self._static_extent = (
                np.asarray(part_offsets, np.int64),
                np.asarray(part_counts, np.int64),
            )
        self.generation = -1
        # A/B knob (benches, regression triage): True restores the legacy
        # upload path — f32 masters over the bus, quantized on device,
        # blocking at issue — instead of async host-staged transfers.
        self.sync_uploads = False
        # Staging strategy: host-side quantize (1-2 bytes/dim over the
        # bus, staged on the worker thread) pays off when there is a real
        # H2D bus to shrink or a spare core to stage on.  On a single-core
        # CPU backend neither exists — the fused device quantizer is less
        # total work, so async uploads dispatch it without blocking.
        self.stage_on_host = (
            jax.default_backend() != "cpu" or (os.cpu_count() or 1) > 1
        )
        # populated by _revalidate (needs store geometry):
        self._pool = None            # (S, D', C) device, mirror dtype
        self._ids_dev = None         # (S, C) int32 device
        self._slot_bucket = None     # (S,) int64 host, -1 = free/invalid
        self._slot_bucket_dev = None
        self._slot_ids = None        # (S, C) int32 host mirror of _ids_dev
        self._scale = None           # (D,) f32 device
        self._offset = None
        self._scale_np = None
        self._offset_np = None
        self._resident: list = []    # per region: OrderedDict key -> slots
        self._free: list = []        # per region: list of free slot indices
        self._inflight: Optional[_UploadTicket] = None  # depth-1 pipeline
        # the serving loop prepares batch N+1 (issue) on the batcher thread
        # while batch N scans (wait/arrays) on the executor thread — every
        # public entry point takes this; reentrant because ensure nests
        # issue+wait and a stale-generation wait re-enters ensure.
        self._lock = threading.RLock()

    # ------------------------------------------------------------ geometry
    @property
    def dim(self) -> int:
        return self.store.dim

    @property
    def packed(self) -> bool:
        return self.dtype == "int4"

    @property
    def quantized(self) -> bool:
        return self.dtype in ("int8", "int4")

    @property
    def bytes_per_value(self) -> float:
        return _BYTES_PER_VALUE[self.dtype]

    @property
    def resident_slots(self) -> int:
        return self.capacity_slots - sum(len(f) for f in self._free)

    def resident_buckets(self) -> list[int]:
        return [k if isinstance(k, int) else k[0]
                for reg in self._resident for k in reg]

    def _region_of(self, b: int) -> int:
        if self._bucket_region is None:
            return 0
        return int(self._bucket_region[b])

    def _masters(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Host-side (data, ids, means) views — NumPy masters for the
        mutable store, a host pull for a frozen one (host RAM is the
        authoritative tier either way)."""
        data = getattr(self.store, "_data", None)
        if data is not None:
            return data, self.store._ids, self.store._dim_means
        return (
            np.asarray(self.store.data),
            np.asarray(self.store.ids),
            np.asarray(self.store.dim_means, np.float32),
        )

    def _bucket_extent(self) -> tuple[np.ndarray, np.ndarray]:
        """Current (part_offsets, part_counts) — re-read per call for
        mutable stores: repack/adopt moves bucket -> partition ownership."""
        if getattr(self.store, "num_buckets", None) is not None:
            return (
                np.asarray(self.store.part_offsets, np.int64),
                np.asarray(self.store.part_counts, np.int64),
            )
        if self._static_extent is None:
            raise ValueError(
                "store has no bucket structure; pass part_offsets/"
                "part_counts to BucketCache"
            )
        return self._static_extent

    # -------------------------------------------------------- invalidation
    def _revalidate(self) -> None:
        gen = getattr(self.store, "tiles_version", 0)
        if gen == self.generation:
            return
        if self.generation >= 0 and _metrics.enabled():
            _metrics.counter(
                "repro_tiered_cache_events_total", event="invalidate"
            )
        data, ids, means = self._masters()
        P, D, C = data.shape
        Dp = (D + 1) // 2 if self.packed else D
        pool_dt = {
            "f32": jnp.float32, "bf16": jnp.bfloat16,
            "int8": jnp.int8, "int4": jnp.uint8,
        }[self.dtype]
        S = self.capacity_slots
        self._pool = jnp.zeros((S, Dp, C), pool_dt)
        self._ids_dev = jnp.full((S, C), -1, jnp.int32)
        self._slot_ids = np.full((S, C), -1, np.int32)
        self._slot_bucket = np.full((S,), -1, np.int64)
        self._slot_bucket_dev = jnp.asarray(self._slot_bucket)
        sc, off = _host_quant_params(data, ids, means, self.dtype)
        self._scale_np, self._offset_np = sc, off
        self._scale = jnp.asarray(sc)
        self._offset = jnp.asarray(off)
        self._resident = [
            collections.OrderedDict() for _ in range(self.n_regions)
        ]
        self._free = [
            list(range(r * self.region_slots, (r + 1) * self.region_slots))
            for r in range(self.n_regions)
        ]
        self.generation = gen

    # ------------------------------------------------------------- serving
    def _host_quantize(self, x: np.ndarray, scale=None, offset=None):
        """(m, D, C) f32 host extent -> pool-dtype staging array.  NumPy
        arithmetic bitwise-matching the jitted extent quantizers (sub/div/
        rint/clip are all exactly-rounded IEEE ops on both paths), so a
        host-staged upload equals on-device quantization bit for bit —
        while the H2D copy shrinks to 1-2 bytes per dimension instead of
        the f32 masters.  ``scale``/``offset`` pin the quant params when
        the staging worker runs after the issue that captured them."""
        sc = self._scale_np if scale is None else scale
        off = self._offset_np if offset is None else offset
        if self.dtype == "int8":
            # in-place passes (one ~x-sized temp total): the staging
            # worker shares cores with the scan, so every avoided
            # temporary is scan time.  Same sub/div/rint/clip op sequence
            # as the jitted twin — bitwise parity is load-bearing.
            q = np.subtract(x, off[None, :, None], dtype=np.float32)
            np.divide(q, sc[None, :, None], out=q)
            np.rint(q, out=q)
            np.clip(q, -127, 127, out=q)
            return q.astype(np.int8)
        if self.dtype == "int4":
            q = np.subtract(x, off[None, :, None], dtype=np.float32)
            np.divide(q, sc[None, :, None], out=q)
            np.rint(q, out=q)
            np.clip(q, -7, 7, out=q)
            q = q.astype(np.int32)
            if q.shape[1] % 2:
                q = np.pad(q, ((0, 0), (0, 1), (0, 0)))
            qb = (q + 8).astype(np.uint8)
            return qb[:, 0::2, :] | (qb[:, 1::2, :] << 4)
        if self.dtype == "bf16":
            return np.asarray(x, np.float32).astype(jnp.bfloat16)
        return np.ascontiguousarray(x, np.float32)

    def _device_quantize(self, ext):
        """Pool-dtype tile from an on-device f32 extent — the jitted
        twins of ``_host_quantize`` (bitwise-equal results)."""
        if self.dtype == "int8":
            return _quantize_extent_int8(ext, self._scale, self._offset)
        if self.dtype == "int4":
            return _quantize_extent_int4(ext, self._scale, self._offset)
        if self.dtype == "bf16":
            return ext.astype(jnp.bfloat16)
        return ext

    @staticmethod
    def _sub_extent(off, cnt, part):
        """Row window of sub-extent ``part = (part_i, n_parts)`` of a
        bucket extent — ceil-divided so every part fits a region."""
        if part is None:
            return off, cnt
        pi, n_parts = part
        per = -(-cnt // n_parts)
        return off + pi * per, max(min(per, cnt - pi * per), 0)

    @_locked
    def resident_ok(self, buckets, parts: Optional[dict] = None) -> bool:
        """True when every (sub-)extent of the request is still resident —
        the run loop's cheap guard against a concurrent batch's ``issue``
        having evicted tiles between this pass's prefetch and its scan."""
        if getattr(self.store, "tiles_version", 0) != self.generation:
            return False
        _, cnts = self._bucket_extent()
        for b in np.asarray(buckets, np.int64).reshape(-1):
            b = int(b)
            if b < 0 or b >= len(cnts) or int(cnts[b]) == 0:
                continue
            part = (parts or {}).get(b)
            key = b if part is None else (b,) + tuple(part)
            if key not in self._resident[self._region_of(b)]:
                return False
        return True

    @_locked
    def issue(self, buckets, parts: Optional[dict] = None) -> _UploadTicket:
        """Asynchronous half of ``ensure``: run the LRU admission
        bookkeeping and hand every missing extent to the staging worker,
        which host-quantizes it and STARTS its ``jax.device_put`` —
        returning a ticket whose ``wait`` installs the in-flight copies
        into the pool.  Staging and copies overlap whatever the query
        thread and device are executing (the
        previous chunk's scan in the tiered loop, the previous batch's
        whole search through the serving handoff).  Depth-1 discipline:
        issuing while another ticket is in flight waits that one first,
        so at most one upload batch is ever pending.

        ``parts`` maps bucket -> ``(part_index, n_parts)`` to admit one
        region-sized sub-extent of a bucket too large for its region; the
        tiered executor scans each sub-extent in its own pass and merges
        top-k, so a single query whose routed demand exceeds the slot pool
        succeeds instead of raising."""
        if self._inflight is not None:
            self.wait(self._inflight)
        self._revalidate()
        offs, cnts = self._bucket_extent()
        data, ids, _ = self._masters()
        hits = misses = evicted = uploaded = 0
        pending: list = []
        seen = set()
        for b in np.asarray(buckets, np.int64).reshape(-1):
            b = int(b)
            part = (parts or {}).get(b)
            key = b if part is None else (b,) + tuple(part)
            if b < 0 or key in seen:
                continue
            seen.add(key)
            cnt = int(cnts[b]) if b < len(cnts) else 0
            off, cnt = self._sub_extent(int(offs[b]) if cnt else 0, cnt, part)
            if cnt == 0:
                continue
            r = self._region_of(b)
            res = self._resident[r]
            if key in res:
                hits += 1
                res.move_to_end(key)
                continue
            misses += 1
            if cnt > self.region_slots:
                raise ValueError(
                    f"bucket {b} spans {cnt} tiles > region capacity "
                    f"{self.region_slots}; split it via parts= or raise "
                    "hbm_slots"
                )
            while len(self._free[r]) < cnt:
                # Evict the coldest entry NOT requested by this batch —
                # everything in ``seen`` is pinned for the upcoming scan.
                victim = next((o for o in res if o not in seen), None)
                if victim is None:
                    raise ValueError(
                        f"batch demands more tiles than region {r} holds "
                        f"({self.region_slots} slots); raise hbm_slots or "
                        "split the batch"
                    )
                old_slots = res.pop(victim)
                self._free[r].extend(old_slots.tolist())
                self._slot_bucket[old_slots] = -1
                evicted += 1
            slots = np.asarray(
                [self._free[r].pop() for _ in range(cnt)], np.int64
            )
            ext_ids = np.ascontiguousarray(ids[off : off + cnt], np.int32)
            ext = np.ascontiguousarray(data[off : off + cnt], np.float32)
            if self.sync_uploads:
                # legacy path: the full-width f32 extent crosses the bus,
                # quantizes on device, and the host stalls until it lands —
                # bitwise-identical tiles (see _host_quantize), 2-4x the
                # H2D payload and zero overlap
                tile = self._device_quantize(jax.device_put(ext))
                jax.block_until_ready(tile)
                pending.append((slots, tile, jax.device_put(ext_ids)))
            elif self.stage_on_host:
                # quantize + device_put on the staging worker: the heavy
                # NumPy pass runs off the query thread, overlapping
                # whatever scan that thread dispatches next, and only the
                # quantized bytes cross the bus
                fut = _stage_pool().submit(
                    lambda x=ext, sc=self._scale_np, of=self._offset_np:
                        jax.device_put(self._host_quantize(x, sc, of))
                )
                pending.append((slots, fut, jax.device_put(ext_ids)))
            else:
                # single-core CPU: fused device quantize dispatched
                # asynchronously — same total work as the legacy path but
                # ``wait`` blocks once per upload batch, not per miss
                tile = self._device_quantize(jax.device_put(ext))
                pending.append((slots, tile, jax.device_put(ext_ids)))
            res[key] = slots
            self._slot_ids[slots] = ext_ids
            self._slot_bucket[slots] = b
            uploaded += cnt
            if _metrics.enabled():
                # actual H2D payload: quantized staging bytes on the
                # host-staged path, the f32 extent otherwise
                staged_host = not self.sync_uploads and self.stage_on_host
                _metrics.counter(
                    "repro_tiered_prefetch_bytes_total",
                    float(cnt * self.dim * data.shape[2])
                    * (self.bytes_per_value if staged_host else 4.0),
                    dtype=self.dtype,
                )
        ticket = _UploadTicket(
            stats={"hits": hits, "misses": misses,
                   "evicted": evicted, "uploaded_slots": uploaded},
            pending=pending, buckets=np.asarray(buckets, np.int64),
            parts=parts, t_issue=time.perf_counter(),
            generation=self.generation,
        )
        self._inflight = ticket
        return ticket

    @_locked
    def wait(self, ticket: Optional[_UploadTicket]) -> dict:
        """Blocking half of ``ensure``: install the ticket's in-flight
        copies into the pool (functional ``.at[slots].set`` updates —
        snapshots captured by earlier ``arrays()`` calls stay consistent),
        block until the H2D transfers land, and meter how long the host
        actually waited vs the full issue->complete window
        (``repro_cache_upload_wait_us`` / ``..._overlap_ratio``): a wait
        near zero means the copies hid entirely behind compute."""
        if ticket is None:
            return {"hits": 0, "misses": 0, "evicted": 0,
                    "uploaded_slots": 0}
        if ticket.done:
            return ticket.stats
        ticket.done = True
        if self._inflight is ticket:
            self._inflight = None
        if getattr(self.store, "tiles_version", 0) != ticket.generation:
            # the store mutated mid-flight: the pool is (about to be)
            # rebuilt; drop the stale copies and re-admit synchronously
            return self.ensure(ticket.buckets, parts=ticket.parts)
        t0 = time.perf_counter()
        if ticket.pending:
            resolved = []
            for slots, tile_dev, ids_dev in ticket.pending:
                if isinstance(tile_dev, concurrent.futures.Future):
                    tile_dev = tile_dev.result()
                jslots = jnp.asarray(slots)
                self._pool = self._pool.at[jslots].set(tile_dev)
                self._ids_dev = self._ids_dev.at[jslots].set(ids_dev)
                resolved.append(tile_dev)
            jax.block_until_ready(resolved)
            done = time.perf_counter()
            from ..obs.meters import cache_upload_wait

            cache_upload_wait(
                (done - t0) * 1e6, (done - ticket.t_issue) * 1e6
            )
        stats = ticket.stats
        if stats["evicted"] or stats["uploaded_slots"]:
            self._slot_bucket_dev = jnp.asarray(self._slot_bucket)
        if _metrics.enabled():
            for key, event in (("hits", "hit"), ("misses", "miss"),
                               ("evicted", "evict")):
                if stats[key]:
                    _metrics.counter(
                        "repro_tiered_cache_events_total",
                        float(stats[key]), event=event,
                    )
            _metrics.gauge(
                "repro_tiered_cache_resident_slots",
                float(self.resident_slots),
            )
        return stats

    @_locked
    def ensure(self, buckets, parts: Optional[dict] = None) -> dict:
        """Admit every requested bucket (routed set of the NEXT batch —
        calling this from the host/prepare phase is the prefetch), evicting
        cold LRU entries per region as needed.  Returns
        ``{"hits", "misses", "evicted", "uploaded_slots"}``.  The
        synchronous composition of ``issue`` + ``wait``; callers that can
        overlap uploads with compute use the halves directly.

        Raises ValueError only when one bucket alone exceeds a region AND
        no ``parts`` sub-extent split was requested (the tiered executor
        always splits, so oversized routed demand succeeds there)."""
        return self.wait(self.issue(buckets, parts=parts))

    @_locked
    def arrays(self):
        """Snapshot of the device-side cache state for a scan closure:
        ``(pool, slot_ids, slot_bucket, scale, offset)``.  Functional pool
        updates mean later ``ensure`` calls never mutate these arrays; an
        in-flight upload ticket is installed first, so the snapshot always
        reflects everything admitted so far."""
        if self._inflight is not None:
            self.wait(self._inflight)
        self._revalidate()
        return (
            self._pool, self._ids_dev, self._slot_bucket_dev,
            self._scale, self._offset,
        )

    @_locked
    def snapshot(self) -> tuple:
        """Atomic ``(arrays(), slot_ids copy)`` pair — the run loop's scan
        inputs and its id-resolution table must come from the same instant
        or a concurrent ``issue`` could remap ids between the two reads."""
        return self.arrays(), np.array(self.slot_ids_host(), copy=True)

    @_locked
    def slot_ids_host(self) -> np.ndarray:
        """(S, C) host copy of the pool's vector ids (candidate positions
        from a pool scan resolve to global ids through this)."""
        if self._inflight is not None:
            self.wait(self._inflight)
        self._revalidate()
        return self._slot_ids


# ==========================================================================
# Mutable PDX — the versioned serving store.
# ==========================================================================
class MutablePDXStore:
    """Versioned, mutable PDX store: sealed tiles + write-head + tombstones.

    Presents the same read interface as ``PDXStore`` (``data``/``ids``/
    ``counts`` device arrays, ``dim``/``capacity``/``num_partitions``), so
    every executor consumes it unchanged; mutation happens on NumPy master
    copies and the device mirror is refreshed lazily, once per version.

    Mutation model
      * ``insert(V)`` appends rows to a small horizontal *write-head*
        ``(head_capacity, D)`` buffer.  Write-head rows are scanned exactly
        (unpruned) by every executor — the planner merges them into each
        top-k (see ``repro.core.plan.execute``) — until a flush drains them
        into sealed tiles.
      * ``delete(ids)`` tombstones: the slot's id becomes -1 (which is also
        the free-slot bitmap bit) and its column is poisoned to
        ``PAD_VALUE`` so no metric can ever rank it into a top-k.
      * ``flush()`` drains live write-head rows into free sealed slots
        (bucket-local for bucketed stores, preserving the bucket-contiguous
        layout); when free slots run out it falls back to ``repack()``.
      * ``repack()`` rebuilds lane-aligned tiles from scratch out of the
        surviving rows (bucket-contiguous for IVF) — the "background
        re-pack" of the ROADMAP.  Partition count shrinks back to the
        minimum, tombstone holes disappear, and pruner metadata
        (``dim_means``/``dim_vars``) is refreshed from running moments.

    ``version`` increases on every mutating call; jitted-executor caches
    (``core.pdxearch._EXEC_CACHE``) and plan traces key on it so a search
    can never reuse state derived from stale tiles.  ``tiles_version``
    increases only when the *sealed* tiles change (sealed delete, flush,
    repack): the device mirror and the sharded executors' ``Placement``
    cache key on it, so a head-only insert never re-uploads the whole store
    or re-arranges a distributed placement.  Under bucket-owned sharding
    (``repro.dist.placement``) this means an insert lands in the owning
    shard's slice for free: the row's bucket is assigned at insert time,
    ``flush`` fills free slots inside that bucket's partitions — which live
    in the owner shard's contiguous slice — and the placement is only
    rebuilt when a flush/repack actually moves sealed tiles.

    Pruner metadata is *incrementally* maintained: running per-dimension
    sum / sum-of-squares are updated O(D) per inserted/deleted row, and the
    public ``dim_means``/``dim_vars`` snapshot is refreshed on repack or
    whenever the fraction of mutations since the last refresh exceeds
    ``meta_staleness`` — never on every insert.
    """

    def __init__(
        self,
        data: np.ndarray,
        ids: np.ndarray,
        counts: np.ndarray,
        dim_means: np.ndarray,
        dim_vars: np.ndarray,
        *,
        head_capacity: int = 256,
        num_buckets: Optional[int] = None,
        part_bucket: Optional[np.ndarray] = None,
        meta_staleness: float = 0.25,
    ):
        # np.asarray over a jax array yields a read-only view; these are the
        # mutable masters, so force writable copies.
        self._data = np.array(data, dtype=np.float32, copy=True, order="C")
        self._ids = np.array(ids, dtype=np.int32, copy=True, order="C")
        self._counts = np.asarray(counts, np.int32).copy()
        # NOTE the per-partition free-slot bitmap IS `self._ids < 0` — a slot
        # is reusable iff its id is the -1 sentinel, with no second array to
        # keep in sync (see _plan_free_slot_fill).
        self._dim_means = np.asarray(dim_means, np.float32).copy()
        self._dim_vars = np.asarray(dim_vars, np.float32).copy()
        self.meta_staleness = float(meta_staleness)
        # version: every mutation (cache keys / plan traces key on it).
        # tiles_version: only mutations that touch the SEALED tiles (sealed
        # delete, flush, repack) — head-only inserts leave it alone, so the
        # device mirror / padded-tile caches skip the full-store re-upload.
        self.version = 0
        self.tiles_version = 0

        P, D, C = self._data.shape
        if head_capacity < 1:
            raise ValueError(
                f"head_capacity must be >= 1, got {head_capacity}"
            )
        self.head_capacity = int(head_capacity)
        self._head_data = np.full(
            (self.head_capacity, D), PAD_VALUE, dtype=np.float32
        )
        self._head_ids = np.full((self.head_capacity,), -1, dtype=np.int32)
        self._head_assign = np.full((self.head_capacity,), -1, dtype=np.int32)
        self._head_n = 0  # append pointer (holes stay until flush)

        # bucket structure (IVF): which bucket owns each sealed partition
        self.num_buckets = num_buckets
        if num_buckets is not None:
            if part_bucket is None:
                raise ValueError("bucketed store needs part_bucket")
            self._part_bucket = np.asarray(part_bucket, np.int64).copy()
        else:
            self._part_bucket = np.full((P,), -1, dtype=np.int64)

        # id -> location map ('s', p, c) sealed | ('h', j) write-head
        self._id_loc = self._build_id_loc()
        self._next_id = 1 + max(self._id_loc, default=-1)

        # running per-dimension moments over live rows (float64 for drift)
        live = self._ids >= 0
        live_vecs = np.swapaxes(self._data, 1, 2)[live].astype(np.float64)
        self._sum = live_vecs.sum(axis=0)
        self._sumsq = (live_vecs**2).sum(axis=0)
        self._n_live = int(live.sum())
        self._mutations_since_meta = 0

        self._dev: Optional[tuple] = None
        self._dev_version = -1
        # mutation oplog (delta-replay for background maintenance): None =
        # not recording; a list accumulates ("insert"|"delete", ...) entries
        # between oplog_start() and oplog_take().
        self._oplog: Optional[list] = None
        self._oplog_limit = 8192

    # -------------------------------------------------- mutation oplog
    def oplog_start(self, limit: int = 8192) -> None:
        """Begin recording mutations (insert/delete) applied to THIS store.

        The maintenance thread calls this right after cloning: mutations
        that land while the clone repacks off-thread are replayed onto the
        clone before ``adopt``, so adoption succeeds under continuous
        traffic instead of discarding the repack work.  Bounded by
        ``limit`` rows — a flood larger than that makes replay pointless
        (the clone is about as stale as a fresh clone is cheap), so the log
        overflows and ``oplog_take`` reports it."""
        self._oplog = []
        self._oplog_limit = int(limit)
        self._oplog_rows = 0

    def oplog_take(self) -> Optional[list]:
        """Stop recording and return the recorded ops in application order,
        or None if the log overflowed ``limit`` rows (caller should discard
        its clone).  Entries are ``("insert", V, assignments, ids)`` /
        ``("delete", ids)`` with defensively copied arrays."""
        ops, self._oplog = self._oplog, None
        if ops is not None and self._oplog_rows > self._oplog_limit:
            return None
        return ops

    def _oplog_record(self, entry: tuple, rows: int) -> None:
        if self._oplog is None:
            return
        self._oplog_rows += rows
        if self._oplog_rows <= self._oplog_limit:
            self._oplog.append(entry)

    def replay(self, ops: list) -> int:
        """Apply an ``oplog_take`` list to this store (the maintenance
        clone); returns rows replayed.  Replayed inserts must reproduce the
        recorded ids — guaranteed because ``clone()`` copies ``_next_id``
        and id assignment is sequential — and a mismatch raises, because a
        store with diverged ids must never be adopted."""
        rows = 0
        for op in ops:
            if op[0] == "insert":
                _, V, assignments, ids = op
                got = self.insert(V, assignments)
                if not np.array_equal(got, ids):
                    raise ValueError(
                        "oplog replay id divergence: "
                        f"expected {ids[:4]}..., got {got[:4]}..."
                    )
                rows += len(ids)
            else:
                rows += self.delete(op[1])
        return rows

    def _build_id_loc(self) -> dict[int, tuple]:
        """Vectorized sealed-slot scan (a Python loop over P*C slots would
        dominate repack latency at 100k+ vectors)."""
        ps, cs = np.nonzero(self._ids >= 0)
        return {
            i: ("s", p, c)
            for i, p, c in zip(
                self._ids[ps, cs].tolist(), ps.tolist(), cs.tolist()
            )
        }

    # ----------------------------------------------------------- constructors
    @classmethod
    def from_store(
        cls,
        store: PDXStore,
        *,
        head_capacity: int = 256,
        num_buckets: Optional[int] = None,
        part_counts: Optional[np.ndarray] = None,
        meta_staleness: float = 0.25,
    ) -> "MutablePDXStore":
        """Unseal a frozen ``PDXStore``.  For a bucketed (IVF) store pass its
        per-bucket ``part_counts`` so repack keeps bucket contiguity (the
        layout is bucket-contiguous, so counts fully determine ownership)."""
        part_bucket = None
        if num_buckets is not None:
            nparts = np.asarray(part_counts, np.int64)
            part_bucket = np.repeat(np.arange(num_buckets), nparts)
            if len(part_bucket) < store.num_partitions:  # pad placeholders
                part_bucket = np.concatenate([
                    part_bucket,
                    np.full(
                        store.num_partitions - len(part_bucket), -1, np.int64
                    ),
                ])
        return cls(
            np.asarray(store.data), np.asarray(store.ids),
            np.asarray(store.counts), np.asarray(store.dim_means),
            np.asarray(store.dim_vars),
            head_capacity=head_capacity, num_buckets=num_buckets,
            part_bucket=part_bucket, meta_staleness=meta_staleness,
        )

    def _bump(self, tiles: bool = False):
        self.version += 1
        if tiles:
            self.tiles_version += 1

    # ------------------------------------------------------ PDXStore interface
    def _sync_device(self):
        if self._dev_version != self.tiles_version:
            _metrics.counter("repro_store_device_uploads_total")
            self._dev = (
                jnp.array(self._data),
                jnp.array(self._ids),
                jnp.array(self._counts),
            )
            self._dev_version = self.tiles_version

    def _obs_mutation(self, op: str, rows: int) -> None:
        """Record one mutation event plus the store-health gauges the
        serving tier watches (live rows, write-head fill, metadata
        staleness).  One enabled() check when observability is off."""
        if not _metrics.enabled():
            return
        _metrics.counter("repro_store_mutations_total", op=op)
        _metrics.counter("repro_store_rows_mutated_total", float(rows), op=op)
        _metrics.gauge("repro_store_live_vectors", float(self._n_live))
        _metrics.gauge(
            "repro_store_head_fill",
            self.head_count / max(self.head_capacity, 1),
        )
        _metrics.gauge(
            "repro_store_meta_staleness",
            self._mutations_since_meta / max(self._n_live, 1),
        )

    @property
    def data(self) -> jax.Array:
        self._sync_device()
        return self._dev[0]

    @property
    def ids(self) -> jax.Array:
        self._sync_device()
        return self._dev[1]

    @property
    def counts(self) -> jax.Array:
        self._sync_device()
        return self._dev[2]

    @property
    def dim_means(self) -> jax.Array:
        return jnp.asarray(self._dim_means)

    @property
    def dim_vars(self) -> jax.Array:
        return jnp.asarray(self._dim_vars)

    @property
    def num_partitions(self) -> int:
        return self._data.shape[0]

    @property
    def dim(self) -> int:
        return self._data.shape[1]

    @property
    def capacity(self) -> int:
        return self._data.shape[2]

    @property
    def num_vectors(self) -> int:
        """Live vectors: sealed non-tombstoned slots + unflushed head rows."""
        return int(self._counts.sum()) + int((self._head_ids >= 0).sum())

    def partition(self, p: int) -> PDXPartition:
        return PDXPartition(
            data=self.data[p], ids=self.ids[p], count=int(self._counts[p])
        )

    # -------------------------------------------------------- bucket structure
    @property
    def part_offsets(self) -> np.ndarray:
        """(K,) first partition id of each bucket (bucket-contiguous layout)."""
        nparts = self.part_counts
        return np.concatenate([[0], np.cumsum(nparts)[:-1]]).astype(np.int64)

    @property
    def part_counts(self) -> np.ndarray:
        """(K,) partitions per bucket; 0 for empty buckets."""
        if self.num_buckets is None:
            raise ValueError("flat store has no bucket structure")
        return np.bincount(
            self._part_bucket[self._part_bucket >= 0],
            minlength=self.num_buckets,
        ).astype(np.int64)

    # -------------------------------------------------------------- write-head
    @property
    def head_count(self) -> int:
        return int((self._head_ids >= 0).sum())

    def head_live(self) -> tuple[np.ndarray, np.ndarray]:
        """Live write-head rows -> ((m,) ids, (m, D) vectors).  These must be
        merged *exactly* (no pruning) into every executor's top-k."""
        mask = self._head_ids >= 0
        return self._head_ids[mask].copy(), self._head_data[mask].copy()

    def head_snapshot(self) -> tuple[np.ndarray, np.ndarray]:
        """The FULL write-head buffer -> ((head_capacity,) ids, (head_capacity,
        D) vectors), dead slots included (id -1, data ``PAD_VALUE``).  Unlike
        ``head_live`` the returned shapes never change, so the merge kernel in
        ``core.plan`` compiles once per (batch bucket, head_capacity) instead
        of once per fill level — the serving tier's zero-recompile contract
        under churn."""
        return self._head_ids.copy(), self._head_data.copy()

    # --------------------------------------------------------------- mutation
    def insert(
        self, V: np.ndarray, assignments: Optional[np.ndarray] = None
    ) -> np.ndarray:
        """Absorb rows into the write-head; returns their new global ids.

        ``assignments`` — per-row IVF bucket (centroid assignment done at
        insert time by the index); required for bucketed stores.  A full
        write-head flushes itself (free-slot fill, falling back to repack).
        """
        V = np.atleast_2d(np.ascontiguousarray(np.asarray(V, np.float32)))
        if V.shape[1] != self.dim:
            raise ValueError(f"expected (N, {self.dim}) rows, got {V.shape}")
        if self.num_buckets is not None:
            if assignments is None:
                raise ValueError("bucketed store insert needs assignments")
            assignments = np.asarray(assignments, np.int32)
            if assignments.shape != (len(V),):
                raise ValueError("one bucket assignment per inserted row")
        new_ids = np.arange(
            self._next_id, self._next_id + len(V), dtype=np.int32
        )
        self._next_id += len(V)
        pos = 0  # chunked copies: bulk-load cost is slice assignments, not rows
        while pos < len(V):
            if self._head_n == self.head_capacity:
                self.flush()
            j0, take = self._head_n, min(
                self.head_capacity - self._head_n, len(V) - pos
            )
            self._head_data[j0 : j0 + take] = V[pos : pos + take]
            self._head_ids[j0 : j0 + take] = new_ids[pos : pos + take]
            if assignments is not None:
                self._head_assign[j0 : j0 + take] = assignments[pos : pos + take]
            self._id_loc.update(
                (i, ("h", j0 + off))
                for off, i in enumerate(new_ids[pos : pos + take].tolist())
            )
            self._head_n += take
            pos += take
        self._sum += V.astype(np.float64).sum(axis=0)
        self._sumsq += (V.astype(np.float64) ** 2).sum(axis=0)
        self._n_live += len(V)
        self._mutations_since_meta += len(V)
        self._maybe_refresh_meta()
        self._oplog_record(
            (
                "insert", V.copy(),
                None if assignments is None else assignments.copy(),
                new_ids.copy(),
            ),
            len(V),
        )
        self._bump()  # head-only: sealed tiles untouched (unless flush ran)
        self._obs_mutation("insert", len(V))
        return new_ids

    def delete(self, ids) -> int:
        """Tombstone rows by id; returns how many were live.  Sealed slots
        are poisoned to ``PAD_VALUE`` and their free-bitmap bit set.

        Batched: the id array is resolved to (partition, column) coordinates
        up front, then every slot is poisoned in one fancy-indexed pass and
        the running moments are updated with one reduction — a 10k-id delete
        costs a handful of NumPy calls, not 10k per-row assignments."""
        sealed_p, sealed_c, head_j = [], [], []
        for i in np.atleast_1d(np.asarray(ids, np.int64)):
            loc = self._id_loc.pop(int(i), None)  # also dedups repeated ids
            if loc is None:
                continue
            if loc[0] == "s":
                sealed_p.append(loc[1])
                sealed_c.append(loc[2])
            else:
                head_j.append(loc[1])
        removed = len(sealed_p) + len(head_j)
        if not removed:
            return 0
        if sealed_p:
            ps = np.asarray(sealed_p, np.int64)
            cs = np.asarray(sealed_c, np.int64)
            vecs = self._data[ps, :, cs].astype(np.float64)  # (m, D)
            self._sum -= vecs.sum(axis=0)
            self._sumsq -= (vecs**2).sum(axis=0)
            self._data[ps, :, cs] = PAD_VALUE
            self._ids[ps, cs] = -1
            np.subtract.at(self._counts, ps, 1)
        if head_j:
            js = np.asarray(head_j, np.int64)
            vecs = self._head_data[js].astype(np.float64)
            self._sum -= vecs.sum(axis=0)
            self._sumsq -= (vecs**2).sum(axis=0)
            self._head_data[js] = PAD_VALUE
            self._head_ids[js] = -1
        self._n_live -= removed
        self._mutations_since_meta += removed
        self._maybe_refresh_meta()
        self._oplog_record(
            ("delete", np.atleast_1d(np.asarray(ids, np.int64)).copy()),
            removed,
        )
        self._bump(tiles=bool(sealed_p))
        self._obs_mutation("delete", removed)
        return removed

    def flush(self) -> None:
        """Drain live write-head rows into free sealed slots (reusing the
        free-slot bitmap; bucket-local for bucketed stores).  Falls back to a
        full ``repack()`` when free slots run out."""
        rows = np.nonzero(self._head_ids >= 0)[0]
        if len(rows) == 0:
            self._reset_head()  # only tombstoned head rows, if any: a no-op
            return
        placements = self._plan_free_slot_fill(rows)
        if placements is None:
            self.repack()
            return
        for j, (p, c) in zip(rows, placements):
            i = int(self._head_ids[j])
            self._data[p, :, c] = self._head_data[j]
            self._ids[p, c] = i
            self._counts[p] += 1
            self._id_loc[i] = ("s", p, int(c))
        self._reset_head()
        self._bump(tiles=True)
        self._obs_mutation("flush", len(rows))

    def _plan_free_slot_fill(self, rows) -> Optional[list]:
        """(p, c) free slot per head row, or None if any row has no slot.
        Free slots are enumerated once per bucket, not once per row."""
        free = self._ids < 0  # the free-slot bitmap
        if self.num_buckets is None:
            free_p, free_c = np.nonzero(free)
            if len(free_p) < len(rows):
                return None
            return list(zip(free_p[: len(rows)], free_c[: len(rows)]))
        placements: dict[int, tuple] = {}
        for b in np.unique(self._head_assign[rows]):
            mine = rows[self._head_assign[rows] == b]
            free_p, free_c = np.nonzero(free & (self._part_bucket == b)[:, None])
            if len(free_p) < len(mine):
                return None
            for j, p, c in zip(mine, free_p, free_c):
                placements[int(j)] = (p, c)
        return [placements[int(j)] for j in rows]

    def _reset_head(self):
        self._head_data[:] = PAD_VALUE
        self._head_ids[:] = -1
        self._head_assign[:] = -1
        self._head_n = 0

    def repack(self) -> None:
        """Drain tombstones and the write-head back into minimal lane-aligned
        tiles (bucket-contiguous for IVF), then refresh pruner metadata."""
        C = self.capacity
        live = self._ids >= 0
        hmask = self._head_ids >= 0
        all_ids = np.concatenate([self._ids[live], self._head_ids[hmask]])
        all_vecs = np.concatenate(
            [np.swapaxes(self._data, 1, 2)[live], self._head_data[hmask]]
        )
        all_bucket = np.concatenate([
            np.repeat(self._part_bucket, C).reshape(self._ids.shape)[live],
            self._head_assign[hmask].astype(np.int64),
        ])
        order = np.argsort(all_ids, kind="stable")  # deterministic layout
        all_ids, all_vecs, all_bucket = (
            all_ids[order], all_vecs[order], all_bucket[order],
        )

        if self.num_buckets is None:
            buckets = [-1]
            groups = [np.arange(len(all_ids))]
        else:
            buckets = list(range(self.num_buckets))
            groups = [np.nonzero(all_bucket == b)[0] for b in buckets]
        self._data, self._ids, self._counts = _pack_groups(
            all_vecs, groups, C, row_ids=all_ids
        )
        nparts = [-(-len(g) // C) for g in groups]
        if sum(nparts) == 0:  # nothing survived: the all-pad placeholder tile
            self._part_bucket = np.asarray([-1], dtype=np.int64)
        else:
            self._part_bucket = np.repeat(buckets, nparts).astype(np.int64)
        self._id_loc = self._build_id_loc()
        self._reset_head()
        self._refresh_meta()
        self._bump(tiles=True)
        self._obs_mutation("repack", len(all_ids))

    def replace_live_vectors(self, X: np.ndarray) -> None:
        """Overwrite every live sealed vector, row ``r`` of ``X`` replacing
        the vector with the ``r``-th smallest id (the ``pdx_to_nary``
        order).  Ids, bucket assignments, and tile geometry are untouched —
        this is the store-level primitive for re-projecting a collection in
        place (e.g. recalibrating BSA's PCA on compact, where the stored
        coordinates change but identity and bucket structure do not).
        Requires a drained write-head (call after ``flush``/``repack``)."""
        if self.head_count:
            raise ValueError(
                "replace_live_vectors needs a drained write-head; "
                "flush() or repack() first"
            )
        X = np.asarray(X, np.float32)
        ps, cs = np.nonzero(self._ids >= 0)
        if len(ps) != len(X):
            raise ValueError(
                f"{len(X)} replacement rows for {len(ps)} live vectors"
            )
        order = np.argsort(self._ids[ps, cs], kind="stable")
        self._data[ps[order], :, cs[order]] = X
        self._sum = X.astype(np.float64).sum(axis=0)
        self._sumsq = (X.astype(np.float64) ** 2).sum(axis=0)
        self._refresh_meta()
        self._bump(tiles=True)

    # ------------------------------------------------- incremental metadata
    def _maybe_refresh_meta(self):
        if self._mutations_since_meta > self.meta_staleness * max(
            self._n_live, 1
        ):
            self._refresh_meta()

    def _refresh_meta(self):
        """Snapshot dim_means/dim_vars (BOND / BSA block metadata) from the
        running moments — O(D), independent of collection size."""
        n = max(self._n_live, 1)
        mean = self._sum / n
        self._dim_means = mean.astype(np.float32)
        self._dim_vars = np.maximum(self._sumsq / n - mean**2, 0.0).astype(
            np.float32
        )
        self._mutations_since_meta = 0

    # ------------------------------------------- background maintenance
    @property
    def fragmentation(self) -> float:
        """Fraction of sealed slots that are pad/tombstone holes — the
        maintenance thread's repack trigger."""
        P, _, C = self._data.shape
        return 1.0 - float(self._counts.sum()) / float(P * C)

    def clone(self) -> "MutablePDXStore":
        """Deep, independent copy of all host-side state (device cache
        excluded — the clone re-uploads lazily on first read).  The serving
        tier's maintenance thread clones under the store lock, repacks the
        clone unlocked off the serving path, and swaps it back in with
        ``adopt``."""
        other = MutablePDXStore.__new__(MutablePDXStore)
        other._data = self._data.copy()
        other._ids = self._ids.copy()
        other._counts = self._counts.copy()
        other._dim_means = self._dim_means.copy()
        other._dim_vars = self._dim_vars.copy()
        other.meta_staleness = self.meta_staleness
        other.version = self.version
        other.tiles_version = self.tiles_version
        other.head_capacity = self.head_capacity
        other._head_data = self._head_data.copy()
        other._head_ids = self._head_ids.copy()
        other._head_assign = self._head_assign.copy()
        other._head_n = self._head_n
        other.num_buckets = self.num_buckets
        other._part_bucket = self._part_bucket.copy()
        other._id_loc = dict(self._id_loc)
        other._next_id = self._next_id
        other._sum = self._sum.copy()
        other._sumsq = self._sumsq.copy()
        other._n_live = self._n_live
        other._mutations_since_meta = self._mutations_since_meta
        other._dev = None
        other._dev_version = -1
        other._oplog = None  # clones never inherit an active recording
        other._oplog_limit = self._oplog_limit
        return other

    def adopt(self, other: "MutablePDXStore", *, expect_version: int) -> bool:
        """Version-fenced swap: take ``other``'s state iff this store is
        still at ``expect_version`` (i.e. no mutation landed since ``other``
        was cloned from it).  Returns False — and changes nothing — when the
        fence fails; the caller just discards the stale clone and re-clones
        later.  On success the device cache is dropped (the adopted tiles
        re-upload lazily) and both versions bump past every prior value, so
        every version-keyed cache (executors, placements, mirrors)
        invalidates."""
        if self.version != expect_version:
            return False
        for attr in (
            "_data", "_ids", "_counts", "_dim_means", "_dim_vars",
            "_head_data", "_head_ids", "_head_assign", "_head_n",
            "_part_bucket", "_id_loc", "_next_id",
            "_sum", "_sumsq", "_n_live", "_mutations_since_meta",
        ):
            setattr(self, attr, getattr(other, attr))
        self._dev = None
        self._dev_version = -1
        self._bump(tiles=True)
        self._obs_mutation("adopt", self._n_live)
        return True
