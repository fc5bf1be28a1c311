"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` mirrors one kernel's contract exactly (shapes, dtypes,
accumulation order up to float-reassociation).  Kernel tests sweep shapes and
dtypes and assert allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "pdx_distance_ref",
    "nary_distance_ref",
    "batched_distance_ref",
    "batched_distance_quant_ref",
    "pdx_prune_scan_ref",
    "pdx_prune_scan_multi_ref",
    "pdx_prune_scan_multi_dskip_ref",
    "dequantize_ref",
    "unpack_int4_ref",
]


def unpack_int4_ref(
    T: jax.Array, axis: int = 0, dim: int | None = None
) -> jax.Array:
    """Packed int4 bytes -> int8 quantization levels along ``axis`` (low
    nibble = even dim, +8 bias — the ``core.layout`` packing), cut to
    ``dim`` when given.  Unpacks in 8 bits: an int32 intermediate would
    be four times the unpacked mirror."""
    u = T.astype(jnp.uint8)
    lo = (u & 0xF).astype(jnp.int8) - 8
    hi = (u >> 4).astype(jnp.int8) - 8
    shape = list(T.shape)
    shape[axis] *= 2
    full = jnp.stack([lo, hi], axis=axis + 1).reshape(shape)
    if dim is not None and dim != shape[axis]:
        full = jax.lax.slice_in_dim(full, 0, dim, axis=axis)
    return full


def dequantize_ref(
    T: jax.Array,
    scale: jax.Array | None,
    offset: jax.Array | None,
    dim_axis: int = 0,
    packed: bool = False,
    dim: int | None = None,
) -> jax.Array:
    """Mirror-dtype tile -> f32, applying the per-dimension affine
    dequantization when scale/offset are given (int8/int4 mirrors; bf16/f32
    pass None and just upcast).  ``dim_axis`` is the axis holding the D
    dimension values (0 for a (D, V) tile, 1 for (P, D, V) stacks).
    ``packed`` unpacks an int4 two-per-byte tile first (low nibble = even
    dim, +8 bias — the ``core.layout`` packing), slicing the doubled axis
    back to logical ``dim`` when given."""
    if packed:
        T = unpack_int4_ref(T, dim_axis, dim)
    T32 = T.astype(jnp.float32)
    if scale is None:
        return T32
    shape = [1] * T32.ndim
    shape[dim_axis] = -1
    return T32 * scale.reshape(shape) + offset.reshape(shape)


def pdx_distance_ref(T: jax.Array, q: jax.Array, metric: str = "l2") -> jax.Array:
    """(D, V), (D,) -> (V,) float32 accumulation regardless of input dtype."""
    T32 = T.astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    if metric == "l2":
        d = T32 - q32[:, None]
        return jnp.sum(d * d, axis=0)
    if metric == "l1":
        return jnp.sum(jnp.abs(T32 - q32[:, None]), axis=0)
    return -jnp.sum(T32 * q32[:, None], axis=0)


def nary_distance_ref(X: jax.Array, q: jax.Array, metric: str = "l2") -> jax.Array:
    """(N, D), (D,) -> (N,)."""
    X32 = X.astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    if metric == "l2":
        d = X32 - q32[None, :]
        return jnp.sum(d * d, axis=1)
    if metric == "l1":
        return jnp.sum(jnp.abs(X32 - q32[None, :]), axis=1)
    return -jnp.sum(X32 * q32[None, :], axis=1)


def batched_distance_ref(T: jax.Array, Q: jax.Array, metric: str = "l2") -> jax.Array:
    """(D, V), (B, D) -> (B, V); l2 or ip (matmul family)."""
    T32 = T.astype(jnp.float32)
    Q32 = Q.astype(jnp.float32)
    cross = jnp.matmul(Q32, T32, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return -cross
    qn = jnp.sum(Q32 * Q32, axis=1, keepdims=True)
    xn = jnp.sum(T32 * T32, axis=0, keepdims=True)
    return qn - 2.0 * cross + xn


def batched_distance_quant_ref(
    T: jax.Array,
    Q: jax.Array,
    scale: jax.Array | None = None,
    offset: jax.Array | None = None,
    metric: str = "l2",
) -> jax.Array:
    """Oracle for the quantized batched kernel: dequantize, then the exact
    ``batched_distance_ref`` arithmetic."""
    return batched_distance_ref(dequantize_ref(T, scale, offset), Q, metric)


def pdx_prune_scan_ref(
    T: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    *,
    d_tile: int,
    eps0: float,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused PDXearch-ADSampling partition kernel.

    Walks dimension tiles of size ``d_tile``; after each tile evaluates the
    ADSampling hypothesis test and freezes pruned vectors' accumulators
    (paper: once pruned, a vector's remaining dims are never visited).
    Returns (dists (V,), alive (V,) f32 mask); pruned vectors report their
    partial distance at pruning time.
    """
    D, V = T.shape
    T32 = T.astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    acc = jnp.zeros((V,), jnp.float32)
    alive = jnp.ones((V,), jnp.float32)
    d_seen = 0
    while d_seen < D:
        hi = min(d_seen + d_tile, D)
        blk = T32[d_seen:hi] - q32[d_seen:hi, None]
        contrib = jnp.sum(blk * blk, axis=0)
        acc = acc + contrib * alive  # frozen lanes stay frozen
        d_seen = hi
        d = jnp.float32(d_seen)
        bound = thr * (1.0 + eps0 / jnp.sqrt(d)) ** 2
        keep = acc * (D / d) <= bound
        alive = alive * keep.astype(jnp.float32)
    return acc, alive


def _dequantize_dims(T, lo, hi, scale, offset, packed):
    """Dims ``[lo, hi)`` of a (P, D, V) mirror stack as f32.  The multi-
    partition oracles dequantize one d-tile at a time, so no f32 copy of the
    whole mirror (the size of the f32 store) ever exists."""
    if packed:
        b0 = lo // 2
        T = unpack_int4_ref(T[:, b0 : (hi + 1) // 2], axis=1)
        T = T[:, lo - 2 * b0 : hi - 2 * b0]
    else:
        T = T[:, lo:hi]
    T32 = T.astype(jnp.float32)
    if scale is None:
        return T32
    return T32 * scale[None, lo:hi, None] + offset[None, lo:hi, None]


def pdx_prune_scan_multi_ref(
    T: jax.Array,
    ids: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    *,
    d_tile: int,
    eps0: float,
    scale: jax.Array | None = None,
    offset: jax.Array | None = None,
    packed: bool = False,
    dim: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the multi-partition megakernel.

    (P, D, V) mirror-dtype tiles, (P, V) ids -> (dists (P, V), alive (P, V)
    f32 mask).  Matches the kernel's contract: lanes with ``ids < 0`` start
    dead (and accumulate nothing), operands dequantize before the L2
    accumulation, the hypothesis test runs once per d-tile.  ``packed``
    takes an int4 mirror, (P, ceil(dim/2), V) uint8 with logical ``dim``.
    """
    P, _, V = T.shape
    D = dim if packed else T.shape[1]
    q32 = q.astype(jnp.float32)
    acc = jnp.zeros((P, V), jnp.float32)
    alive = (ids >= 0).astype(jnp.float32)
    d_seen = 0
    while d_seen < D:
        hi = min(d_seen + d_tile, D)
        x = _dequantize_dims(T, d_seen, hi, scale, offset, packed)
        blk = x - q32[None, d_seen:hi, None]
        contrib = jnp.sum(blk * blk, axis=1)
        acc = acc + contrib * alive
        d_seen = hi
        d = jnp.float32(d_seen)
        bound = thr * (1.0 + eps0 / jnp.sqrt(d)) ** 2
        keep = acc * (D / d) <= bound
        alive = alive * keep.astype(jnp.float32)
    return acc, alive


def pdx_prune_scan_multi_dskip_ref(
    T: jax.Array,
    ids: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    *,
    d_tile: int,
    eps0: float,
    scale: jax.Array | None = None,
    offset: jax.Array | None = None,
    packed: bool = False,
    dim: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the d-tile-granular prefetch-skip megakernel: identical
    dists/alive to ``pdx_prune_scan_multi_ref``, plus a per-partition
    ``streamed`` (P,) count of d-tiles the skipping kernel would actually
    fetch — a tile is streamed iff any of the partition's lanes is alive
    when the tile is reached (the hardware path's conditional DMA)."""
    P, _, V = T.shape
    D = dim if packed else T.shape[1]
    q32 = q.astype(jnp.float32)
    acc = jnp.zeros((P, V), jnp.float32)
    alive = (ids >= 0).astype(jnp.float32)
    streamed = jnp.zeros((P,), jnp.float32)
    d_seen = 0
    while d_seen < D:
        hi = min(d_seen + d_tile, D)
        streamed = streamed + jnp.any(alive > 0, axis=1).astype(jnp.float32)
        x = _dequantize_dims(T, d_seen, hi, scale, offset, packed)
        blk = x - q32[None, d_seen:hi, None]
        contrib = jnp.sum(blk * blk, axis=1)
        acc = acc + contrib * alive
        d_seen = hi
        d = jnp.float32(d_seen)
        bound = thr * (1.0 + eps0 / jnp.sqrt(d)) ** 2
        keep = acc * (D / d) <= bound
        alive = alive * keep.astype(jnp.float32)
    return acc, alive, streamed
