"""Jitted public wrappers for the Pallas kernels: shape padding, dtype policy,
tile-size selection.  Callers use these; the raw kernels stay minimal.

Every executor-facing op takes a static ``use_pallas`` knob: True runs the
Pallas kernel (interpreted on the CPU — the correctness gate), False runs
the pure-jnp oracle body from ``ref`` under the same contract (the XLA
fallback the planner picks via ``SearchSpec.kernel="jnp"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .batched_matmul import (
    batched_distance_pallas,
    batched_distance_quant_pallas,
)
from .nary_scan import nary_distance_pallas
from .pdx_scan import (
    pdx_distance_pallas,
    pdx_prune_scan_multi_pallas,
    pdx_prune_scan_multi_prefetch_pallas,
    pdx_prune_scan_pallas,
)

__all__ = [
    "pdx_distance_op",
    "nary_distance_op",
    "batched_distance_op",
    "batched_distance_quant_op",
    "pdx_prune_scan_op",
    "pdx_prune_scan_multi_op",
    "pdx_prune_scan_multi_prefetch_op",
    "batched_cascade_stage_op",
]

# Padding a packed int4 tile must stay harmless after in-kernel unpacking:
# 0x88 decodes to the (0, 0) level pair, which dequantizes to 0 under the
# zero-padded scale/offset — exactly like the 0 padding of unpacked tiles.
_INT4_PAD_BYTE = 0x88


def _pad_to(
    x: jax.Array, axis: int, mult: int, value: float | int = 0
) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pick(size: int, pref: int, align: int) -> int:
    """Largest aligned tile <= pref covering `size` if small."""
    if size <= pref:
        return max(((size + align - 1) // align) * align, align)
    return pref


@functools.partial(jax.jit, static_argnames=("metric",))
def pdx_distance_op(T: jax.Array, q: jax.Array, metric: str = "l2") -> jax.Array:
    """(D, V), (D,) -> (V,); handles non-aligned shapes by zero-padding
    (zero dims contribute 0 to every metric)."""
    D, V = T.shape
    dt = _pick(D, 256, 8)
    vt = _pick(V, 1024, 128)
    Tp = _pad_to(_pad_to(T, 0, dt), 1, vt)
    qp = _pad_to(q, 0, dt)
    return pdx_distance_pallas(Tp, qp, metric, dt, vt)[:V]


@functools.partial(jax.jit, static_argnames=("metric",))
def nary_distance_op(X: jax.Array, q: jax.Array, metric: str = "l2") -> jax.Array:
    N, D = X.shape
    nt = _pick(N, 256, 8)
    dt = _pick(D, 512, 128)
    Xp = _pad_to(_pad_to(X, 0, nt), 1, dt)
    qp = _pad_to(q, 0, dt)
    return nary_distance_pallas(Xp, qp, metric, nt, dt)[:N]


@functools.partial(jax.jit, static_argnames=("metric",))
def batched_distance_op(T: jax.Array, Q: jax.Array, metric: str = "l2") -> jax.Array:
    D, V = T.shape
    B = Q.shape[0]
    bt = _pick(B, 128, 8)
    dt = _pick(D, 256, 128)
    vt = _pick(V, 512, 128)
    Tp = _pad_to(_pad_to(T, 0, dt), 1, vt)
    Qp = _pad_to(_pad_to(Q, 1, dt), 0, bt)
    return batched_distance_pallas(Tp, Qp, metric, bt, dt, vt)[:B, :V]


@functools.partial(jax.jit, static_argnames=("eps0", "d_tile"))
def pdx_prune_scan_op(
    T: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    ids: jax.Array | None = None,
    eps0: float = 2.1,
    d_tile: int = 64,
) -> tuple[jax.Array, jax.Array]:
    """Fused PDXearch/ADSampling partition scan -> (dists f32, alive bool).

    Zero-pads both axes; the hypothesis test keeps counting in logical
    (un-padded) dimensions.  ``ids`` is the partition's (V,) id row: lanes
    with ``ids < 0`` (PAD columns) start dead and can never surface as
    candidates.  Padded lanes introduced here are masked the same way.
    """
    D, V = T.shape
    vt = _pick(V, 1024, 128)
    dt = min(d_tile, D)
    Tp = _pad_to(_pad_to(T, 0, dt), 1, vt)
    qp = _pad_to(q, 0, dt)
    if ids is None:
        ids = jnp.zeros((V,), jnp.int32)  # all lanes real
    idp = _pad_to(ids, 0, vt, value=-1)
    dists, alive = pdx_prune_scan_pallas(
        Tp, qp, thr, idp, eps0, dt, vt, logical_dim=D
    )
    return dists[:V], alive[:V] != 0.0


def _prep_multi(T, ids, q, scale, offset, d_tile, packed, dim):
    """Shared padding/tiling for the megakernel wrappers.

    Returns (Tp, idp, qp, sp, op, dt, logical_dim, quantized).  For packed
    int4 mirrors the byte axis pads with ``_INT4_PAD_BYTE`` to ``dt/2`` and
    q/scale/offset pad out to the padded *logical* (even) dimension count.
    """
    if packed:
        P, Dp, V = T.shape
        dt = min(d_tile, 2 * Dp)
        dt += dt % 2  # packed bytes hold dim pairs; 2*Dp is even, so safe
        Tp = _pad_to(_pad_to(T, 1, dt // 2, value=_INT4_PAD_BYTE), 2, _pick(V, 1024, 128))
        Dlog = 2 * Tp.shape[1]
        qp = jnp.pad(q, (0, Dlog - dim))
        sp = jnp.pad(scale, (0, Dlog - dim))
        op = jnp.pad(offset, (0, Dlog - dim))
        idp = _pad_to(ids, 1, Tp.shape[2], value=-1)
        return Tp, idp, qp, sp, op, dt, dim, True
    P, D, V = T.shape
    quantized = scale is not None
    vt = _pick(V, 1024, 128)
    dt = min(d_tile, D)
    Tp = _pad_to(_pad_to(T, 1, dt), 2, vt)
    qp = _pad_to(q, 0, dt)
    idp = _pad_to(ids, 1, vt, value=-1)
    if quantized:
        sp = _pad_to(scale, 0, dt)
        op = _pad_to(offset, 0, dt)
    else:
        sp = jnp.ones((Tp.shape[1],), jnp.float32)
        op = jnp.zeros((Tp.shape[1],), jnp.float32)
    return Tp, idp, qp, sp, op, dt, D, quantized


@functools.partial(
    jax.jit, static_argnames=("eps0", "d_tile", "use_pallas", "packed", "dim")
)
def pdx_prune_scan_multi_op(
    T: jax.Array,
    ids: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    scale: jax.Array | None = None,
    offset: jax.Array | None = None,
    eps0: float = 2.1,
    d_tile: int = 64,
    use_pallas: bool = True,
    packed: bool = False,
    dim: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Megakernel wrapper: whole-store fused scan -> ((P, V) dists f32,
    (P, V) alive bool).

    ``T`` is a device mirror at any scan dtype (f32/bf16/int8/int4);
    ``scale``/``offset`` are the (D,) dequant vectors for quantized mirrors
    (None means the operands are plain floats).  ``packed`` marks an int4
    mirror, (P, ceil(dim/2), V) uint8 with logical dimensionality ``dim``
    (q/scale/offset stay length-``dim``).  PAD lanes (``ids < 0``) start
    dead.
    """
    if not use_pallas:
        D = dim if packed else T.shape[1]
        dists, alive = ref.pdx_prune_scan_multi_ref(
            T, ids, q, thr, d_tile=min(d_tile, D), eps0=eps0,
            scale=scale, offset=offset, packed=packed, dim=dim,
        )
        return dists, alive != 0.0
    V = T.shape[2]
    Tp, idp, qp, sp, op, dt, Dlog, quantized = _prep_multi(
        T, ids, q, scale, offset, d_tile, packed, dim
    )
    dists, alive = pdx_prune_scan_multi_pallas(
        Tp, idp, qp, thr, sp, op, eps0, dt,
        logical_dim=Dlog, quantized=quantized, packed=packed,
    )
    return dists[:, :V], alive[:, :V] != 0.0


@functools.partial(
    jax.jit, static_argnames=("eps0", "d_tile", "use_pallas", "packed", "dim")
)
def pdx_prune_scan_multi_prefetch_op(
    T: jax.Array,
    ids: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    scale: jax.Array | None = None,
    offset: jax.Array | None = None,
    eps0: float = 2.1,
    d_tile: int = 64,
    use_pallas: bool = True,
    packed: bool = False,
    dim: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Prefetch-skip megakernel wrapper for the later cascade stages ->
    ``(dists (P, V) f32, alive (P, V) bool, streamed (P,) f32)``.

    Builds the *(partition, d-tile)* pair schedule from ``ids`` itself:
    partitions with any live lane (``ids >= 0``) are listed first,
    partition-major over their d-tiles; tail slots carry partition -1 and
    fetch nothing.  On the Pallas path an entry-dead partition's tiles are
    never DMA'd AND a partition whose last lane dies at d-tile t stops
    fetching at t (see ``pdx_prune_scan_multi_prefetch_pallas``); slot-
    ordered outputs scatter back to partition order (dead partitions report
    dist 0 / alive False / streamed 0).  ``streamed`` counts the d-tiles
    each partition actually fetched — the realized-traffic meter.  The jnp
    twin (``use_pallas=False``) computes identical dists/alive and the same
    streamed model, with no actual traffic skip.
    """
    if not use_pallas:
        D = dim if packed else T.shape[1]
        dists, alive, streamed = ref.pdx_prune_scan_multi_dskip_ref(
            T, ids, q, thr, d_tile=min(d_tile, D), eps0=eps0,
            scale=scale, offset=offset, packed=packed, dim=dim,
        )
        return dists, alive != 0.0, streamed
    P, _, V = T.shape
    Tp, idp, qp, sp, op, dt, Dlog, quantized = _prep_multi(
        T, ids, q, scale, offset, d_tile, packed, dim
    )
    nd = -(-(2 * Tp.shape[1] if packed else Tp.shape[1]) // dt)
    part_alive = jnp.any(idp >= 0, axis=1)
    n_alive = jnp.sum(part_alive)
    perm = jnp.argsort(~part_alive).astype(jnp.int32)  # stable: alive first
    slot_real = jnp.arange(P) < n_alive                # (P,)
    sched_p = jnp.where(slot_real, perm, -1)
    order_p = jnp.repeat(sched_p, nd)                  # (P*nd,) pair schedule
    order_t = jnp.tile(jnp.arange(nd, dtype=jnp.int32), P)
    out_d, out_a, out_s = pdx_prune_scan_multi_prefetch_pallas(
        Tp, idp, qp, thr, sp, op, order_p, order_t, eps0, dt,
        logical_dim=Dlog, quantized=quantized, packed=packed,
    )
    # slot -> partition scatter through the (duplicate-free) permutation;
    # tail slots write zeros into the partitions the schedule skipped
    m = slot_real[:, None]
    dists = jnp.zeros_like(out_d).at[perm].set(jnp.where(m, out_d, 0.0))
    alive = jnp.zeros_like(out_a).at[perm].set(jnp.where(m, out_a, 0.0))
    streamed = jnp.zeros((P,), jnp.float32).at[perm].set(
        jnp.where(slot_real, out_s[:, 0], 0.0)
    )
    return dists[:, :V], alive[:, :V] != 0.0, streamed


@functools.partial(
    jax.jit, static_argnames=("metric", "use_pallas", "packed", "dim")
)
def batched_distance_quant_op(
    T: jax.Array,
    Q: jax.Array,
    scale: jax.Array | None = None,
    offset: jax.Array | None = None,
    metric: str = "l2",
    use_pallas: bool = True,
    packed: bool = False,
    dim: int | None = None,
) -> jax.Array:
    """Quantized-operand MXU batch scan: (D, V) mirror tile + (B, D) f32
    queries -> (B, V) f32 distances, dequantizing in-register.  ``packed``
    takes an int4 tile ((ceil(dim/2), V) uint8): the nibbles unpack to int8
    levels outside the kernel (XLA fuses the unpack into the feed) and the
    existing quantized MXU path runs unchanged."""
    if packed:
        T = ref.unpack_int4_ref(T, 0, dim)
    if not use_pallas:
        return ref.batched_distance_quant_ref(T, Q, scale, offset, metric)
    D, V = T.shape
    B = Q.shape[0]
    quantized = scale is not None
    bt = _pick(B, 128, 8)
    dt = _pick(D, 256, 128)
    vt = _pick(V, 512, 128)
    Tp = _pad_to(_pad_to(T, 0, dt), 1, vt)
    Qp = _pad_to(_pad_to(Q, 1, dt), 0, bt)
    if quantized:
        sp = _pad_to(scale, 0, dt)
        op = _pad_to(offset, 0, dt)
    else:
        sp = jnp.ones((Tp.shape[0],), jnp.float32)
        op = jnp.zeros((Tp.shape[0],), jnp.float32)
    out = batched_distance_quant_pallas(
        Tp, Qp, sp, op, metric, quantized, bt, dt, vt
    )
    return out[:B, :V]


@functools.partial(
    jax.jit, static_argnames=("eps0", "d_tile", "use_pallas", "packed", "dim")
)
def batched_cascade_stage_op(
    T: jax.Array,
    alive: jax.Array,
    Q: jax.Array,
    thr: jax.Array,
    scale: jax.Array | None = None,
    offset: jax.Array | None = None,
    eps0: float = 2.1,
    d_tile: int = 64,
    use_pallas: bool = True,
    packed: bool = False,
    dim: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """MXU-batched cascade stage ladder: (Dp, S) compacted survivor columns
    + (B, D) stage queries -> ((B, S) dists f32, (B, S) alive bool).

    Each d-tile runs through the batched quantized MXU kernel
    (``batched_distance_quant_op``) over the whole query batch at once,
    accumulating per-(query, slot) partial distances with frozen
    accumulators for dead slots; between tiles the ADSampling hypothesis
    test fires exactly as the per-query megakernel's does —
    ``acc * (D / d_seen) <= thr * (1 + eps0 / sqrt(d_seen))**2`` with
    per-query thresholds.  ``alive`` carries the cross-stage survivor
    bitmap in: slots dead on entry accumulate nothing and never revive.
    ``packed`` int4 columns unpack to int8 levels once up front; per-tile
    scale/offset slices ride into the kernel's in-register dequant."""
    if packed:
        T = ref.unpack_int4_ref(T, 0, dim)
    D = T.shape[0]
    quantized = scale is not None
    a = alive.astype(jnp.float32)
    acc = jnp.zeros((Q.shape[0], T.shape[1]), jnp.float32)
    d_seen = 0
    while d_seen < D:
        hi = min(d_seen + d_tile, D)
        sc = scale[d_seen:hi] if quantized else None
        off = offset[d_seen:hi] if quantized else None
        contrib = batched_distance_quant_op(
            T[d_seen:hi], Q[:, d_seen:hi], sc, off, metric="l2",
            use_pallas=use_pallas,
        )
        acc = acc + contrib * a
        d_seen = hi
        d = jnp.float32(d_seen)
        bound = thr[:, None] * (1.0 + eps0 / jnp.sqrt(d)) ** 2
        keep = acc * (D / d) <= bound
        a = a * keep.astype(jnp.float32)
    return acc, a != 0.0
