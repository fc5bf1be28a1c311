"""Pallas TPU kernels for PDX dimension-major distance scans.

TPU adaptation of the paper's Algorithm 1: the partition tile ``(D, V)`` puts
vectors on the 128-wide lane axis and dimensions on sublanes, so the running
``distances`` array is one (or a few) vector registers / a VMEM accumulator —
exactly the paper's "distances array fits into the available SIMD registers",
scaled to TPU widths.  There is no horizontal reduction and no dependency
between lanes (paper Figure 3).

Kernels:
  * ``pdx_distance_pallas``  — plain distance scan (L2/L1/IP).
  * ``pdx_prune_scan_pallas`` — fused PDXearch step: distance accumulation +
    ADSampling hypothesis test per dimension tile, with whole-tile compute
    skip once every lane is pruned (the PRUNE phase at tile granularity —
    VPU work is skipped; the HBM→VMEM fetch of later tiles is the remaining
    cost, hoistable with manual DMA; design notes live in the
    ``repro.kernels`` package docstring).
  * ``pdx_prune_scan_multi_pallas`` — the *megakernel*: one grid over
    (partition, d-tile) covering the whole store, quantized (bf16/int8)
    operands dequantized in-register into an f32 VMEM accumulator, the
    keep-mask seeded from ``ids >= 0`` so PAD lanes can never surface.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _backend

__all__ = [
    "pdx_distance_pallas",
    "pdx_prune_scan_pallas",
    "pdx_prune_scan_multi_pallas",
    "pdx_prune_scan_multi_prefetch_pallas",
]


# --------------------------------------------------------------------------
# Plain PDX distance scan.
# --------------------------------------------------------------------------
def _pdx_dist_kernel(q_ref, x_ref, o_ref, *, metric: str):
    i = pl.program_id(1)  # dimension-tile index (innermost => accumulation)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (dt, vt)
    q = q_ref[...].astype(jnp.float32)  # (dt, 1)
    if metric == "l2":
        d = x - q
        o_ref[...] += jnp.sum(d * d, axis=0, keepdims=True)
    elif metric == "l1":
        o_ref[...] += jnp.sum(jnp.abs(x - q), axis=0, keepdims=True)
    else:  # ip (negated)
        o_ref[...] += -jnp.sum(x * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("metric", "d_tile", "v_tile"))
def pdx_distance_pallas(
    T: jax.Array,
    q: jax.Array,
    metric: str = "l2",
    d_tile: int = 256,
    v_tile: int = 1024,
) -> jax.Array:
    """(D, V), (D,) -> (V,) float32. Inputs f32 or bf16."""
    D, V = T.shape
    d_tile = min(d_tile, D)
    v_tile = min(v_tile, V)
    nd = pl.cdiv(D, d_tile)
    nv = pl.cdiv(V, v_tile)
    q2 = q.reshape(D, 1)
    grid = (nv, nd)  # d innermost: each out block accumulates over all d-tiles
    out = pl.pallas_call(
        functools.partial(_pdx_dist_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d_tile, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((d_tile, v_tile), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, v_tile), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, V), jnp.float32),
        interpret=_backend.interpret_mode(),
    )(q2, T)
    return out[0]


# --------------------------------------------------------------------------
# Fused PDXearch + ADSampling partition scan.
# --------------------------------------------------------------------------
def _prune_scan_kernel(
    q_ref, x_ref, ids_ref, thr_ref, o_ref, alive_ref,
    *, dim: int, d_tile: int, eps0: float,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        # PAD lanes (ids < 0) start dead: they can never surface as survivors
        alive_ref[...] = (ids_ref[...] >= 0).astype(alive_ref.dtype)

    alive = alive_ref[...]
    any_alive = jnp.sum(alive) > 0.0

    # PRUNE at tile granularity: once every lane in this partition is pruned
    # the remaining dimension tiles contribute no VPU work at all.
    @pl.when(any_alive)
    def _compute():
        x = x_ref[...].astype(jnp.float32)
        q = q_ref[...].astype(jnp.float32)
        d = x - q
        contrib = jnp.sum(d * d, axis=0, keepdims=True)
        acc = o_ref[...] + contrib * alive_ref[...]
        o_ref[...] = acc
        # ADSampling hypothesis test at d = (i+1)*d_tile dims seen (clipped).
        d_seen = jnp.minimum((i + 1) * d_tile, dim).astype(jnp.float32)
        bound = thr_ref[0, 0] * (1.0 + eps0 / jnp.sqrt(d_seen)) ** 2
        keep = (acc * (dim / d_seen) <= bound).astype(jnp.float32)
        alive_ref[...] = alive_ref[...] * keep


@functools.partial(
    jax.jit, static_argnames=("eps0", "d_tile", "v_tile", "logical_dim")
)
def pdx_prune_scan_pallas(
    T: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    ids: jax.Array,
    eps0: float = 2.1,
    d_tile: int = 64,
    v_tile: int = 1024,
    logical_dim: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused distance+prune over one partition.

    (D, V), (D,), scalar-thr, (V,)-ids -> (dists (V,) f32, alive (V,) f32
    mask).  L2 metric (ADSampling's domain).  Lanes whose ``ids`` entry is
    negative (PAD columns) start dead.  ``logical_dim`` is the un-padded D
    used by the hypothesis test's dims-seen counter (padded dims contribute
    zero distance but must not inflate the estimator's sample count).
    """
    D, V = T.shape
    d_tile = min(d_tile, D)
    v_tile = min(v_tile, V)
    nd = pl.cdiv(D, d_tile)
    dim_for_test = logical_dim if logical_dim is not None else D
    q2 = q.reshape(D, 1)
    ids2 = ids.reshape(1, V)
    thr2 = jnp.asarray(thr, jnp.float32).reshape(1, 1)
    grid = (nd,)
    dists, alive = pl.pallas_call(
        functools.partial(
            _prune_scan_kernel, dim=dim_for_test, d_tile=d_tile, eps0=eps0
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((d_tile, V), lambda i: (i, 0)),
            pl.BlockSpec((1, V), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, V), lambda i: (0, 0)),
            pl.BlockSpec((1, V), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, V), jnp.float32),
            jax.ShapeDtypeStruct((1, V), jnp.float32),
        ],
        interpret=_backend.interpret_mode(),
    )(q2, T, ids2, thr2)
    return dists[0], alive[0]


# --------------------------------------------------------------------------
# Multi-partition megakernel: the whole store in ONE grid, quantized
# operands dequantized in-register.
# --------------------------------------------------------------------------
def _prune_scan_multi_kernel(
    q_ref, x_ref, ids_ref, thr_ref, scale_ref, offset_ref, o_ref, alive_ref,
    *, dim: int, d_tile: int, eps0: float, quantized: bool, packed: bool,
):
    i = pl.program_id(1)  # d-tile index (innermost => accumulation)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        alive_ref[...] = (ids_ref[...] >= 0).astype(alive_ref.dtype)

    alive = alive_ref[...]
    any_alive = jnp.sum(alive) > 0.0

    # Whole-tile compute skip: a partition whose lanes are all dead pays no
    # VPU work for its remaining dimension tiles.
    @pl.when(any_alive)
    def _compute():
        if packed:
            # int4 in-register unpack: the byte block (dt/2, V) holds the
            # even dim in its low nibble, the odd dim in its high nibble,
            # +8 biased.  Interleave back to (dt, V) quantization levels.
            xi = x_ref[...].astype(jnp.int32)                # (dt/2, V)
            lo = (xi & 0xF) - 8
            hi = (xi >> 4) - 8
            x = jnp.stack([lo, hi], axis=1).reshape(
                2 * xi.shape[0], xi.shape[1]
            ).astype(jnp.float32)
        else:
            x = x_ref[...].astype(jnp.float32)               # (dt, V)
        if quantized:
            # in-register dequantization: the f32 value never touches HBM
            x = x * scale_ref[...] + offset_ref[...]
        q = q_ref[...].astype(jnp.float32)                   # (dt, 1)
        d = x - q
        contrib = jnp.sum(d * d, axis=0, keepdims=True)      # (1, V)
        acc = o_ref[...] + contrib * alive_ref[...]
        o_ref[...] = acc
        d_seen = jnp.minimum((i + 1) * d_tile, dim).astype(jnp.float32)
        bound = thr_ref[0, 0] * (1.0 + eps0 / jnp.sqrt(d_seen)) ** 2
        keep = (acc * (dim / d_seen) <= bound).astype(jnp.float32)
        alive_ref[...] = alive_ref[...] * keep


@functools.partial(
    jax.jit,
    static_argnames=("eps0", "d_tile", "logical_dim", "quantized", "packed"),
)
def pdx_prune_scan_multi_pallas(
    T: jax.Array,
    ids: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    scale: jax.Array,
    offset: jax.Array,
    eps0: float = 2.1,
    d_tile: int = 64,
    logical_dim: int | None = None,
    quantized: bool = False,
    packed: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused distance+prune over EVERY partition of a store in one kernel.

    (P, D, V) tiles (f32/bf16/int8), (P, V) ids, (D,) f32 query, scalar
    threshold, (D,) scale/offset dequant vectors -> (dists (P, V) f32,
    alive (P, V) f32 mask).  Grid is (partition, d-tile); the running
    distances and keep-mask for one partition live in VMEM across its
    d-tiles, so each stored byte is touched exactly once, at mirror width.

    ``packed`` takes an int4 mirror: (P, D/2, V) uint8 bytes unpacked
    in-register (q/scale/offset stay at the logical, even, D; ``d_tile``
    must be even).
    """
    P, Din, V = T.shape
    D = 2 * Din if packed else Din  # logical (padded) dimension count
    d_tile = min(d_tile, D)
    if packed and d_tile % 2:
        raise ValueError(f"packed scan needs an even d_tile, got {d_tile}")
    nd = pl.cdiv(D, d_tile)
    dim_for_test = logical_dim if logical_dim is not None else D
    q2 = q.reshape(D, 1)
    thr2 = jnp.asarray(thr, jnp.float32).reshape(1, 1)
    scale2 = scale.reshape(D, 1)
    offset2 = offset.reshape(D, 1)
    x_block = (pl.squeezed, d_tile // 2 if packed else d_tile, V)
    # (P, V) rows travel as (P, 1, V) with the partition axis squeezed, so
    # each block's last two dims equal the array's (the TPU tiling rule)
    row = pl.BlockSpec((pl.squeezed, 1, V), lambda p, i: (p, 0, 0))
    grid = (P, nd)
    dists, alive = pl.pallas_call(
        functools.partial(
            _prune_scan_multi_kernel, dim=dim_for_test, d_tile=d_tile,
            eps0=eps0, quantized=quantized, packed=packed,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d_tile, 1), lambda p, i: (i, 0)),
            pl.BlockSpec(x_block, lambda p, i: (p, i, 0)),
            row,
            pl.BlockSpec((1, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((d_tile, 1), lambda p, i: (i, 0)),
            pl.BlockSpec((d_tile, 1), lambda p, i: (i, 0)),
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1, V), jnp.float32),
            jax.ShapeDtypeStruct((P, 1, V), jnp.float32),
        ],
        interpret=_backend.interpret_mode(),
    )(q2, T, ids.reshape(P, 1, V), thr2, scale2, offset2)
    return dists.reshape(P, V), alive.reshape(P, V)


# --------------------------------------------------------------------------
# Prefetch-skip megakernel: scalar-prefetched (partition, d-tile) pair
# schedule + in-kernel conditional DMA, so a partition's tiles stop leaving
# HBM at the d-tile where its last lane dies — not just when the previous
# cascade stage killed the whole partition.
# --------------------------------------------------------------------------
def _prune_scan_dskip_kernel(
    order_p_ref, order_t_ref, q_ref, ids_ref, thr_ref, scale_ref,
    offset_ref, x_any, o_ref, alive_ref, str_ref, tile, sem,
    *, dim: int, d_tile: int, eps0: float, quantized: bool, packed: bool,
    row_block: int,
):
    g = pl.program_id(0)
    p = order_p_ref[g]
    t = order_t_ref[g]
    real = p >= 0

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        str_ref[...] = jnp.zeros_like(str_ref)
        # tail slots (p < 0) start dead wholesale; real slots seed the
        # keep-mask from the previous stage's ids (PAD/dead lanes < 0)
        alive_ref[...] = jnp.where(
            real, (ids_ref[...] >= 0).astype(alive_ref.dtype), 0.0
        )

    any_alive = jnp.sum(alive_ref[...]) > 0.0

    # The HBM->VMEM fetch itself is conditional: once every lane of this
    # partition is pruned, tiles t+1..T are never DMA'd.
    @pl.when(any_alive)
    def _fetch_and_compute():
        dma = pltpu.make_async_copy(
            x_any.at[p, pl.ds(t * row_block, row_block), :], tile, sem
        )
        dma.start()
        dma.wait()
        if packed:
            xi = tile[...].astype(jnp.int32)                 # (dt/2, V)
            lo = (xi & 0xF) - 8
            hi = (xi >> 4) - 8
            x = jnp.stack([lo, hi], axis=1).reshape(
                2 * xi.shape[0], xi.shape[1]
            ).astype(jnp.float32)
        else:
            x = tile[...].astype(jnp.float32)                # (dt, V)
        if quantized:
            x = x * scale_ref[...] + offset_ref[...]
        q = q_ref[...].astype(jnp.float32)                   # (dt, 1)
        d = x - q
        contrib = jnp.sum(d * d, axis=0, keepdims=True)      # (1, V)
        acc = o_ref[...] + contrib * alive_ref[...]
        o_ref[...] = acc
        str_ref[...] += 1.0
        d_seen = jnp.minimum((t + 1) * d_tile, dim).astype(jnp.float32)
        bound = thr_ref[0, 0] * (1.0 + eps0 / jnp.sqrt(d_seen)) ** 2
        keep = (acc * (dim / d_seen) <= bound).astype(jnp.float32)
        alive_ref[...] = alive_ref[...] * keep


@functools.partial(
    jax.jit,
    static_argnames=("eps0", "d_tile", "logical_dim", "quantized", "packed"),
)
def pdx_prune_scan_multi_prefetch_pallas(
    T: jax.Array,
    ids: jax.Array,
    q: jax.Array,
    thr: jax.Array,
    scale: jax.Array,
    offset: jax.Array,
    order_p: jax.Array,
    order_t: jax.Array,
    eps0: float = 2.1,
    d_tile: int = 64,
    logical_dim: int | None = None,
    quantized: bool = False,
    packed: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``pdx_prune_scan_multi_pallas`` with a ``PrefetchScalarGridSpec``-driven
    *(partition, d-tile)* pair schedule and d-tile-granular traffic skip.

    ``order_p``/``order_t`` are (P*nd,) int32 arrays enumerating the scan as
    flat pairs, partition-major: slot ``s = g // nd`` runs partition
    ``order_p[s*nd]`` (its leading entries are the partitions still alive
    after the previous cascade stage; tail slots carry ``order_p = -1`` and
    do nothing), and ``order_t[g] = g % nd`` walks that partition's d-tiles.
    The tile array lives in ANY memory space and each (p, t) tile is fetched
    with an explicit conditional DMA: an entry-dead partition fetches
    nothing (partition-granular skip, as before), and a partition whose last
    lane dies at tile t never fetches tiles t+1..T (the new d-tile-granular
    skip — previously one surviving lane streamed the whole partition).

    Returns SLOT-ordered ``(dists, alive, streamed)``; ``streamed[s, :]``
    broadcasts the number of d-tiles slot ``s`` actually fetched, which the
    caller meters as realized HBM traffic.  The caller scatters slots back
    to partition order (dead partitions report dist 0 / alive 0 /
    streamed 0).
    """
    P, Din, V = T.shape
    D = 2 * Din if packed else Din
    d_tile = min(d_tile, D)
    if packed and d_tile % 2:
        raise ValueError(f"packed scan needs an even d_tile, got {d_tile}")
    nd = pl.cdiv(D, d_tile)
    dim_for_test = logical_dim if logical_dim is not None else D
    row_block = d_tile // 2 if packed else d_tile
    q2 = q.reshape(D, 1)
    thr2 = jnp.asarray(thr, jnp.float32).reshape(1, 1)
    scale2 = scale.reshape(D, 1)
    offset2 = offset.reshape(D, 1)
    # (P, V) rows travel as (P, 1, V), partition axis squeezed (TPU tiling)
    slot_row = pl.BlockSpec(
        (pl.squeezed, 1, V), lambda g, op, ot: (g // nd, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(P * nd,),
        in_specs=[
            pl.BlockSpec((d_tile, 1), lambda g, op, ot: (ot[g], 0)),
            pl.BlockSpec(
                (pl.squeezed, 1, V),
                lambda g, op, ot: (jnp.maximum(op[g], 0), 0, 0),
            ),
            pl.BlockSpec((1, 1), lambda g, op, ot: (0, 0)),
            pl.BlockSpec((d_tile, 1), lambda g, op, ot: (ot[g], 0)),
            pl.BlockSpec((d_tile, 1), lambda g, op, ot: (ot[g], 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # tiles: manual DMA only
        ],
        out_specs=[slot_row, slot_row, slot_row],
        scratch_shapes=[
            pltpu.VMEM((row_block, V), T.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kernel = functools.partial(
        _prune_scan_dskip_kernel,
        dim=dim_for_test, d_tile=d_tile, eps0=eps0,
        quantized=quantized, packed=packed, row_block=row_block,
    )
    dists, alive, streamed = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((P, 1, V), jnp.float32)] * 3,
        interpret=_backend.interpret_mode(),
    )(
        order_p.astype(jnp.int32), order_t.astype(jnp.int32),
        q2, ids.reshape(P, 1, V), thr2, scale2, offset2, T,
    )
    return dists.reshape(P, V), alive.reshape(P, V), streamed.reshape(P, V)
