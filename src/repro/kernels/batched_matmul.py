"""Pallas TPU kernel: batched-query distance via the MXU (beyond-paper).

dist(b, v) = ||q_b||^2 - 2 <q_b, x_v> + ||x_v||^2 over a PDX tile (D, V):
the tile is contraction-major, so the cross term is a straight MXU matmul
with no relayout — the TPU analogue of the paper's observation that the PDX
layout is what the compute unit natively wants.  Norm terms are fused as an
epilogue on the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _backend

__all__ = ["batched_distance_pallas", "batched_distance_quant_pallas"]

# The distance is ||q||^2 - 2 q.x + ||x||^2: at the TPU's default precision
# (one bf16 pass) the cross term's error swamps the neighbour gaps of
# 1536-wide embeddings, even as a pre-filter before an exact re-rank.
_EXACT = jax.lax.Precision.HIGHEST


def _bmm_kernel(q_ref, x_ref, qn_ref, xn_ref, o_ref, *, nd: int, metric: str):
    i = pl.program_id(2)  # K (dimension) tile, innermost

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)  # (bt, dt)
    x = x_ref[...].astype(jnp.float32)  # (dt, vt)
    cross = jax.lax.dot_general(
        q, x, (((1,), (0,)), ((), ())),
        precision=_EXACT,
        preferred_element_type=jnp.float32,
    )
    if metric == "ip":
        o_ref[...] += -cross
    else:
        o_ref[...] += -2.0 * cross

        @pl.when(i == nd - 1)
        def _epilogue():
            o_ref[...] += qn_ref[...] + xn_ref[...]


@functools.partial(
    jax.jit, static_argnames=("metric", "b_tile", "d_tile", "v_tile")
)
def batched_distance_pallas(
    T: jax.Array,
    Q: jax.Array,
    metric: str = "l2",
    b_tile: int = 128,
    d_tile: int = 256,
    v_tile: int = 512,
) -> jax.Array:
    """(D, V), (B, D) -> (B, V) float32 distances (l2) or neg-IP."""
    D, V = T.shape
    B = Q.shape[0]
    b_tile = min(b_tile, B)
    d_tile = min(d_tile, D)
    v_tile = min(v_tile, V)
    nb, nv, nd = pl.cdiv(B, b_tile), pl.cdiv(V, v_tile), pl.cdiv(D, d_tile)
    qn = jnp.sum(
        Q.astype(jnp.float32) * Q.astype(jnp.float32), axis=1, keepdims=True
    )  # (B, 1)
    xn = jnp.sum(
        T.astype(jnp.float32) * T.astype(jnp.float32), axis=0, keepdims=True
    )  # (1, V)
    out = pl.pallas_call(
        functools.partial(_bmm_kernel, nd=nd, metric=metric),
        grid=(nb, nv, nd),
        in_specs=[
            pl.BlockSpec((b_tile, d_tile), lambda b, v, i: (b, i)),
            pl.BlockSpec((d_tile, v_tile), lambda b, v, i: (i, v)),
            pl.BlockSpec((b_tile, 1), lambda b, v, i: (b, 0)),
            pl.BlockSpec((1, v_tile), lambda b, v, i: (0, v)),
        ],
        out_specs=pl.BlockSpec((b_tile, v_tile), lambda b, v, i: (b, v)),
        out_shape=jax.ShapeDtypeStruct((B, V), jnp.float32),
        interpret=_backend.interpret_mode(),
    )(Q, T, qn, xn)
    return out


# --------------------------------------------------------------------------
# Quantized-operand variant: the tile streams at mirror width (bf16/int8),
# dequantizes in-register, and accumulates both the MXU cross term and the
# tile's own squared norm per K step (so no f32 norm array over the store
# needs to exist anywhere — each stored byte is touched exactly once).
# --------------------------------------------------------------------------
def _bmm_quant_kernel(
    q_ref, x_ref, qn_ref, scale_ref, offset_ref, o_ref,
    *, nd: int, metric: str, quantized: bool,
):
    i = pl.program_id(2)  # K (dimension) tile, innermost

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (dt, vt)
    if quantized:
        x = x * scale_ref[...] + offset_ref[...]
    q = q_ref[...].astype(jnp.float32)  # (bt, dt)
    cross = jax.lax.dot_general(
        q, x, (((1,), (0,)), ((), ())),
        precision=_EXACT,
        preferred_element_type=jnp.float32,
    )
    if metric == "ip":
        o_ref[...] += -cross
    else:
        xn = jnp.sum(x * x, axis=0, keepdims=True)  # (1, vt) this K tile
        o_ref[...] += -2.0 * cross + xn

        @pl.when(i == nd - 1)
        def _epilogue():
            o_ref[...] += qn_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("metric", "quantized", "b_tile", "d_tile", "v_tile"),
)
def batched_distance_quant_pallas(
    T: jax.Array,
    Q: jax.Array,
    scale: jax.Array,
    offset: jax.Array,
    metric: str = "l2",
    quantized: bool = False,
    b_tile: int = 128,
    d_tile: int = 256,
    v_tile: int = 512,
) -> jax.Array:
    """(D, V) bf16/int8 tile + (D,) dequant vectors, (B, D) f32 -> (B, V)."""
    D, V = T.shape
    B = Q.shape[0]
    b_tile = min(b_tile, B)
    d_tile = min(d_tile, D)
    v_tile = min(v_tile, V)
    nb, nv, nd = pl.cdiv(B, b_tile), pl.cdiv(V, v_tile), pl.cdiv(D, d_tile)
    Q32 = Q.astype(jnp.float32)
    qn = jnp.sum(Q32 * Q32, axis=1, keepdims=True)  # (B, 1)
    out = pl.pallas_call(
        functools.partial(
            _bmm_quant_kernel, nd=nd, metric=metric, quantized=quantized
        ),
        grid=(nb, nv, nd),
        in_specs=[
            pl.BlockSpec((b_tile, d_tile), lambda b, v, i: (b, i)),
            pl.BlockSpec((d_tile, v_tile), lambda b, v, i: (i, v)),
            pl.BlockSpec((b_tile, 1), lambda b, v, i: (b, 0)),
            pl.BlockSpec((d_tile, 1), lambda b, v, i: (i, 0)),
            pl.BlockSpec((d_tile, 1), lambda b, v, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((b_tile, v_tile), lambda b, v, i: (b, v)),
        out_shape=jax.ShapeDtypeStruct((B, V), jnp.float32),
        interpret=_backend.interpret_mode(),
    )(Q32, T, qn, scale.reshape(D, 1), offset.reshape(D, 1))
    return out
