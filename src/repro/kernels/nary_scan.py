"""Pallas TPU kernel for the horizontal (N-ary) distance scan — the paper's
baseline layout.  Each row tile reduces along the dimension axis, which is the
reduction the paper shows to be lane-inefficient at low D (Figure 3): on TPU
the per-row reduce crosses lanes, whereas the PDX kernel reduces across
sublanes and keeps lanes independent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _backend

__all__ = ["nary_distance_pallas"]


def _nary_kernel(q_ref, x_ref, o_ref, *, metric: str):
    i = pl.program_id(1)  # dim-tile index, innermost

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (nt, dt)
    q = q_ref[...].astype(jnp.float32)  # (1, dt)
    if metric == "l2":
        d = x - q
        o_ref[...] += jnp.sum(d * d, axis=1, keepdims=True)
    elif metric == "l1":
        o_ref[...] += jnp.sum(jnp.abs(x - q), axis=1, keepdims=True)
    else:
        o_ref[...] += -jnp.sum(x * q, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("metric", "n_tile", "d_tile"))
def nary_distance_pallas(
    X: jax.Array,
    q: jax.Array,
    metric: str = "l2",
    n_tile: int = 256,
    d_tile: int = 512,
) -> jax.Array:
    """(N, D), (D,) -> (N,) float32."""
    N, D = X.shape
    n_tile = min(n_tile, N)
    d_tile = min(d_tile, D)
    nn = pl.cdiv(N, n_tile)
    nd = pl.cdiv(D, d_tile)
    q2 = q.reshape(1, D)
    out = pl.pallas_call(
        functools.partial(_nary_kernel, metric=metric),
        grid=(nn, nd),
        in_specs=[
            pl.BlockSpec((1, d_tile), lambda j, i: (0, i)),
            pl.BlockSpec((n_tile, d_tile), lambda j, i: (j, i)),
        ],
        out_specs=pl.BlockSpec((n_tile, 1), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.float32),
        interpret=_backend.interpret_mode(),
    )(q2, X)
    return out[:, 0]
