"""Distance kernels on horizontal (N-ary), PDX, and DSM layouts — pure jnp.

These are the reference implementations of the paper's Algorithm 1 and the
baselines it compares against.  The Pallas TPU kernels in ``repro.kernels``
implement the same contracts with explicit VMEM tiling; these jnp versions are
both the oracles for those kernels and the (XLA-autovectorized) CPU kernels
used by the benchmark harness — matching the paper's claim that PDX needs no
hand-written intrinsics, only a vectorization-friendly layout.

Conventions:
  * horizontal data: ``X   (N, D)``  — one row per vector
  * PDX data:        ``T   (D, V)``  — one row per dimension (a partition tile)
  * metrics return *uncorrected* values (squared L2; raw IP, larger=closer is
    NOT applied here — engines negate IP so that all metrics minimize).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "METRICS",
    "nary_distance",
    "pdx_distance",
    "pdx_partial",
    "pdx_accumulate",
    "batched_distance_matmul",
]

METRICS = ("l2", "ip", "l1")


def _check_metric(metric: str) -> None:
    if metric not in METRICS:
        raise ValueError(f"metric must be one of {METRICS}, got {metric!r}")


# --------------------------------------------------------------------------
# Horizontal (vector-at-a-time) kernels — the paper's N-ary baseline.
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("metric",))
def nary_distance(X: jax.Array, q: jax.Array, metric: str = "l2") -> jax.Array:
    """(N, D), (D,) -> (N,). Reduction runs along each row (per-vector)."""
    _check_metric(metric)
    if metric == "l2":
        diff = X - q[None, :]
        return jnp.sum(diff * diff, axis=1)
    if metric == "l1":
        return jnp.sum(jnp.abs(X - q[None, :]), axis=1)
    return -jnp.sum(X * q[None, :], axis=1)  # ip, negated to minimize


# --------------------------------------------------------------------------
# PDX (dimension-at-a-time) kernels — the paper's Algorithm 1.
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("metric",))
def pdx_distance(T: jax.Array, q: jax.Array, metric: str = "l2") -> jax.Array:
    """(D, V), (D,) -> (V,). Accumulation runs across dimensions; each value
    of the output vector lives in its own SIMD lane (no horizontal reduce)."""
    _check_metric(metric)
    if metric == "l2":
        diff = T - q[:, None]
        return jnp.sum(diff * diff, axis=0)
    if metric == "l1":
        return jnp.sum(jnp.abs(T - q[:, None]), axis=0)
    return -jnp.sum(T * q[:, None], axis=0)


@functools.partial(jax.jit, static_argnames=("metric",))
def pdx_accumulate(
    T_slice: jax.Array, q_slice: jax.Array, acc: jax.Array, metric: str = "l2"
) -> jax.Array:
    """Partial-distance accumulation over a dimension slice.

    (d, V), (d,), (V,) -> (V,).  This is the inner step of PDXearch: the
    running ``distances`` array of Algorithm 1 stays resident (registers on
    CPU, VMEM scratch on TPU) while dimension slices stream through.
    """
    _check_metric(metric)
    if metric == "l2":
        diff = T_slice - q_slice[:, None]
        return acc + jnp.sum(diff * diff, axis=0)
    if metric == "l1":
        return acc + jnp.sum(jnp.abs(T_slice - q_slice[:, None]), axis=0)
    return acc - jnp.sum(T_slice * q_slice[:, None], axis=0)


def pdx_partial(
    T: jax.Array, q: jax.Array, d0: int, d1: int, acc: jax.Array, metric: str = "l2"
) -> jax.Array:
    """Accumulate dimensions [d0, d1) of tile T into acc (static bounds)."""
    return pdx_accumulate(T[d0:d1], q[d0:d1], acc, metric)


# --------------------------------------------------------------------------
# Batched-query matmul form (beyond-paper, MXU-native).
#
# ||q - x||^2 = ||q||^2 - 2 q.x + ||x||^2 — over a PDX tile the -2 Q X term is
# a single (B, d) @ (d, V) matmul with the PDX tile already in the K-major
# layout the MXU wants.  L1 has no matmul form; engines fall back to vmapped
# pdx_distance for it.
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("metric",))
def batched_distance_matmul(
    T: jax.Array, Q: jax.Array, metric: str = "l2"
) -> jax.Array:
    """(D, V), (B, D) -> (B, V) for l2/ip."""
    if metric == "l1":
        return jax.vmap(lambda q: pdx_distance(T, q, "l1"))(Q)
    # HIGHEST: these are the exact distances; the TPU default rounds f32
    # matmul operands to bf16
    cross = jnp.matmul(Q, T, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return -cross
    qn = jnp.sum(Q * Q, axis=1, keepdims=True)  # (B, 1)
    xn = jnp.sum(T * T, axis=0, keepdims=True)  # (1, V)
    return qn - 2.0 * cross + xn
