"""Plain exact k-nearest-neighbour search under L2, in ``jax.numpy``.

It shares no code with the program under test.  Two passes over the corpus:

1. candidates: for each query the ``n_cand`` rows nearest by
   ||q||^2 - 2 q.x + ||x||^2, with the cross term at ``Precision.HIGHEST``
   (float32 on the TPU; the default would round the operands to bfloat16),
   over row blocks so that no (queries, n) matrix exists;
2. exact distances: sum((x - q)^2) in float32 for the candidates and for any
   other ids the caller names, in one program, so that one (q, x) pair gets
   the same bits wherever it sits.

``control_answers`` is the same search with the operands, the products and
the distances in bfloat16: the precision one step below the float32 the
configurations state.  It stands in for the program in the check that the
comparison can fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["candidates", "exact_distances", "control_answers"]

_HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("n_cand", "block", "low"))
def _candidates(X, Q, n_cand: int, block: int, low: bool):
    n = X.shape[0]
    dt = jnp.bfloat16 if low else jnp.float32
    Qc = Q.astype(dt)
    qn = jnp.sum(Qc * Qc, axis=1)
    nb = -(-n // block)

    def body(state, b):
        best_d, best_i = state
        # the last block is clamped to end at n; rows an earlier block
        # already covered are masked out, so no id enters twice
        start = jnp.minimum(b * block, n - block)
        xb = jax.lax.dynamic_slice_in_dim(X, start, block).astype(dt)
        xn = jnp.sum(xb * xb, axis=1)
        cross = jnp.matmul(Qc, xb.T, precision=_HIGHEST)
        d = qn[:, None] - 2 * cross + xn[None, :]
        idx = start + jnp.arange(block, dtype=jnp.int32)
        d = jnp.where((idx >= b * block)[None, :], d, jnp.inf).astype(dt)
        neg, pos = jax.lax.top_k(-d, n_cand)
        all_d = jnp.concatenate([best_d, (-neg).astype(jnp.float32)], axis=1)
        all_i = jnp.concatenate([best_i, idx[pos]], axis=1)
        neg, sel = jax.lax.top_k(-all_d, n_cand)
        return (-neg, jnp.take_along_axis(all_i, sel, axis=1)), None

    U = Q.shape[0]
    init = (jnp.full((U, n_cand), jnp.inf, jnp.float32),
            jnp.full((U, n_cand), -1, jnp.int32))
    (d, i), _ = jax.lax.scan(body, init, jnp.arange(nb))
    return d, i


def candidates(X, Q, n_cand: int, block: int = 16384):
    """(U, n_cand) int32 ids of the rows of X nearest to each query, best
    first (float32 matrix form at HIGHEST)."""
    block = min(block, X.shape[0])
    return _candidates(X, jnp.asarray(Q, jnp.float32), n_cand, block,
                       False)[1]


@jax.jit
def _exact(X, Q, ids):
    rows = X[jnp.clip(ids, 0, X.shape[0] - 1)]          # (S, m, D)
    diff = rows - Q[:, None, :]
    return jnp.sum(diff * diff, axis=2)


def exact_distances(X, Q, ids) -> np.ndarray:
    """(S, m) float32 sum((X[ids[s, j]] - Q[s])^2); ids out of range read
    row 0 or n-1 (the caller masks them)."""
    return np.asarray(_exact(X, jnp.asarray(Q, jnp.float32),
                             jnp.asarray(ids, jnp.int32)))


def control_answers(X, Q, k: int, block: int = 16384):
    """The k nearest rows and their distances, all in bfloat16: what a
    server computing one precision step below float32 would return."""
    block = min(block, X.shape[0])
    d, i = _candidates(X, jnp.asarray(Q, jnp.float32), k, block, True)
    return np.asarray(i), np.asarray(d)
