"""Streaming top-k candidate set — the jit-friendly analogue of the paper's
max-heap.  State is a fixed-size (k,) pair of (distances, ids), merged with
candidate batches via lax.top_k; the running threshold (paper: "current best
k-th exact distance") is ``heap_dists[-1]`` since we keep it sorted ascending.

Also home of ``rerank_positions``, the exact-f32 re-rank every quantized
scan path shares: candidates selected from a reduced-precision mirror are
tracked as flat tile *positions* (``p * C + c``, -1 = pad), their master
columns are gathered, and the final top-k is rebuilt from exact distances
with global ids.  Lives here (not in the executors) because the host fused
executors and both shard_map bodies must agree on the PAD-position
convention bit for bit.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "TopK", "topk_init", "topk_merge", "topk_threshold", "rerank_positions",
]

INF = jnp.float32(jnp.inf)

# Candidate batches longer than this are pre-reduced row by row.
_TOPK_ROW = 4096

# Candidates re-scored per step of ``rerank_positions``: each reads one
# (D, 128) tile row, so a step holds _RERANK_BLOCK * D * 512 bytes.
_RERANK_BLOCK = 256


class TopK(NamedTuple):
    dists: jax.Array  # (k,) ascending
    ids: jax.Array    # (k,) int32, -1 = empty slot


def topk_init(k: int) -> TopK:
    return TopK(dists=jnp.full((k,), INF), ids=jnp.full((k,), -1, jnp.int32))


@jax.jit
def topk_merge(state: TopK, cand_dists: jax.Array, cand_ids: jax.Array) -> TopK:
    """Merge a (m,) candidate batch into the (k,) state. Padded candidates
    must carry dist=+inf (or id=-1 with huge dist) and are never selected."""
    k = state.dists.shape[0]
    # Guard: candidates with id == -1 are padding slots from partial tiles.
    cand_dists = jnp.where(cand_ids < 0, INF, cand_dists)
    m = cand_dists.shape[0]
    if m > _TOPK_ROW and k < _TOPK_ROW:
        # Exact two-level selection: a candidate in the top k is in the top
        # k of its row (ties go to the lower index at both levels, and rows
        # keep index order), so the result equals one top_k over all m.
        # The TPU compiler takes tens of seconds on one top_k over ~1e6.
        pad = (-m) % _TOPK_ROW
        d2 = jnp.pad(cand_dists, (0, pad), constant_values=INF)
        i2 = jnp.pad(cand_ids, (0, pad), constant_values=-1)
        neg, pos = jax.lax.top_k(-d2.reshape(-1, _TOPK_ROW), k)
        cand_dists = -neg.reshape(-1)
        cand_ids = jnp.take_along_axis(
            i2.reshape(-1, _TOPK_ROW), pos, axis=1
        ).reshape(-1)
    all_d = jnp.concatenate([state.dists, cand_dists])
    all_i = jnp.concatenate([state.ids, cand_ids])
    neg_top, idx = jax.lax.top_k(-all_d, k)
    return TopK(dists=-neg_top, ids=all_i[idx])


@functools.partial(jax.jit, static_argnames=("k",))
def topk_from_batch(cand_dists: jax.Array, cand_ids: jax.Array, k: int) -> TopK:
    return topk_merge(topk_init(k), cand_dists, cand_ids)


def topk_threshold(state: TopK) -> jax.Array:
    """Pruning threshold: worst distance currently in the candidate set."""
    return state.dists[-1]


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def rerank_positions(
    master: jax.Array,
    ids: jax.Array,
    Q: jax.Array,
    cand: TopK,
    k: int,
    metric: str = "l2",
) -> TopK:
    """Exact f32 re-rank: ``cand.ids`` are flat tile positions (-1 = pad)
    into the (P, D, C) ``master`` tiles; gather those columns, recompute
    their distances to the (B, D) queries, and keep the best ``k`` as
    global ids from the (P, C) ``ids`` array."""
    from .distance import nary_distance  # topk is imported by distance users

    P, D, C = master.shape
    safe = jnp.maximum(cand.ids, 0)                      # (B, rk) positions
    B, rk = safe.shape
    # A candidate's column is D values strided by C.  Gathering those
    # directly makes XLA:TPU first relayout all of ``master`` with D minor,
    # a copy the size of the store on every call; slicing the candidate's
    # whole 128-lane tile row and picking its lane reads only that row.
    L = 128 if C % 128 == 0 else C

    def column_distance(pos, b):
        p, c = pos // C, pos % C
        row = jax.lax.dynamic_slice(master, (p, 0, c - c % L), (1, D, L))
        col = jax.lax.dynamic_index_in_dim(row[0], c % L, axis=1)  # (D, 1)
        q = jax.lax.dynamic_index_in_dim(Q, b, keepdims=False)
        return nary_distance(col.T, q, metric)[0]

    d = jax.lax.map(
        lambda pb: column_distance(*pb),
        (safe.reshape(-1), jnp.repeat(jnp.arange(B), rk)),
        batch_size=min(B * rk, _RERANK_BLOCK),
    ).reshape(B, rk)
    d = jnp.where(cand.ids >= 0, d, INF)
    gids = jnp.where(cand.ids >= 0, ids.reshape(-1)[safe], -1)
    merge = lambda dd, ii: topk_merge(topk_init(k), dd, ii)  # noqa: E731
    return jax.vmap(merge)(d, gids)
