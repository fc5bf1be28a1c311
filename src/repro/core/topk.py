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
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "TopK", "topk_init", "topk_merge", "topk_threshold", "rerank_positions",
    "SlotTopK", "slot_topk_levels", "slot_topk_init", "slot_topk_insert",
    "slot_topk_finish", "scan_topk",
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


# ----------------------------------------------------- slot-wise running top-R
# A batched scan over P tiles of C lanes keeps, for each query and each lane
# ("slot") c, the R best (distance, partition) pairs seen at that slot,
# ascending.  Inserting a (B, C) tile is R compare-exchange passes of
# elementwise ops, with no sort or gather; one exact selection over the R * C
# kept entries finishes the batch.  Distances are compared as int32 keys in
# IEEE total order (-0.0 before 0.0, NaNs at the ends), the order
# ``lax.top_k`` sorts by, so ties and signed zeros fall as in ``topk_merge``.

# Largest number of kept levels; shapes that need more keep the merge.
_SLOT_MAX_LEVELS = 8
# Chance per batch, under uniform slots, that R of a query's top rk share
# one slot, which the certificate cannot tell from an eviction: about the
# share of batches that fall back to the merge.
_SLOT_COLLIDE = 1e-3
# Most partitions per step of the slot-wise scan: the loop-invariant operand
# copies of the distance call run once per step, not once per partition, so
# a partition costs a fifth of the device operations (and profile events).
_SLOT_UNROLL = 16
# The key of +inf: empty levels and PAD lanes.
_INF_KEY = 0x7F800000


class SlotTopK(NamedTuple):
    keys: tuple   # R x (B, C) int32 total-order keys, ascending along R
    parts: tuple  # R x (B, C) int32 partition of each kept entry, -1 = empty


def _f32_key(d: jax.Array) -> jax.Array:
    """f32 -> int32 whose signed order is the floats' total order."""
    bits = jax.lax.bitcast_convert_type(d, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _key_f32(key: jax.Array) -> jax.Array:
    """Inverse of ``_f32_key`` (the map is an involution on the bits)."""
    return jax.lax.bitcast_convert_type(
        key ^ ((key >> 31) & 0x7FFFFFFF), jnp.float32
    )


def slot_topk_levels(B: int, C: int, rk: int):
    """Kept levels R for a (B, C) tile stream selecting ``rk``: the smallest
    R >= 2 with B * comb(rk, R) / C**(R - 1) <= 1e-3, the chance under
    uniform slots that some query's top ``rk`` puts R entries in one
    slot.  Never more than ``rk`` (R = rk always certifies).  None when it
    would take more than ``_SLOT_MAX_LEVELS``: keep the per-step merge."""
    R = 2
    while R < rk and B * math.comb(rk, R) > _SLOT_COLLIDE * C ** (R - 1):
        R += 1
    R = min(R, rk)
    return R if R <= _SLOT_MAX_LEVELS else None


def slot_topk_init(B: int, C: int, R: int) -> SlotTopK:
    empty = jnp.full((B, C), _INF_KEY, jnp.int32)
    none = jnp.full((B, C), -1, jnp.int32)
    return SlotTopK(keys=(empty,) * R, parts=(none,) * R)


def slot_topk_insert(state: SlotTopK, dists: jax.Array, p, pad: jax.Array
                     ) -> SlotTopK:
    """Insert partition ``p``'s (B, C) distances; ``pad`` (C,) or (B, C)
    marks PAD lanes (and entries a query skips), which enter as +inf and
    displace nothing.  The new entry goes in after every kept entry it does
    not beat strictly (those came from lower partitions, so they win ties)
    and the levels below it shift down one."""
    x = jnp.where(pad, _INF_KEY, _f32_key(dists))
    keys, parts = [], []
    above = None  # (took, key, part) of the level above
    for k, kp in zip(state.keys, state.parts):
        took = x < k
        if above is None:
            keys.append(jnp.where(took, x, k))
            parts.append(jnp.where(took, p, kp))
        else:
            t0, k0, kp0 = above
            keys.append(jnp.where(took, jnp.where(t0, k0, x), k))
            parts.append(jnp.where(took, jnp.where(t0, kp0, p), kp))
        above = (took, k, kp)
    return SlotTopK(keys=tuple(keys), parts=tuple(parts))


def slot_topk_finish(state: SlotTopK, rk: int) -> tuple[TopK, jax.Array]:
    """The (B, rk) top of the kept entries as flat positions ``p * C + c``
    (-1 = empty), ties to the lower position, as a stable ``lax.top_k`` over
    the merged stream gives; and the batch's fallback flag.  An entry a slot
    evicted is worse than that slot's last kept one, so the result is the
    exact top ``rk`` unless some slot's last kept entry is strictly better
    than a query's ``rk``-th: then the flag is set.  Needs R * C >= rk,
    which ``slot_topk_levels`` always gives.

    The selection is ``rk`` passes of two row minima (key, then position
    among the key's ties), not a sort: the TPU compiler takes seconds per
    sort, and a sort of R * C lanes per query several times longer."""
    B, C = state.keys[0].shape
    c = jnp.arange(C, dtype=jnp.int32)
    pos = [jnp.where(p >= 0, p * C + c, -1) for p in state.parts]
    keys = jnp.concatenate(state.keys, axis=1)            # (B, R * C)
    flat = jnp.concatenate(pos, axis=1)
    taken = jnp.iinfo(jnp.int32).max  # above every key that can be kept

    def select(i, carry):
        keys, out_k, out_p = carry
        k = jnp.min(keys, axis=1, keepdims=True)
        p = jnp.min(jnp.where(keys == k, flat, taken), axis=1, keepdims=True)
        # the empty entries (+inf, -1) repeat: they go out together, and
        # every place after them reads (+inf, -1)
        k, p = jnp.minimum(k, _INF_KEY), jnp.where(k < _INF_KEY, p, -1)
        keys = jnp.where((keys == k) & (flat == p), taken, keys)
        out_k = jax.lax.dynamic_update_slice(out_k, k, (0, i))
        out_p = jax.lax.dynamic_update_slice(out_p, p, (0, i))
        return keys, out_k, out_p

    empty = jnp.full((B, rk), -1, jnp.int32)
    _, out_k, out_p = jax.lax.fori_loop(0, rk, select, (keys, empty, empty))
    tk, tp = out_k[:, -1:], out_p[:, -1:]
    lk, lp = state.keys[-1], pos[-1]
    fallback = jnp.any((lk < tk) | ((lk == tk) & (lp < tp)))
    return TopK(dists=_key_f32(out_k), ids=out_p), fallback


def scan_topk(distances, xs, pad: jax.Array, B: int, rk: int, probe=None):
    """Per-query running top-``rk`` flat positions over the P tiles of
    ``xs``: ``distances(x)`` gives the (B, C) distances of each leading
    slice of ``xs``; ``pad`` (P, C) marks PAD lanes (position -1).

    Keeps the slot-wise top-R of ``slot_topk_levels`` and selects once;
    when the certificate fails, the same program re-runs the scan with the
    per-step ``topk_merge``, so the answer is always the merge's, bit for
    bit.  Returns (TopK of (B, rk), fallback flag); the flag is None where
    the shapes keep the merge alone.

    ``probe`` (B, P) bool, when given, restricts each query to its own
    partitions: its distances on the others enter as +inf, as PAD lanes
    do, and only the partitions some query probes are read, in ascending
    order, by a loop whose trip count is their number.  The answer is the
    full scan's with the unprobed entries at +inf, bit for bit."""
    P, C = pad.shape
    pos = jnp.where(pad, -1, jnp.arange(P * C, dtype=jnp.int32).reshape(P, C))
    if probe is None:
        # a divisor of P: with a remainder, lax.scan copies the other tiles
        unroll = max(u for u in range(1, _SLOT_UNROLL + 1) if P % u == 0)

        def walk(insert, init):
            def body(state, inp):
                x, p, tpad = inp
                return insert(state, distances(x), p, tpad), None

            return jax.lax.scan(
                body, init, (xs, jnp.arange(P, dtype=jnp.int32), pad),
                unroll=unroll,
            )[0]
    else:
        walk = _probed_walk(distances, xs, pad, probe)

    def merge_insert(state: TopK, d, p, skip) -> TopK:
        ids = jnp.where(skip, -1, pos[p])
        return jax.vmap(topk_merge)(state, d, jnp.broadcast_to(ids, d.shape))

    def merge_scan(_=None) -> TopK:
        init = jax.vmap(lambda _: topk_init(rk))(jnp.arange(B))
        return walk(merge_insert, init)

    R = slot_topk_levels(B, C, rk)
    if R is None:
        return merge_scan(), None
    slots = walk(slot_topk_insert, slot_topk_init(B, C, R))
    cand, fallback = slot_topk_finish(slots, rk)
    cand = jax.lax.cond(fallback, merge_scan, lambda c: c, cand)
    return cand, fallback


def _probed_walk(distances, xs, pad, probe):
    """``scan_topk``'s walk over the union of the probed partitions: their
    ids packed in ascending order, ``_SLOT_UNROLL`` to a step, a trip count
    of whole steps; the places past the union read partition 0, skipped
    by every query.  One program serves every union size."""
    P, _ = pad.shape
    U = min(_SLOT_UNROLL, P)
    cap = -(-P // U) * U
    union = jnp.any(probe, axis=0)
    n = jnp.sum(union, dtype=jnp.int32)
    plist = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(union, jnp.cumsum(union) - 1, cap)
    ].set(jnp.arange(P, dtype=jnp.int32), mode="drop")

    def walk(insert, init):
        def step(i, state):
            for j in range(U):
                p = plist[i * U + j]
                live = probe[:, p] & (i * U + j < n)
                x = jax.lax.dynamic_index_in_dim(xs, p, keepdims=False)
                state = insert(state, distances(x), p,
                               pad[p][None, :] | ~live[:, None])
            return state

        return jax.lax.fori_loop(0, (n + U - 1) // U, step, init)

    return walk


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
