"""Top-k merge and exact re-rank: the forms that compile well on the TPU
return exactly what the direct forms below return."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distance import nary_distance
from repro.core.topk import (
    TopK, rerank_positions, scan_topk, slot_topk_levels, topk_merge,
)


def _one_top_k(state, d, i):
    k = state.dists.shape[0]
    d = jnp.where(i < 0, jnp.inf, d)
    all_d = jnp.concatenate([state.dists, d])
    all_i = jnp.concatenate([state.ids, i])
    neg, idx = jax.lax.top_k(-all_d, k)
    return -neg, all_i[idx]


@pytest.mark.parametrize("m", [4097, 10000, 65536])
@pytest.mark.parametrize("k", [1, 40, 300])
@pytest.mark.parametrize("values", ["distinct", "ties", "mostly_inf"])
def test_topk_merge_long_batch_equals_one_top_k(m, k, values, rng):
    if values == "distinct":
        d = rng.standard_normal(m).astype(np.float32)
    elif values == "ties":
        d = rng.integers(0, 5, m).astype(np.float32)
    else:
        d = np.full(m, np.inf, np.float32)
        d[rng.integers(0, m, 3)] = 1.0
    i = rng.integers(-1, 10**6, m).astype(np.int32)
    state = TopK(jnp.asarray(np.sort(rng.standard_normal(k)), jnp.float32),
                 jnp.arange(k, dtype=jnp.int32) + 10**7)
    got = topk_merge(state, jnp.asarray(d), jnp.asarray(i))
    want_d, want_i = _one_top_k(state, jnp.asarray(d), jnp.asarray(i))
    np.testing.assert_array_equal(got.dists, want_d)
    np.testing.assert_array_equal(got.ids, want_i)


@pytest.mark.parametrize("metric", ["l2", "ip", "l1"])
@pytest.mark.parametrize("P,D,C,B,rk", [(5, 96, 256, 3, 40), (4, 33, 100, 2, 7),
                                        (3, 1536, 1024, 2, 300)])
def test_rerank_positions_equals_direct_gather(metric, P, D, C, B, rk, rng):
    master = jnp.asarray(rng.standard_normal((P, D, C)), jnp.float32)
    ids = jnp.asarray(rng.permutation(P * C).reshape(P, C), jnp.int32)
    Q = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
    pos = jnp.asarray(rng.integers(-1, P * C, size=(B, rk)), jnp.int32)
    got = rerank_positions(master, ids, Q, TopK(jnp.zeros((B, rk)), pos), 5,
                           metric)

    safe = jnp.maximum(pos, 0)
    vecs = master[safe // C, :, safe % C]                 # (B, rk, D)
    d = jax.vmap(lambda v, q: nary_distance(v, q, metric))(vecs, Q)
    d = jnp.where(pos >= 0, d, jnp.inf)
    gids = jnp.where(pos >= 0, ids.reshape(-1)[safe], -1)
    for b in range(B):
        want_d, want_i = _one_top_k(
            TopK(jnp.full((5,), jnp.inf), jnp.full((5,), -1, jnp.int32)),
            d[b], gids[b],
        )
        np.testing.assert_array_equal(got.dists[b], want_d)
        np.testing.assert_array_equal(got.ids[b], want_i)


def _merge_scan(dmats, pad, rk):
    """The per-step merge the slot-wise scan must reproduce: one vmapped
    ``topk_merge`` of each (B, C) tile into the running (B, rk) state."""
    P, B, C = dmats.shape
    pos = jnp.where(pad, -1, jnp.arange(P * C, dtype=jnp.int32).reshape(P, C))

    def body(state, inp):
        d, tpos = inp
        return jax.vmap(topk_merge, (0, 0, None))(state, d, tpos), None

    init = TopK(jnp.full((B, rk), jnp.inf), jnp.full((B, rk), -1, jnp.int32))
    return jax.lax.scan(body, init, (dmats, pos))[0]


@pytest.mark.parametrize("P,C,B,rk,values,fallback", [
    (7, 128, 4, 40, "normal", False),
    (30, 256, 8, 20, "normal", False),
    (5, 1024, 64, 40, "normal", False),
    (9, 32, 1, 36, "normal", False),          # C < rk
    (12, 64, 3, 10, "integers", False),       # ties across and within tiles
    (12, 128, 2, 10, "coarse", False),        # three values: mostly ties
    (6, 128, 4, 20, "signed_zeros", False),   # -0.0 ranks before 0.0
    (8, 128, 2, 20, "mostly_pad", False),     # fewer real candidates than rk
    (8, 128, 2, 20, "mostly_inf", False),     # real +inf lanes never enter
    (10, 64, 2, 10, "one_slot", True),        # R + 1 of the top in one slot
    (12, 64, 4, 10, "tied_slot", True),       # the same, tied with the rk-th
    (4, 16, 2, 40, "normal", None),           # R would pass 8: merge only
])
def test_slot_topk_equals_per_step_merge(P, C, B, rk, values, fallback):
    """The slot-wise scan (``scan_topk``) returns what the per-step merge
    returns, bit for bit, ties and signed zeros included; ``fallback`` is
    whether its certificate fails (None: the shapes keep the merge)."""
    rng = np.random.default_rng([P, C, B, rk])
    shape = (P, B, C)
    if values == "normal":
        d = rng.standard_normal(shape)
    elif values == "integers":
        d = rng.integers(0, 40, shape)
    elif values == "coarse":
        d = rng.integers(0, 3, shape)
    elif values == "signed_zeros":
        d = rng.choice([-0.0, 0.0, 1.0], shape)
    elif values == "mostly_inf":
        d = np.where(rng.random(shape) < 0.02, rng.standard_normal(shape),
                     np.inf)
    else:
        d = rng.uniform(1.0, 2.0, shape)
    if values == "one_slot":  # query 0's best P entries all sit at slot 3
        d[:, 0, 3] = np.arange(P) * 0.01
    elif values == "tied_slot":  # and ties the last partition's lanes 10-20
        d[:] = 2.0
        d[:, 0, 3] = d[-1, 0, 10:21] = 1.0
    d = jnp.asarray(d, jnp.float32)
    pad = rng.random((P, C)) < (0.9 if values == "mostly_pad" else 0.1)
    pad[:, 3] = pad[-1, 10:21] = False
    pad = jnp.asarray(pad)

    got, flag = jax.jit(
        lambda d, pad: scan_topk(lambda x: x, d, pad, B, rk)
    )(d, pad)
    want = _merge_scan(d, pad, rk)
    np.testing.assert_array_equal(
        jax.lax.bitcast_convert_type(got.dists, jnp.int32),
        jax.lax.bitcast_convert_type(want.dists, jnp.int32))
    np.testing.assert_array_equal(got.ids, want.ids)
    assert (None if flag is None else bool(flag)) == fallback


@pytest.mark.parametrize("B,C,rk,R", [
    (64, 1024, 40, 5), (32, 1024, 40, 5), (1, 1024, 40, 4),
    (4, 256, 5, 3), (1, 1024, 1, 1), (64, 16, 40, None),
])
def test_slot_topk_levels(B, C, rk, R):
    assert slot_topk_levels(B, C, rk) == R
