"""Top-k merge and exact re-rank: the forms that compile well on the TPU
return exactly what the direct forms below return."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distance import nary_distance
from repro.core.topk import TopK, rerank_positions, topk_merge


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
