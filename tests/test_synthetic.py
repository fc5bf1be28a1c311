"""Synthetic corpora and the exact ground truth: the chunked, buffer-reusing
forms return exactly what the whole-array forms below return."""
import numpy as np
import pytest

from repro.data import synthetic
from repro.data.synthetic import ground_truth, make_dataset


def _whole_array_dataset(n, dim, kind, n_queries, n_clusters, seed):
    rng = np.random.default_rng(seed)
    if kind == "normal":
        X = rng.standard_normal((n, dim))
        Q = rng.standard_normal((n_queries, dim))
    elif kind == "skewed":
        shape = rng.uniform(0.5, 2.0, size=dim)
        scale = rng.uniform(0.2, 5.0, size=dim)
        X = rng.gamma(shape[None, :], scale[None, :], size=(n, dim))
        Q = rng.gamma(shape[None, :], scale[None, :], size=(n_queries, dim))
    else:
        centers = rng.standard_normal((n_clusters, dim)) * 4.0
        widths = rng.uniform(0.3, 1.2, size=(n_clusters, 1))
        ca = rng.integers(0, n_clusters, size=n)
        X = centers[ca] + rng.standard_normal((n, dim)) * widths[ca]
        qa = rng.integers(0, n_clusters, size=n_queries)
        Q = centers[qa] + rng.standard_normal((n_queries, dim)) * widths[qa]
    return X.astype(np.float32), Q.astype(np.float32)


@pytest.mark.parametrize("kind", synthetic.DATASET_KINDS)
def test_make_dataset_chunks_equal_whole_array(kind, monkeypatch):
    monkeypatch.setattr(synthetic, "_CHUNK_ROWS", 7)  # ragged last block
    X, Q = make_dataset(50, 12, kind, n_queries=5, n_clusters=4, seed=3)
    WX, WQ = _whole_array_dataset(50, 12, kind, 5, 4, 3)
    assert X.dtype == Q.dtype == np.float32
    np.testing.assert_array_equal(X, WX)
    np.testing.assert_array_equal(Q, WQ)


@pytest.mark.parametrize("metric", ["l2", "l1", "ip"])
def test_ground_truth_equals_direct_brute_force(metric, rng):
    X = rng.standard_normal((300, 24)).astype(np.float32)
    Q = rng.standard_normal((6, 24)).astype(np.float32)
    ids, ds = ground_truth(X, Q, 5, metric, chunk=64)
    for qi, q in enumerate(Q):
        if metric == "l2":
            d = ((X - q[None, :]) ** 2).sum(1)
        elif metric == "l1":
            d = np.abs(X - q[None, :]).sum(1)
        else:
            d = -(X @ q)
        order = np.argsort(d, kind="stable")[:5]
        np.testing.assert_array_equal(ids[qi], order)
        np.testing.assert_array_equal(ds[qi], d[order])
