"""Synthetic vector collections matching the paper's dataset taxonomy
(Section 2.2): *normal* (DEEP/GloVe/Contriever-like) vs *skewed*
(SIFT/GIST/MSong/OpenAI-like), plus *clustered* mixtures so IVF has real
structure to find.  Also exact ground-truth KNN and recall@k.
"""
from __future__ import annotations

import numpy as np

__all__ = ["make_dataset", "ground_truth", "ivf_probe_ground_truth",
           "recall_at_k", "DATASET_KINDS"]

DATASET_KINDS = ("normal", "skewed", "clustered")

# Rows drawn per float64 block: a corpus materializes as float32 only, and
# its float64 temporaries stay a few hundred MB at any n.
_CHUNK_ROWS = 16384


def _rows(n: int, dim: int, draw) -> np.ndarray:
    """(n, dim) float32 from ``draw(lo, hi)`` -> (hi - lo, dim) float64,
    called on consecutive row blocks.  The generator consumes its stream in
    the same order as one whole-array draw, so the values are identical to
    drawing all n rows at once and casting."""
    X = np.empty((n, dim), np.float32)
    for lo in range(0, n, _CHUNK_ROWS):
        hi = min(lo + _CHUNK_ROWS, n)
        X[lo:hi] = draw(lo, hi)
    return X


def make_dataset(
    n: int,
    dim: int,
    kind: str = "normal",
    *,
    n_queries: int = 16,
    n_clusters: int = 64,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Returns (X (n, dim), Q (n_queries, dim)) float32.

    normal    — i.i.d. standard normal dims (hard to prune; paper Table 2).
    skewed    — per-dimension gamma with varying scale (easy to prune).
    clustered — mixture of Gaussians (IVF-friendly), mildly anisotropic.
    """
    rng = np.random.default_rng(seed)
    if kind == "normal":
        X = _rows(n, dim, lambda lo, hi: rng.standard_normal((hi - lo, dim)))
        Q = rng.standard_normal((n_queries, dim))
    elif kind == "skewed":
        shape = rng.uniform(0.5, 2.0, size=dim)
        scale = rng.uniform(0.2, 5.0, size=dim)
        X = _rows(n, dim, lambda lo, hi: rng.gamma(
            shape[None, :], scale[None, :], size=(hi - lo, dim)))
        Q = rng.gamma(shape[None, :], scale[None, :], size=(n_queries, dim))
    elif kind == "clustered":
        centers = rng.standard_normal((n_clusters, dim)) * 4.0
        widths = rng.uniform(0.3, 1.2, size=(n_clusters, 1))
        ca = rng.integers(0, n_clusters, size=n)
        X = _rows(n, dim, lambda lo, hi: (
            centers[ca[lo:hi]]
            + rng.standard_normal((hi - lo, dim)) * widths[ca[lo:hi]]))
        qa = rng.integers(0, n_clusters, size=n_queries)
        Q = centers[qa] + rng.standard_normal((n_queries, dim)) * widths[qa]
    else:
        raise ValueError(f"kind must be one of {DATASET_KINDS}")
    return X, Q.astype(np.float32)


def ground_truth(
    X: np.ndarray, Q: np.ndarray, k: int, metric: str = "l2", chunk: int = 65536
) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k by brute force (numpy, chunked): (B, k) ids and dists.

    One (chunk, D) buffer holds every chunk's differences, so a call
    allocates it once instead of twice per chunk and query."""
    B = Q.shape[0]
    ids = np.zeros((B, k), np.int64)
    ds = np.zeros((B, k), np.float32)
    buf = np.empty((min(chunk, len(X)), X.shape[1]), np.result_type(X, Q))
    for qi in range(B):
        q = Q[qi]
        best_d = None
        best_i = None
        for lo in range(0, len(X), chunk):
            xc = X[lo : lo + chunk]
            if metric in ("l2", "l1"):
                diff = np.subtract(xc, q[None, :], out=buf[: len(xc)])
                if metric == "l2":
                    d = np.square(diff, out=diff).sum(1)
                else:
                    d = np.abs(diff, out=diff).sum(1)
            else:
                d = -(xc @ q)
            idx = np.argpartition(d, min(k, len(d) - 1))[:k]
            cd, ci = d[idx], idx + lo
            if best_d is None:
                best_d, best_i = cd, ci
            else:
                alld = np.concatenate([best_d, cd])
                alli = np.concatenate([best_i, ci])
                sel = np.argpartition(alld, k - 1)[:k]
                best_d, best_i = alld[sel], alli[sel]
        order = np.argsort(best_d, kind="stable")
        ids[qi], ds[qi] = best_i[order], best_d[order]
    return ids, ds


def ivf_probe_ground_truth(
    X: np.ndarray, Q: np.ndarray, centroids: np.ndarray, assign: np.ndarray,
    nprobe: int, k: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k under L2 among the rows of each query's ``nprobe``
    nearest IVF lists, in float64: (B, k) ids and dists (-1 / inf where
    the lists hold fewer than k rows).  The index is data here: its
    ``centroids`` (K, D) and each row's list ``assign`` (n,); the lists are
    ranked by exact distance to the centroids, ties to the lower list."""
    X = np.asarray(X, np.float64)
    C = np.asarray(centroids, np.float64)
    assign = np.asarray(assign)
    B = len(Q)
    ids = np.full((B, k), -1, np.int64)
    ds = np.full((B, k), np.inf)
    for qi, q in enumerate(np.asarray(Q, np.float64)):
        lists = np.argsort(((C - q) ** 2).sum(1), kind="stable")[:nprobe]
        rows = np.flatnonzero(np.isin(assign, lists))
        d = ((X[rows] - q) ** 2).sum(1)
        best = np.argsort(d, kind="stable")[:k]
        ids[qi, : len(best)], ds[qi, : len(best)] = rows[best], d[best]
    return ids, ds


def recall_at_k(found_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Mean |found ∩ true| / k over queries (paper Section 2.1)."""
    found_ids = np.atleast_2d(found_ids)
    true_ids = np.atleast_2d(true_ids)
    k = true_ids.shape[1]
    hits = 0
    for f, t in zip(found_ids, true_ids):
        hits += len(set(f.tolist()) & set(t.tolist()))
    return hits / (len(true_ids) * k)
