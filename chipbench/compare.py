"""The comparison that decides ``correct``: served answers against the plain
reference, each number beside its limit.

Every sampled answer is a (k,) list of ids with a (k,) list of distances.
The reference computes, for the answer's query, the exact float32 distance
of its ``n_cand`` best candidates and of every served id in one program
(``references/<name>.py``).  Three numbers, each worse when higher:

``bad_answers``
    answers with an id outside [0, n), an id twice, or a distance that is
    not finite.  Limit 0.
``dist_err``
    the largest gap between a served distance and the exact distance of the
    id it was served with, as a share of that query's exact k-th distance.
``recall_miss``
    1 - recall@k, where a served id counts as found when its exact distance
    is at most the exact k-th distance: a tie at the k-th place is found
    whichever of the tied ids is served.
"""
from __future__ import annotations

import numpy as np

__all__ = ["compare", "N_CAND"]

#: candidates per query in the reference's first pass (>= k)
N_CAND = 64


def compare(ref, X, Q, served_ids, served_dists, k: int, limits: dict):
    """``Q`` (S, D) the sampled answers' queries; ``served_*`` (S, k).
    Returns ``({name: {"value", "limit"}}, correct)``."""
    served_ids = np.asarray(served_ids, np.int64)
    served_dists = np.asarray(served_dists, np.float64)
    S = served_ids.shape[0]
    n = X.shape[0]
    cand = np.asarray(ref.candidates(X, Q, max(N_CAND, k)))
    ids = np.concatenate([cand, np.clip(served_ids, 0, n - 1)], axis=1)
    exact = ref.exact_distances(X, Q, ids).astype(np.float64)
    kth = np.sort(exact[:, : cand.shape[1]], axis=1)[:, k - 1]
    got = exact[:, cand.shape[1]:]

    in_range = (served_ids >= 0) & (served_ids < n)
    dup = np.array([len(set(r.tolist())) < len(r) for r in served_ids])
    finite = np.isfinite(served_dists)
    bad = ~(in_range.all(axis=1) & finite.all(axis=1)) | dup
    ok = in_range & finite & ~dup[:, None]

    scale = np.maximum(kth, np.finfo(np.float32).tiny)[:, None]
    err = np.where(ok, np.abs(served_dists - got) / scale, 0.0)
    found = ok & (got <= kth[:, None])
    numbers = {
        "bad_answers": float(bad.sum()),
        "dist_err": float(err.max()) if S else 0.0,
        "recall_miss": 1.0 - float(found.sum()) / max(S * k, 1),
    }
    out = {name: {"value": v, "limit": float(limits[name])}
           for name, v in numbers.items()}
    correct = S > 0 and all(v["value"] <= v["limit"] for v in out.values())
    return out, correct
