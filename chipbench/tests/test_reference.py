"""The plain reference and the comparison, at a tiny size on the CPU."""
import numpy as np
import pytest

from chipbench import compare
from chipbench.harness import load_module
from repro.data.synthetic import ground_truth

ref = load_module("references/knn_l2.py")
gen = load_module("generators/clustered_unit.py")
PARAMS = {"n_clusters": 8, "width_lo": 0.3, "width_hi": 1.2}
LIMITS = {"bad_answers": 0, "dist_err": 1e-4, "recall_miss": 0.01}


@pytest.fixture(scope="module")
def data():
    X, Q = gen.generate(7, 2**40 + 5, 3000, 48, 24, PARAMS)
    return X, Q


def test_generator_is_seeded_and_unit_length(data):
    X, Q = data
    X2, Q2 = gen.generate(7, 2**40 + 5, 3000, 48, 24, PARAMS)
    X3, Q3 = gen.generate(7, 5, 3000, 48, 24, PARAMS)
    X4, _ = gen.generate(8, 2**40 + 5, 3000, 48, 24, PARAMS)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
    np.testing.assert_array_equal(np.asarray(Q), np.asarray(Q2))
    # the query seed draws the queries and leaves the corpus alone
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X3))
    assert not np.array_equal(np.asarray(Q), np.asarray(Q3))
    assert not np.array_equal(np.asarray(X), np.asarray(X4))
    np.testing.assert_allclose(np.linalg.norm(np.asarray(Q), axis=1), 1.0,
                               rtol=1e-5)


@pytest.mark.parametrize("block", [3000, 1024, 700])
def test_reference_matches_repro_ground_truth(data, block):
    X, Q = data
    gt_ids, gt_d = ground_truth(np.asarray(X), np.asarray(Q), 10)
    cand = np.asarray(ref.candidates(X, Q, 10, block=block))
    np.testing.assert_array_equal(np.sort(cand, axis=1),
                                  np.sort(gt_ids, axis=1))
    d = ref.exact_distances(X, Q, cand)
    np.testing.assert_allclose(np.sort(d, axis=1), gt_d, rtol=1e-5,
                               atol=1e-6)


def exact_answers(X, Q, k=10):
    ids, d = ground_truth(np.asarray(X), np.asarray(Q), k)
    return ids, d


def test_exact_answers_pass(data):
    X, Q = data
    ids, d = exact_answers(X, Q)
    out, ok = compare.compare(ref, X, Q, ids, d, 10, LIMITS)
    assert ok, out
    assert out["recall_miss"]["value"] == 0.0
    assert out["dist_err"]["value"] < 1e-5


def test_tied_neighbour_counts_as_found(data):
    """An id tied with the k-th neighbour is as good as the k-th."""
    X, Q = data
    X = np.asarray(X).copy()
    ids, d = exact_answers(X, Q)
    X = np.concatenate([X, X[ids[0, 9]][None]])   # a twin of q0's 10th
    ids[0, 9] = len(X) - 1
    out, ok = compare.compare(ref, X, Q, ids, d, 10, LIMITS)
    assert ok, out


@pytest.mark.parametrize("fault", ["far_id", "dist", "dup", "out_of_range",
                                   "nan"])
def test_wrong_answers_fail(data, fault):
    X, Q = data
    ids, d = exact_answers(X, Q)
    if fault == "far_id":        # one neighbour swapped for a far row
        far = np.argmax(np.sum((np.asarray(X) - np.asarray(Q)[0]) ** 2, 1))
        ids[0, 3] = far
    elif fault == "dist":        # a distance off by 1%
        d[1, 0] *= 1.01
    elif fault == "dup":
        ids[2, 1] = ids[2, 0]
    elif fault == "out_of_range":
        ids[3, 0] = -1
    else:
        d[4, 2] = np.nan
    out, ok = compare.compare(ref, X, Q, ids, d, 10, LIMITS)
    assert not ok, out


def test_control_fails(data):
    """The reference in bfloat16, put in the program's place, is caught."""
    X, Q = data
    ids, d = ref.control_answers(X, Q, 10)
    out, ok = compare.compare(ref, X, Q, ids, d, 10, LIMITS)
    assert not ok, out
    assert out["dist_err"]["value"] > 10 * LIMITS["dist_err"]
