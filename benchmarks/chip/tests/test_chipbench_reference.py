"""The comparison that decides ``correct`` catches a wrong id, a wrong
distance and a lost query, and the lower-precision control fails it."""

import numpy as np
import pytest

from chipbench_common import SEED

import corpus
import reference

LIMITS = {"recall_miss": 0.05, "dist_gap": 1e-4, "lost": 0}
DATA = {"generator": "clustered_gaussian", "data_seed": 0, "n": 3000,
        "dim": 96, "dtype": "float32", "n_clusters": 16, "cluster_std": 0.35,
        "center_scale": 0.7, "query_noise": 0.5}


@pytest.fixture(scope="module")
def case():
    corp = corpus.Corpus(DATA)
    q = corp.queries(corpus.rng(SEED, "window"), 48)
    ids, d = reference.exact_knn(corp.vectors, q, 10)
    return corp.vectors, q, ids, d.astype(np.float32)


def _numbers(case, ids, dists, answered=None):
    x, q = case[:2]
    answered = np.ones(len(q), bool) if answered is None else answered
    return reference.compare(x, q, ids, dists, answered, 10)


def test_exact_answers_pass(case):
    got = _numbers(case, case[2], case[3])
    assert got["recall_miss"] == 0.0 and got["lost"] == 0
    assert got["dist_gap"] < 1e-6
    assert reference.verdict(got, LIMITS)


def test_exact_knn_matches_a_plain_loop(case):
    x, q, ids, d = case
    for i in (0, 17):
        full = ((x.astype(np.float64) - q[i]) ** 2).sum(1)
        assert np.array_equal(np.sort(np.argsort(full)[:10]), np.sort(ids[i]))
        assert np.allclose(np.sort(full)[:10], d[i], rtol=1e-6)


def test_a_wrong_id_fails(case):
    ids = case[2].copy()
    ids[5, 3] = (ids[5, 3] + 1) % len(case[0])
    got = _numbers(case, ids, case[3])
    assert got["dist_gap"] > LIMITS["dist_gap"]
    assert not reference.verdict(got, LIMITS)


def test_a_wrong_distance_fails(case):
    dists = case[3].copy()
    dists[7, 0] *= 1.001
    got = _numbers(case, case[2], dists)
    assert got["recall_miss"] == 0.0
    assert not reference.verdict(got, LIMITS)


def test_far_ids_with_their_true_distances_fail_recall(case):
    x, q, ids, _ = case
    far = np.argsort(-((x[None] - q[:, None]) ** 2).sum(-1), axis=1)[:, :10]
    dists = reference.served_distances(x, q, far).astype(np.float32)
    got = _numbers(case, far, dists)
    assert got["recall_miss"] == 1.0 and got["dist_gap"] < 1e-6
    assert not reference.verdict(got, LIMITS)


def test_a_lost_query_fails(case):
    ids, dists = case[2].copy(), case[3].copy()
    ids[2] = -1
    answered = np.ones(len(ids), bool)
    answered[9] = False
    got = _numbers(case, ids, dists, answered)
    assert got["lost"] == 2
    assert reference.lost(ids, answered) == 2
    assert not reference.verdict(got, LIMITS)


def test_the_bfloat16_control_fails(case):
    x, q = case[:2]
    ids, dists = reference.knn_lower_precision(x, q, 10, block=1024)
    got = _numbers(case, ids, dists)
    assert got["dist_gap"] > 10 * LIMITS["dist_gap"]
    assert not reference.verdict(got, LIMITS)
