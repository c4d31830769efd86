"""Each mix runs at a tiny size on the CPU through its driver's own
functions; the generator's inputs follow the seed and only the seed."""

import numpy as np
import pytest

from chipbench_common import BENCH, ROOT, SEED, SHRINK

import corpus
import harness

MIXES = sorted(p.stem for p in (BENCH / "mixes").glob("*.json"))


@pytest.fixture(scope="module")
def deployment(memo_builds):
    from repro.api import Deployment

    spec = harness.load_spec(ROOT)
    cfg = harness._merge(harness.config(spec, spec["workloads"][0], ROOT),
                         SHRINK["config"])
    corp = corpus.Corpus(cfg["data"])
    return Deployment.from_config(harness.serve_config(cfg),
                                  dataset=corp), corp


@pytest.mark.parametrize("name", MIXES)
def test_mix_runs_a_window_on_the_cpu(name, deployment):
    dep, corp = deployment
    mix = harness._merge(harness.mix({"traffic": name}, ROOT), SHRINK["mix"])
    driver = harness.driver(mix, ROOT).make(dep, corp, mix, SEED)
    try:
        driver.warm_up(1.0)
        rec = driver.window(1.0)
    finally:
        driver.close()
    assert rec.attempted > 0 and rec.answered.all()
    assert rec.ids.shape == (rec.attempted, dep.config.search.k)
    assert (rec.ids >= 0).all() and rec.window_s > 0


def test_every_seed_gets_the_same_batches_in_its_own_order():
    order = harness.driver({"driver": "closed_batches"}, ROOT).batch_order
    one = order(12, 4, SEED)
    first = [one() for _ in range(6)]             # two passes over the pool
    again = order(12, 4, SEED)
    assert all(np.array_equal(a, again()) for a in first)
    fixed = {frozenset(range(s, s + 4)) for s in (0, 4, 8)}
    for p in (first[:3], first[3:]):
        assert {frozenset(b.tolist()) for b in p} == fixed
    other = order(12, 4, SEED + 1)
    others = [other() for _ in range(3)]
    assert {frozenset(b.tolist()) for b in others} == fixed
    assert not all(np.array_equal(a, b) for a, b in zip(first, others))
    with pytest.raises(ValueError):
        order(10, 4, SEED)


DATA = {"generator": "clustered_gaussian", "data_seed": 3, "n": 500,
        "dim": 8, "n_clusters": 4, "cluster_std": 0.35, "center_scale": 0.7,
        "query_noise": 0.5}


@pytest.mark.parametrize("dtype", ["float32", "uint8", "int8"])
def test_the_corpus_follows_its_data_seed_and_queries_the_run_seed(dtype):
    data = dict(DATA, dtype=dtype)
    one = corpus.Corpus(data)
    assert np.array_equal(one.vectors, corpus.Corpus(data).vectors)
    assert not np.array_equal(one.vectors, corpus.Corpus(
        dict(data, data_seed=data["data_seed"] + 1)).vectors)
    q = one.queries(corpus.rng(SEED, "window"), 64)
    assert np.array_equal(q, one.queries(corpus.rng(SEED, "window"), 64))
    assert not np.array_equal(
        q, one.queries(corpus.rng(SEED + 1, "window"), 64))
    assert q.shape == (64, data["dim"]) and q.dtype == np.float32
    if dtype != "float32":
        info = np.iinfo(dtype)
        for x in (q, one.vectors):
            assert np.array_equal(x, np.rint(x))
            assert info.min <= x.min() and x.max() <= info.max
        assert one.vectors.min() == info.min and one.vectors.max() == info.max
    # a query is near, not on, a corpus point
    d = ((one.vectors[None] - q[:8, None]) ** 2).sum(-1).min(1)
    assert (d > 0).all()


def test_an_unknown_generator_or_driver_is_an_error():
    with pytest.raises(KeyError):
        corpus.Corpus(dict(DATA, dtype="float32", generator="no_such"))
    with pytest.raises(KeyError):
        harness.driver({"driver": "no_such"}, ROOT)
