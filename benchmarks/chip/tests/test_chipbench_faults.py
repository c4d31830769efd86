"""A whole run, minus the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have, and true for the sound run."""

import json
import shutil

import jax
import numpy as np
import pytest

from chipbench_common import BENCH, ROOT, SEED, SHRINK

import harness

from repro.core import baton

BATCH = "faults.batch"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose BENCHMARK.json has a cell of the batch mix on the
    first configuration, which the tests shrink."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / harness.BENCH_REL,
                    ignore=shutil.ignore_patterns(".jax_cache", ".index_cache",
                                                  "__pycache__"))
    spec = harness.load_spec(ROOT)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(BATCH)
    spec["workloads"].append({"name": BATCH,
                              "config": spec["configs"][0]["name"],
                              "traffic": "batch", "chips": 1,
                              "why": "fault tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(workload, root, shrink=SHRINK):
    return harness.execute(workload, SEED, 1.0, False, t_start=0.0,
                           root=root, log=lambda m: None, require_chip=False,
                           cache=False, shrink=shrink)


def test_sound_run_is_correct(root, memo_builds):
    out = _run(BATCH, root)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


# --- the batch loop: Deployment.search -> baton.run_simulated --------------

def _unchanged_step(monkeypatch):
    monkeypatch.setattr(baton, "run_supersteps",
                        lambda devs, shard, codebook, cfg, P: (devs, 0))


def _half_batch_left_out(monkeypatch):
    split = baton._split_round_robin

    def half(index, queries, cfg):
        q, qid, st, sd, *rest = split(index, queries, cfg)
        qid = np.where(qid >= len(queries) // 2, -1, qid)
        return (q, qid, st, sd, *rest)

    monkeypatch.setattr(baton, "_split_round_robin", half)


def _exchange_left_out(monkeypatch):
    monkeypatch.setattr(baton, "merge_recv",
                        lambda dev, incoming, cfg, codebook: dev)
    step = baton.run_supersteps.__wrapped__
    # a new function object, so that jax traces it anew with the patch
    monkeypatch.setattr(baton, "run_supersteps", jax.jit(
        lambda devs, shard, codebook, cfg, P: step(devs, shard, codebook,
                                                   cfg, P),
        static_argnames=("cfg", "P")))


def _answer_altered(monkeypatch):
    collect = baton._collect

    def altered(*args):
        ids, dists, stats = collect(*args)
        ids[0, 0] = (ids[0, 0] + 1) % 2000
        return ids, dists, stats

    monkeypatch.setattr(baton, "_collect", altered)


@pytest.mark.parametrize("fault", [_unchanged_step, _half_batch_left_out,
                                   _exchange_left_out, _answer_altered])
def test_batch_fault_is_caught(fault, monkeypatch, root, memo_builds):
    fault(monkeypatch)
    out = _run(BATCH, root)
    assert not out["correct"], out["checks"]
