"""The index cache: keyed by the configuration and the program's build
sources, it loads what an earlier run built and rebuilds on a new key."""

import json

import numpy as np

from chipbench_common import ROOT, SHRINK

import corpus
import harness


def _tiny_config():
    spec = harness.load_spec(ROOT)
    cfg = harness.config(spec, spec["workloads"][0], ROOT)
    return harness._merge(cfg, dict(SHRINK["config"],
                                    data={"n": 1200}))


def test_the_key_follows_the_configuration_and_the_build_sources(tmp_path):
    cfg = _tiny_config()
    src = tmp_path / "src" / "repro" / "core"
    src.mkdir(parents=True)
    (src / "vamana.py").write_text("R = 32\n")
    key = harness.index_dir(cfg, tmp_path)
    assert key == harness.index_dir(json.loads(json.dumps(cfg)), tmp_path)
    assert key.parent == tmp_path / harness.BENCH_REL / ".index_cache"
    assert harness.index_dir(harness._merge(cfg, {"index": {"r": 12}}),
                             tmp_path) != key
    assert harness.index_dir(harness._merge(cfg, {"data": {"data_seed": 5}}),
                             tmp_path) != key
    (src / "vamana.py").write_text("R = 64\n")
    assert harness.index_dir(cfg, tmp_path) != key


def test_a_run_loads_what_the_first_built_and_a_new_key_rebuilds(tmp_path):
    from repro.api import Deployment

    cfg = _tiny_config()
    corp = corpus.Corpus(cfg["data"])

    def deploy(c):
        return Deployment.from_config(
            harness.serve_config(c), dataset=corp,
            index_cache=str(harness.index_dir(c, tmp_path)))

    first = deploy(cfg)
    assert first.build_s                      # built, and kept
    again = deploy(cfg)
    assert not again.build_s                  # loaded
    for name in ("part_vectors", "part_neighbors", "codes", "codebook"):
        assert np.array_equal(getattr(first.index, name),
                              getattr(again.index, name))
    other = deploy(harness._merge(cfg, {"index": {"r": 12}}))
    assert other.build_s                      # a new key builds
