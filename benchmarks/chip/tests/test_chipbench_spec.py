"""BENCHMARK.json and the files it names: every piece is found by name,
and a mix, a metric or a configuration added as files only is picked up."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench_common import BENCH, ROOT, SEED, SHRINK

import corpus
import harness
import reference

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec(ROOT)


def test_every_cell_finds_its_files(spec):
    for c in spec["workloads"]:
        cfg = harness.config(spec, c, ROOT)
        assert cfg["name"] == c["config"]
        assert set(reference.CHECKS) <= set(cfg["limits"])
        assert callable(corpus.load("generators", cfg["data"]["generator"],
                                    BENCH).draw)
        mx = harness.mix(c, ROOT)
        assert callable(harness.driver(mx, ROOT).make)
        for per_layer in (False, True):
            names = [m["name"] for m in
                     harness.metrics_for(spec, c["name"], per_layer)]
            assert names, (c["name"], per_layer)
            for name in names:
                assert callable(harness.reader(name, ROOT))
        e2e = [m["name"] for m in harness.metrics_for(spec, c["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2


def test_names_units_and_links(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {c["name"] for c in spec["workloads"]}
    entries = spec["configs"] + spec["workloads"] + spec["end_to_end"] \
        + spec["per_layer"]
    assert all(NAME.match(e["name"]) for e in entries)
    assert all(UNIT.match(m["unit"]) for m in spec["end_to_end"]
               + spec["per_layer"])
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(spec["paths"][0] + "/")


def test_peaks_table_is_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite", ROOT)["hbm_bytes"] > 0
    with pytest.raises(KeyError):
        harness.peaks("TPU v0 imaginary", ROOT)


def _copy_checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / harness.BENCH_REL,
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


# a driver of a kind of traffic the benchmark lacks: one batch of the
# pool in its stored order, whatever the seconds, with the sizes it served
ONE_BATCH = """
import numpy as np
from traffic import Records, pool


class OneBatch:
    def __init__(self, dep, corpus, mix, seed):
        self.dep, self.q = dep, pool(corpus, mix)[:mix["batch"]]

    def warm_up(self, seconds):
        self.dep.search(self.q)

    def window(self, seconds):
        res = self.dep.search(self.q)
        n = len(self.q)
        return Records(entry="one", window_s=1.0, queries=self.q,
                       ids=res.ids, dists=res.dists,
                       answered=np.ones(n, bool), admitted=np.ones(n, bool),
                       counters={}, extra={"served": n})

    def close(self):
        pass


def make(dep, corpus, mix, seed):
    return OneBatch(dep, corpus, mix, seed)
"""

# a corpus generator the benchmark lacks: points uniform in a cube
UNIFORM = """
import numpy as np


def draw(data, g):
    return g.uniform(-1, 1, size=(data["n"], data["dim"])).astype(np.float32)


def query_noise(data):
    return 0.05
"""


def test_a_driver_a_generator_a_metric_and_a_config_added_as_files_are_picked_up(
        tmp_path, memo_builds):
    """A new kind of traffic, a new corpus in a new dtype, a new metric and
    a new configuration, each added as files (and entries of
    BENCHMARK.json) only, run through the harness with their own
    behaviour."""
    root = _copy_checkout(tmp_path)
    chip = root / harness.BENCH_REL
    (chip / "drivers" / "one_batch.py").write_text(ONE_BATCH)
    (chip / "mixes" / "dummy.json").write_text(json.dumps(
        {"why": "test", "driver": "one_batch", "batch": 24, "pool": 24,
         "compare": 0}))
    (chip / "generators" / "uniform_cube.py").write_text(UNIFORM)
    (chip / "metrics" / "dummy_served.py").write_text(
        "def read(run):\n    return run.records.extra.get('served')\n")
    cfg = json.loads((chip / "configs" / "deep-1m.json").read_text())
    cfg["name"] = "dummy-cfg"
    cfg["data"].update(generator="uniform_cube", dtype="int8", dim=16)
    (chip / "configs" / "dummy-cfg.json").write_text(json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="dummy-cfg",
                                file="benchmarks/chip/configs/dummy-cfg.json"))
    spec["workloads"].append({"name": "dummy-cfg.dummy", "config": "dummy-cfg",
                              "traffic": "dummy", "chips": 1, "why": "test"})
    spec["end_to_end"].append({
        "name": "dummy_served", "unit": "count", "better": "higher",
        "bound": 0.01, "source": "host_clock",
        "workloads": ["dummy-cfg.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    shrink = {"config": SHRINK["config"]}
    out = harness.execute("dummy-cfg.dummy", SEED, 0.5, False, t_start=0.0,
                          root=root, log=lambda m: None, require_chip=False,
                          cache=False, shrink=shrink)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 24
    assert set(out["metrics"]) == {"dummy_served", "setup_s"}
    assert out["metrics"]["dummy_served"]["value"] == 24.0
    stored = corpus.Corpus(harness._merge(cfg, shrink["config"])["data"],
                           chip)
    assert stored.vectors.min() == -128 and stored.vectors.max() == 127
    assert np.array_equal(stored.vectors, np.rint(stored.vectors))


def test_command_exits_nonzero_without_a_chip(tmp_path):
    """On the CPU the command refuses to run and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               TMPDIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "deep-1m.batch", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
