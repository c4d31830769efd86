"""What the reduction and the metric readers read from the small trace that
``data/record_trace.py`` recorded on a v5e, pinned: a change to the program
or to the benchmark that moves one of these numbers moves what the
accepted cells measure."""

from pathlib import Path

import numpy as np
import pytest

from chipbench_common import ROOT

import harness
import traces
import traffic

SMALL = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return traces.reduce(str(SMALL))


def _records_of_three_batches(n=48):
    """Records a window of three 16-query batches could leave."""
    return traffic.Records(
        entry="search", window_s=0.15, queries=np.zeros((n, 4), np.float32),
        ids=np.zeros((n, 2), np.int32), dists=np.zeros((n, 2), np.float32),
        answered=np.ones(n, bool), admitted=np.ones(n, bool),
        counters={k: np.arange(n, dtype=np.int64) % 5 for k in
                  ("hops", "inter_hops", "dist_comps", "reads",
                   "lut_builds")},
        batches=[{"t0": 0.05 * i, "t1": 0.05 * (i + 1), "n": 16,
                  "n_supersteps": s} for i, s in enumerate((11, 12, 14))])


@pytest.mark.parametrize("name,value", [
    ("qps", 320.0),
    ("setup_s", 12.5),
    ("host_ms_per_batch", 1.8010336666666669),
    ("device_ms_per_query", 0.026173916666666668),
    ("supersteps_per_batch", 12.333333333333334),
    ("inter_hops_per_query", 1.9375),
])
def test_readers_read_what_they_read(small, name, value):
    run = harness.Run(records=_records_of_three_batches(), setup_s=12.5,
                      trace=small)
    assert harness.reader(name, ROOT)(run) == pytest.approx(value, rel=1e-12)


def test_small_trace_reduces_as_it_did(small):
    assert small.window == (46104047.0, 202660215.0)
    assert small.busy_s() == pytest.approx(0.001256348, rel=1e-12)
    ops = small.top_ops()
    assert [n for n, _ in ops] == [
        "jit__lambda/fusion fusion f32[2048,2048]",
        "jit__lambda/copy-done copy-done f32[2048,2048]",
        "jit__lambda/copy-start copy-start (...)"]
    assert [t for _, t in ops] == pytest.approx(
        [0.001372677, 0.000317366, 1.95e-07], rel=1e-9)
    gaps = small.idle_gaps()
    assert [n for n, _ in gaps] == ["sleep"] * 10
    assert [t for _, t in gaps] == pytest.approx([
        0.052422454, 0.051092236, 0.050812849, 0.000173132, 0.00015128,
        0.00014451, 0.000139115, 0.000123635, 8.3747e-05, 6.3012e-05],
        rel=1e-9)
