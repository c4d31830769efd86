"""The trace reduction, checked on a small trace recorded on a v5e by
``data/record_trace.py``: three batches of five 2048x2048 matmuls, each
followed by a 50 ms sleep in which the device idles."""

from pathlib import Path

import numpy as np
import pytest

import chipbench_common  # noqa: F401  (the benchmark on the path)
import traces

SMALL = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return traces.reduce(str(SMALL))


def test_window_and_spans(small):
    assert len(small.spans_named("batch")) == 3
    assert len(small.spans_named("sleep")) == 3
    assert 0.15 < small.window_s < 0.17
    assert len(small.busy) == 1          # one chip


def test_busy_time_is_the_matmuls(small):
    # fifteen matmuls of about 0.1 ms each, some before the host's window
    # span opens (the device clock runs early)
    assert 0.5e-3 < small.busy_s() < 2.5e-3
    assert small.busy_s(0, 1e12) == pytest.approx(
        sum(s for _, s in small.top_ops()), rel=1e-9)


def test_top_op_is_the_matmul_fusion_by_self_time(small):
    name, seconds = small.top_ops()[0]
    assert name == "jit__lambda/fusion fusion f32[2048,2048]"
    assert 1.0e-3 < seconds < 2.0e-3


def test_idle_gaps_are_named_by_the_host_span(small):
    gaps = small.idle_gaps()
    assert [g[0] for g in gaps[:3]] == ["sleep"] * 3
    assert all(0.045 < g[1] < 0.06 for g in gaps[:3])
    assert sum(g[1] for g in gaps) <= small.window_s


def test_nested_events_keep_their_own_time():
    events = [(0.0, 10.0, "w"), (1.0, 4.0, "a"), (5.0, 9.0, "b"),
              (6.0, 7.0, "c"), (12.0, 13.0, "d")]
    assert traces._self_times(events) == [3.0, 3.0, 3.0, 1.0, 1.0]
    merged = traces._merge(np.asarray([(s, e) for s, e, _ in events]))
    assert merged.tolist() == [[0.0, 10.0], [12.0, 13.0]]


def test_op_names_drop_layouts_and_operands():
    text = ("%fusion.604 = f32[3407872]{0:T(1024)S(1)} fusion(f32[8,32,26,256]"
            "{3,2,1,0:T(8,128)S(1)} %custom-call), kind=kLoop")
    assert traces._op_name(text, "jit_run_supersteps(123)") == \
        "jit_run_supersteps/fusion.604 fusion f32[3407872]"
    text = "%while.2 = (f32[8]{0}, s32[]{:T(128)}) while((f32[8]{0}) %t)"
    assert traces._op_name(text, "jit_f(1)") == "jit_f/while.2 while (...)"
