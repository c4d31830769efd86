"""What a profiler sees of the baton search: every device op of the
super-step loop sits in exactly one of ``baton.PHASES`` (read from the
``op_name`` of the compiled program), each stage of a call is a
``baton:<stage>`` host span, and ``stats`` counts the local-advance bodies
and the ADC rows they scored."""

import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core import baton
from repro.data import synth

_LOOP_LEVEL = re.compile(r"/while/(?:body|cond)/")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _phases_by_level(op_name: str) -> list:
    """The ``PHASES`` an ``op_name`` names, per loop level (split at each
    ``while/body`` and ``while/cond``), after its first part, the program's
    own name.  A name inside a nested ``jit(...)`` ends the search: it comes
    from that function's own trace, which JAX may have cached from another
    call site."""
    levels = []
    for level in _LOOP_LEVEL.split(op_name.partition("/")[2]):
        found, nested = [], False
        for part in level.split("/"):
            while (m := _WRAPPED.match(part)):
                if part.startswith("jit("):
                    nested = True
                    break
                part = m.group(1)
            if nested:
                break
            if part in baton.PHASES:
                found.append(part)
        levels.append(found)
        if nested:
            break
    return levels


@pytest.fixture(scope="module")
def tiny():
    ds = synth.make_dataset("deep", n=600, n_queries=16, seed=1)
    idx = baton.build_index(ds.vectors, p=4, r=8, l_build=16, pq_m=8,
                            pq_k=16, head_fraction=0.05, seed=1)
    return idx, ds.queries


def _params(**kw):
    return baton.BatonParams(L=16, W=2, k=5, pool=32, slots=8, pair_cap=2,
                             n_starts=2, **kw)


def test_phases_by_level_reads_each_loop_level():
    name = ("jit(run_supersteps)/exchange/while/body/vmap(frontier)/while/"
            "body/adc/jit(take_along_axis)/merge/gather")
    assert _phases_by_level(name) == [["exchange"], ["frontier"], ["adc"]]
    assert _phases_by_level("jit(f)/vmap()/add") == [[]]


@pytest.mark.parametrize("adc_impl", ["gather", "mxu", "mxu_tiled"])
def test_every_instruction_of_the_superstep_loop_has_one_phase(tiny,
                                                               adc_impl):
    """From the compiled text of ``run_supersteps``: every instruction the
    super-step loop runs names exactly one phase at its own loop level (a
    phase around a loop names the loop's control, the ops inside name their
    own), and every phase is used."""
    idx, queries = tiny
    cfg = _params(adc_impl=adc_impl)
    devs, codebook, _ = baton._initial_devices(idx, queries, cfg)
    text = baton.run_supersteps.lower(
        devs, idx.stacked_shards(), codebook, cfg=cfg, P=idx.p
    ).compile().as_text()
    seen, checked = set(), 0
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if not m or "/while/" not in m.group(1):
            continue
        levels = _phases_by_level(m.group(1))
        named = [p for level in levels for p in level]
        assert named and max(map(len, levels)) == 1, m.group(1)
        seen.add(named[-1])
        checked += 1
    assert checked > 100
    assert seen == set(baton.PHASES)


def test_step_counters_and_the_live_share_of_adc_rows(tiny):
    """``local_steps`` covers every super-step, ``adc_rows`` is its P·S·W·R
    rows a body, and the rows that were live candidates (the queries'
    ``dist_comps - reads``) are a share of them in (0, 1]."""
    idx, queries = tiny
    cfg = _params()
    _, _, stats = baton.run_simulated(idx, queries, cfg)
    r = idx.part_neighbors.shape[-1]
    assert stats["local_steps"] >= stats["n_supersteps"] > 0
    assert stats["adc_rows"] == \
        stats["local_steps"] * idx.p * cfg.slots * cfg.W * r
    live = int(stats["dist_comps"].sum() - stats["reads"].sum())
    assert 0.0 < live / stats["adc_rows"] <= 1.0


def test_each_stage_of_a_call_is_a_numbered_host_span(tiny, tmp_path):
    idx, queries = tiny
    cfg = _params()
    baton.run_simulated(idx, queries, cfg)          # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        baton.run_simulated(idx, queries, cfg)
        baton.run_simulated(idx, queries, cfg)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    calls = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("baton:"):
                    call = dict(ev.stats)["call"]
                    calls.setdefault(int(call), []).append(
                        (ev.start_ns, ev.name[len("baton:"):]))
    stages = ["head", "split", "init", "upload", "supersteps", "collect"]
    assert len(calls) == 2
    for spans in calls.values():
        assert [name for _, name in sorted(spans)] == stages


def test_a_step_left_out_counts_no_local_steps(tiny, monkeypatch):
    """The counts come from the program that ran: a search whose super-step
    program returns the states as they came reports no steps."""
    idx, queries = tiny
    monkeypatch.setattr(baton, "run_supersteps",
                        lambda devs, shard, codebook, cfg, P: (devs, 0))
    _, _, stats = baton.run_simulated(idx, queries, _params())
    assert (stats["n_supersteps"], stats["local_steps"],
            stats["adc_rows"]) == (0, 0, 0)
    assert np.all(stats["hops"] == 0)
