"""Executable async serving tier (repro.serve_async) + PR satellites.

The ISSUE-7 acceptance battery:

* **Parity** — the tier's (ids, dists) and all five ``STAT_FIELDS``
  counters are bit-identical to ``baton.run_simulated`` (= what
  ``Engine.search`` runs) at *every* worker count: concurrency may
  reorder completions, never answers.
* **Determinism** — one worker, same seed: byte-identical result order
  across runs.
* **Conservation** — under overload every offered arrival is exactly one
  of {completed, rejected}; completed arrivals keep bit-parity.
* **Wire** — the baton round-trips as real bytes (0-d leaves included)
  and the measured message size tracks the modeled ``envelope_bytes``
  within a small fixed header overhead.
* Satellites: the seeded ``diurnal`` arrival generator, the ``ExecSpec``
  config section + ``Deployment.run_exec`` facade, and the bench
  runner's one-line unknown ``--only`` tag error.

The ISSUE-8 micro-batching battery extends it:

* **Batched parity** — bit-identity to the engine at every
  (workers x batch) combination; ``runtime.advance_batch`` advances a
  stacked group leaf-for-leaf identically to sequential
  ``advance_state`` calls.
* **Tiled ADC** — the slot-tiled Pallas kernel (``adc_impl=mxu_tiled``)
  bit-matches the gather reference (and the engine run built on it),
  unlike the dense one-hot route which only matches to float tolerance.
* **Drain/wire mechanics** — ``get_many`` priority, budget and slot-gate
  semantics; multi-baton frame round-trip; coalescing and same-worker
  short-circuit accounting (hand-offs are conserved as
  ``wire_batons + local_handoffs``).
"""

import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

from repro.api import Deployment, EXEC_FIELDS, ExecSpec, ServeConfig
from repro.api.engine import BatonEngine
from repro.cluster import diurnal, make_workload
from repro.core import baton
from repro.core.state import STAT_FIELDS
from repro.serve_async import (AsyncServingTier, decode_baton, decode_frame,
                               encode_baton, encode_frame, runtime)
from repro.serve_async.queues import ThreadInbox

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)  # the benchmarks namespace package


@pytest.fixture(scope="module")
def exec_cfg():
    return baton.BatonParams(L=32, W=4, k=10, pool=128, slots=8)


@pytest.fixture(scope="module")
def engine_result(baton_index, dataset, exec_cfg):
    return baton.run_simulated(baton_index, dataset.queries, exec_cfg)


# ---------------------------------------------------------------------------
# parity / determinism / conservation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_workers", [1, 2, 4])
def test_tier_matches_engine_bitwise(baton_index, dataset, exec_cfg,
                                     engine_result, n_workers):
    ids_e, dists_e, stats_e = engine_result
    with AsyncServingTier(baton_index, exec_cfg,
                          n_workers=n_workers) as tier:
        res = tier.search(dataset.queries)
    assert np.array_equal(res.ids, ids_e)
    assert np.array_equal(res.dists, dists_e)
    got = res.stats_dict()
    for f in STAT_FIELDS:
        assert np.array_equal(got[f], stats_e[f]), f
    # every inter_hops increment crossed a queue as one encoded baton
    assert res.handoffs == int(np.sum(stats_e["inter_hops"]))
    assert res.handoffs > 0


def test_single_worker_order_deterministic(baton_index, dataset, exec_cfg):
    orders = []
    for _ in range(2):
        with AsyncServingTier(baton_index, exec_cfg, n_workers=1) as tier:
            res = tier.search(dataset.queries)
        orders.append(np.argsort(res.done_s, kind="stable"))
    assert np.array_equal(orders[0], orders[1])


def test_overload_conservation(baton_index, dataset, exec_cfg,
                               engine_result):
    ids_e, dists_e, _ = engine_result
    with AsyncServingTier(baton_index, exec_cfg, n_workers=2,
                          slots=4, queue_cap=2) as tier:
        wl = make_workload(len(dataset.queries), 100000.0, 200, "poisson",
                           seed=1)
        res = tier.serve(dataset.queries, wl)
    assert res.offered == 200
    assert res.offered == res.completed + res.rejected
    assert res.rejected > 0          # the flood must overflow queue_cap=2
    assert res.completed > 0
    # rejected rows are sentinel-filled; completed rows keep bit-parity
    ok = res.accepted
    assert np.all(res.ids[~ok] == -1)
    assert np.all(np.isnan(res.latencies_s[~ok]))
    assert np.array_equal(res.ids[ok], ids_e[res.trace_idx[ok]])
    assert np.array_equal(res.dists[ok], dists_e[res.trace_idx[ok]])


@pytest.mark.slow
def test_process_mode_matches_engine(baton_index, dataset, exec_cfg,
                                     engine_result):
    ids_e, dists_e, _ = engine_result
    with AsyncServingTier(baton_index, exec_cfg, n_workers=2,
                          mode="process") as tier:
        res = tier.search(dataset.queries)
    assert np.array_equal(res.ids, ids_e)
    assert np.array_equal(res.dists, dists_e)


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------


def test_wire_round_trip_including_scalars():
    leaves = {
        "query": np.arange(6, dtype=np.float32).reshape(2, 3),
        "qid": np.int32(7),                    # 0-d must stay 0-d
        "home": np.asarray(3, np.int32),
        "pool_ids": np.asarray([1, -1, 5], np.int32),
        "stats": np.asarray([0, 1, 2, 3, 4], np.int64),
    }
    out = decode_baton(encode_baton(leaves))
    assert sorted(out) == sorted(leaves)
    for name, arr in leaves.items():
        assert out[name].shape == np.asarray(arr).shape, name
        assert out[name].dtype == np.asarray(arr).dtype, name
        assert np.array_equal(out[name], arr), name
    assert int(out["qid"]) == 7                # scalar conversion works


def test_wire_rejects_garbage():
    with pytest.raises(ValueError):
        decode_baton(b"nope" + b"\x00" * 16)


def test_measured_wire_size_tracks_envelope(baton_index, exec_cfg):
    with AsyncServingTier(baton_index, exec_cfg, n_workers=1) as tier:
        delta = tier.wire_bytes_per_handoff - tier.envelope_bytes
    # self-describing header/name overhead only — small and bounded
    assert 0 < delta < 512


# ---------------------------------------------------------------------------
# diurnal arrival generator (satellite)
# ---------------------------------------------------------------------------


def test_diurnal_mean_rate_and_determinism():
    wl = diurnal(32, rate_qps=200.0, n=4000, seed=5)
    assert wl.kind == "diurnal"
    assert len(wl.times_s) == 4000
    assert np.all(np.diff(wl.times_s) >= 0)
    mean_rate = len(wl.times_s) / wl.times_s[-1]
    assert 0.8 * 200.0 < mean_rate < 1.25 * 200.0
    wl2 = diurnal(32, rate_qps=200.0, n=4000, seed=5)
    assert np.array_equal(wl.times_s, wl2.times_s)
    assert np.array_equal(wl.trace_idx, wl2.trace_idx)


def test_diurnal_rate_envelope_varies():
    # day_s defaults to n/rate: one full sine period — the busiest
    # quarter-day must see clearly more arrivals than the quietest
    wl = diurnal(8, rate_qps=100.0, n=8000, seed=0, peak_ratio=3.0)
    day = wl.times_s[-1]
    counts = np.histogram(wl.times_s, bins=4, range=(0, day))[0]
    assert counts.max() > 1.5 * counts.min()


def test_diurnal_is_rate_invariant():
    # same seed, same n: the schedule at 2x the rate is the same pattern
    # compressed 2x — what lets sim and exec run "the same day" each at
    # its own operating point
    a = diurnal(16, rate_qps=50.0, n=1000, seed=3)
    b = diurnal(16, rate_qps=100.0, n=1000, seed=3)
    assert np.allclose(a.times_s, 2.0 * b.times_s)
    assert np.array_equal(a.trace_idx, b.trace_idx)


def test_make_workload_wires_diurnal():
    wl = make_workload(16, 100.0, 500, "diurnal", seed=2)
    assert wl.kind == "diurnal"
    assert len(wl.times_s) == 500
    with pytest.raises(ValueError, match="diurnal"):
        make_workload(16, 100.0, 500, "lunar")
    with pytest.raises(ValueError):
        diurnal(16, rate_qps=0.0, n=10)
    with pytest.raises(ValueError):
        diurnal(16, rate_qps=10.0, n=10, peak_ratio=0.5)


# ---------------------------------------------------------------------------
# ExecSpec config section + Deployment.run_exec (satellite)
# ---------------------------------------------------------------------------


def test_exec_spec_validation():
    ExecSpec()                                # defaults are valid
    with pytest.raises(ValueError, match="mode"):
        ExecSpec(mode="fiber")
    with pytest.raises(ValueError, match="arrival"):
        ExecSpec(arrival="lunar")
    with pytest.raises(ValueError, match="workers"):
        ExecSpec(workers=-1)
    with pytest.raises(ValueError, match="queue_cap"):
        ExecSpec(queue_cap=0)
    with pytest.raises(ValueError, match="time_scale"):
        ExecSpec(time_scale=0.0)


def test_serve_config_exec_cross_checks():
    cfg = ServeConfig.from_dict({"exec": {"workers": 2}})
    assert cfg.exec.workers == 2
    rt = ServeConfig.from_json(cfg.to_json())
    assert rt.exec == cfg.exec
    with pytest.raises(ValueError, match="workers"):
        ServeConfig.from_dict({"index": {"p": 4}, "exec": {"workers": 8}})
    with pytest.raises(ValueError, match="baton"):
        ServeConfig.from_dict({"index": {"engine": "exact"},
                               "exec": {"workers": 1}})


def test_run_exec_schema_and_parity(baton_index, dataset):
    cfg = ServeConfig.from_dict({
        "name": "exec-test",
        "search": {"L": 32, "W": 4, "slots": 8},
        "exec": {"workers": 2},
    })
    dep = Deployment.from_parts(cfg, BatonEngine(index=baton_index),
                                dataset)
    out = dep.run_exec(dataset.queries)
    assert tuple(out) == EXEC_FIELDS
    assert out["parity"] is True
    assert out["completed"] == out["offered"] == len(dataset.queries)
    assert out["rejected"] == 0
    assert out["handoffs"] > 0
    assert out["envelope_bytes"] < out["wire_bytes_per_handoff"]


def test_run_exec_refuses_disabled_tier(baton_index, dataset):
    dep = Deployment.from_parts(ServeConfig.from_dict({}),
                                BatonEngine(index=baton_index), dataset)
    with pytest.raises(ValueError, match="exec.workers"):
        dep.run_exec(dataset.queries)


# ---------------------------------------------------------------------------
# bench runner --only validation (satellite)
# ---------------------------------------------------------------------------


def test_run_only_unknown_tag_one_line_error(monkeypatch, capsys):
    from benchmarks.run import main

    monkeypatch.setattr(sys, "argv",
                        ["run.py", "--only", "fig3,nosuchtag"])
    with pytest.raises(SystemExit) as exc:
        main()
    msg = str(exc.value.code)
    assert "unknown suite tag" in msg
    assert "nosuchtag" in msg
    assert "fig3" in msg            # the valid-tag list names real tags
    assert "\n" not in msg          # one line, no traceback


def test_run_exits_nonzero_on_failed_suite(monkeypatch, tmp_path, capsys):
    from benchmarks import run

    def boom():
        raise RuntimeError("suite broke")

    monkeypatch.setattr(run, "ARTIFACTS", str(tmp_path))
    monkeypatch.setattr(run, "_resolve", lambda spec: boom)
    monkeypatch.setattr(sys, "argv", ["run.py", "--only", "fig3"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "fig3_FAILED" in str(exc.value.code)
    assert "fig3_FAILED" in (tmp_path / "bench.csv").read_text()


def test_process_mode_refused_off_cpu(baton_index, exec_cfg, monkeypatch):
    """A process that holds an accelerator cannot hand it to spawned
    workers: process mode is refused up front, naming thread mode."""
    from repro.serve_async import tier as tier_mod

    monkeypatch.setattr(tier_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="mode='thread'"):
        AsyncServingTier(baton_index, exec_cfg, n_workers=2, mode="process")


def test_fig20_suite_registered():
    from benchmarks import figures
    from benchmarks.run import SUITES

    tags = dict(SUITES)
    assert tags["fig20execsim"] == "figures.fig20_exec_vs_sim"
    assert callable(figures.fig20_exec_vs_sim)


# ---------------------------------------------------------------------------
# ISSUE-8: micro-batched parity (workers x batch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_workers,batch",
                         [(1, 4), (1, 8), (2, 4), (2, 8), (4, 4), (4, 8)])
def test_tier_batched_matches_engine_bitwise(baton_index, dataset, exec_cfg,
                                             engine_result, n_workers,
                                             batch):
    ids_e, dists_e, stats_e = engine_result
    with AsyncServingTier(baton_index, exec_cfg, n_workers=n_workers,
                          batch=batch) as tier:
        res = tier.search(dataset.queries)
    assert res.batch == batch
    assert np.array_equal(res.ids, ids_e)
    assert np.array_equal(res.dists, dists_e)
    got = res.stats_dict()
    for f in STAT_FIELDS:
        assert np.array_equal(got[f], stats_e[f]), f
    # conservation: every inter_hops increment crossed a queue exactly
    # once — inside a serialized frame or as a same-worker short-circuit
    assert res.handoffs == res.wire_batons + res.local_handoffs
    assert res.advance_calls > 0


@pytest.mark.slow
def test_process_mode_batched_matches_engine(baton_index, dataset, exec_cfg,
                                             engine_result):
    ids_e, dists_e, _ = engine_result
    with AsyncServingTier(baton_index, exec_cfg, n_workers=2, batch=8,
                          mode="process") as tier:
        res = tier.search(dataset.queries)
    assert np.array_equal(res.ids, ids_e)
    assert np.array_equal(res.dists, dists_e)
    assert res.handoffs == res.wire_batons + res.local_handoffs


def test_advance_batch_equals_sequential(baton_index, dataset, exec_cfg):
    import jax.numpy as jnp

    from repro.core import pq

    n = 5
    queries = np.asarray(dataset.queries[:n], np.float32)
    starts, start_d = baton_index.head_starts(queries, exec_cfg.n_starts)
    luts = pq.build_lut(jnp.asarray(baton_index.codebook),
                        jnp.asarray(queries))
    states = [
        runtime.seed_state(jnp.asarray(queries[i]), jnp.asarray(starts[i]),
                           jnp.asarray(start_d[i]), luts[i], 0, i,
                           exec_cfg.L, exec_cfg.pool)
        for i in range(n)
    ]
    shard = runtime.partition_shard(baton_index, 0)
    sts, done_b, dest_b = runtime.advance_batch(
        runtime.stack_states(states), shard, 0, exec_cfg.W,
        exec_cfg.max_local_steps)
    unstacked = runtime.unstack_states(sts, n)
    done_b, dest_b = np.asarray(done_b), np.asarray(dest_b)
    for i, st in enumerate(states):
        st1, done1, dest1 = runtime.advance_state(
            st, shard, 0, exec_cfg.W, exec_cfg.max_local_steps)
        assert bool(done1) == bool(done_b[i])
        assert int(dest1) == int(dest_b[i])
        la = jax.tree.leaves(jax.device_get(st1))
        lb = jax.tree.leaves(unstacked[i])
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_single_worker_all_handoffs_local(baton_index, dataset, exec_cfg):
    with AsyncServingTier(baton_index, exec_cfg, n_workers=1,
                          batch=4) as tier:
        res = tier.search(dataset.queries)
    assert res.handoffs > 0
    assert res.wire_frames == 0 and res.wire_batons == 0
    assert res.wire_bytes == 0
    assert res.local_handoffs == res.handoffs


def test_coalescing_packs_multiple_batons_per_frame(baton_index, dataset,
                                                    exec_cfg, engine_result):
    ids_e, dists_e, _ = engine_result
    # deep slots so drains fill the batch and same-destination hand-offs
    # pile up within one loop iteration
    with AsyncServingTier(baton_index, exec_cfg, n_workers=2, batch=8,
                          slots=16) as tier:
        res = tier.search(dataset.queries)
    assert np.array_equal(res.ids, ids_e)
    assert np.array_equal(res.dists, dists_e)
    assert res.wire_batons > 0 and res.wire_bytes > 0
    assert res.wire_frames < res.wire_batons   # >=1 frame was coalesced
    assert res.handoffs == res.wire_batons + res.local_handoffs


def test_tier_rejects_bad_batch(baton_index, exec_cfg):
    with pytest.raises(ValueError, match="batch"):
        AsyncServingTier(baton_index, exec_cfg, n_workers=1, batch=0)


# ---------------------------------------------------------------------------
# ISSUE-8: slot-tiled ADC kernel (adc_impl="mxu_tiled")
# ---------------------------------------------------------------------------


def test_tiled_adc_bitmatches_gather():
    import jax.numpy as jnp

    from repro.core.pq import adc_slots
    from repro.kernels.pq_adc.ops import pq_adc_slots, pq_adc_slots_tiled

    rng = np.random.default_rng(0)
    for s, c, m, k in ((8, 64, 16, 128), (6, 70, 8, 64), (1, 32, 4, 16)):
        luts = jnp.asarray(rng.normal(size=(s, m, k)).astype(np.float32))
        codes = jnp.asarray(
            rng.integers(0, k, size=(s, c, m)).astype(np.int32))
        got = pq_adc_slots_tiled(luts, codes)
        # bit-identical to the gather (the tiled kernel emits exact
        # per-subspace partials; the caller reduces in gather order) ...
        assert jnp.array_equal(adc_slots(luts, codes), got), (s, c, m, k)
        # ... and numerically equal to the dense one-hot route, whose
        # different accumulation order only matches to float tolerance
        dense = pq_adc_slots(luts, codes)
        assert np.allclose(np.asarray(dense), np.asarray(got), atol=1e-4)


def test_tiled_adc_engine_and_tier_parity(baton_index, dataset, exec_cfg,
                                          engine_result):
    ids_e, dists_e, stats_e = engine_result
    cfg = dataclasses.replace(exec_cfg, adc_impl="mxu_tiled")
    ids, dists, stats = baton.run_simulated(baton_index, dataset.queries,
                                            cfg)
    assert np.array_equal(np.asarray(ids), ids_e)
    assert np.array_equal(np.asarray(dists), dists_e)
    for f in STAT_FIELDS:
        assert np.array_equal(np.asarray(stats[f]), stats_e[f]), f
    # and through the batched exec tier: the kernel rides advance_batch
    with AsyncServingTier(baton_index, cfg, n_workers=2, batch=4) as tier:
        res = tier.search(dataset.queries)
    assert np.array_equal(res.ids, ids_e)
    assert np.array_equal(res.dists, dists_e)


def test_baton_params_rejects_unknown_adc_impl():
    with pytest.raises(ValueError, match="adc_impl"):
        baton.BatonParams(adc_impl="dense")


# ---------------------------------------------------------------------------
# ISSUE-8: get_many drain semantics + multi-baton frames
# ---------------------------------------------------------------------------


def test_get_many_priority_then_budgeted_admissions():
    ib = ThreadInbox(slots=8, admit_headroom=2, queue_cap=16)
    for i in range(3):
        assert ib.offer_admit(("a", i))
    ib.push_handoff(("frame", "f0"), n=2, nbytes=100)
    ib.push_handoff(("local", "l0"), n=1, local=True)
    got = ib.get_many(4)
    # hand-offs first (the 2-baton frame + the short-circuit), then
    # admissions fill what's left of the budget
    assert [k for k, _ in got] == ["handoff", "handoff", "admit"]
    assert got[0][1] == ("frame", "f0")
    assert got[1][1] == ("local", "l0")
    assert ib.resident == 4
    c = ib.counter_snapshot()
    assert c["wire_frames"] == 1 and c["wire_batons"] == 2
    assert c["wire_bytes"] == 100 and c["local_batons"] == 1


def test_get_many_oversize_frame_taken_whole():
    ib = ThreadInbox(slots=8, admit_headroom=2, queue_cap=16)
    ib.push_handoff(("frame", "big"), n=5, nbytes=1)
    ib.push_handoff(("frame", "next"), n=1, nbytes=1)
    # batons inside one message are indivisible: the 5-baton frame blows
    # the budget but is taken whole, and nothing else rides along
    got = ib.get_many(2)
    assert [item for _, item in got] == [("frame", "big")]


def test_get_many_slot_gate_blocks_admissions_not_handoffs():
    ib = ThreadInbox(slots=4, admit_headroom=2, queue_cap=16)  # usable=2
    for i in range(6):
        assert ib.offer_admit(i)
    got = ib.get_many(8)
    assert [k for k, _ in got] == ["admit", "admit"]   # gate, not budget
    assert ib.resident == 2
    ib.push_handoff(("local", "x"), n=1, local=True)   # ignores the gate
    got2 = ib.get_many(8)
    assert [k for k, _ in got2] == ["handoff"]
    for _ in range(3):
        ib.release()
    got3 = ib.get_many(8)                              # slots freed
    assert [k for k, _ in got3] == ["admit", "admit"]


def test_get_many_drains_then_stops():
    ib = ThreadInbox(slots=8, admit_headroom=2, queue_cap=4)
    ib.push_handoff(("local", "x"), n=1, local=True)
    ib.stop()
    assert ib.get_many(4) == [("handoff", ("local", "x"))]
    assert ib.get_many(4) is None
    assert ib.get() is None


def test_frame_round_trip_and_rejects_garbage():
    records = [(0, 3, b"abc"), (7, 1, b""), (2, 2, b"\x00" * 5)]
    assert decode_frame(encode_frame(records)) == records
    with pytest.raises(ValueError):
        decode_frame(b"XXXX\x01\x00\x00")
    with pytest.raises(ValueError, match="length"):
        decode_frame(encode_frame(records) + b"junk")


def test_exec_spec_batch_validation_and_run_exec(baton_index, dataset):
    assert ExecSpec(batch=4).batch == 4
    with pytest.raises(ValueError, match="batch"):
        ExecSpec(batch=0)
    cfg = ServeConfig.from_dict({
        "name": "exec-batch-test",
        "search": {"L": 32, "W": 4, "slots": 8},
        "exec": {"workers": 2, "batch": 8},
    })
    dep = Deployment.from_parts(cfg, BatonEngine(index=baton_index),
                                dataset)
    out = dep.run_exec(dataset.queries)
    assert tuple(out) == EXEC_FIELDS
    assert out["parity"] is True
    assert out["batch"] == 8
    assert out["advance_calls"] > 0
    assert out["wire_batons"] + out["local_handoffs"] == out["handoffs"]


# ---------------------------------------------------------------------------
# ISSUE-9: close() is atomic under concurrent callers (the lock-discipline
# finding the static analyzer surfaced: unguarded check-then-act on _closed)
# ---------------------------------------------------------------------------


def test_concurrent_close_runs_teardown_once(baton_index, exec_cfg,
                                             monkeypatch):
    import threading

    tier = AsyncServingTier(baton_index, exec_cfg, n_workers=2)
    stops = []
    orig_stop = ThreadInbox.stop

    def counting_stop(self):
        stops.append(self)
        return orig_stop(self)

    monkeypatch.setattr(ThreadInbox, "stop", counting_stop)
    threads = [threading.Thread(target=tier.close) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # exactly one closer won the race: each inbox stopped once, not 8x
    assert len(stops) == len(tier._inboxes)
    assert all(not w.is_alive() for w in tier._workers)
    tier.close()                               # idempotent afterwards
    assert len(stops) == len(tier._inboxes)
    with pytest.raises(RuntimeError, match="closed"):
        tier.search(np.zeros((1, baton_index.dim), np.float32))


def test_fig21_and_advbatch_suites_registered():
    from benchmarks import bench_kernels, figures
    from benchmarks.run import SUITES

    tags = dict(SUITES)
    assert tags["fig21batch"] == "figures.fig21_batch_sweep"
    assert tags["advbatch"] == "bench_kernels.advance_batch_rows"
    assert callable(figures.fig21_batch_sweep)
    assert callable(bench_kernels.advance_batch_rows)
