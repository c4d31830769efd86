"""SPMD (shard_map) equivalence: the multi-device path must produce the same
results as the single-host simulated path.

Runs in subprocesses because the host-platform device-count override must not
leak into other tests (jax locks device count at first backend init).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.data import synth
    from repro.core import ref, baton

    ds = synth.make_dataset("deep", n=1200, n_queries=24, seed=1)
    idx = baton.build_index(ds.vectors, p=8, r=16, l_build=32, pq_m=16,
                            pq_k=128, head_fraction=0.03, seed=1)
    cfg = baton.BatonParams(L=32, W=4, k=10, pool=128, slots=16, pair_cap=4,
                            n_starts=4)
    ids_sim, d_sim, stats_sim = baton.run_simulated(idx, ds.queries, cfg)

    mesh = jax.make_mesh((8,), ("part",))
    ids_spmd, d_spmd, stats_spmd = baton.run_spmd(idx, ds.queries, cfg, mesh)
    assert stats_spmd["part_device"] == list(range(8)), stats_spmd
    assert stats_spmd["delivered"] == 1.0, stats_spmd["delivered"]
    assert np.array_equal(ids_sim, ids_spmd), "sim/spmd mismatch"
    assert np.array_equal(d_sim, d_spmd), "sim/spmd distances differ"
    rec = ref.recall_at_k(ids_spmd, ds.gt, 10)
    assert rec > 0.8, rec
    print("SPMD-EQUIV-OK", rec)
    """
)


@pytest.mark.slow
def test_spmd_matches_simulation():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True,
        text=True, timeout=1800,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "SPMD-EQUIV-OK" in r.stdout


_COUNTS_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.data import synth
    from repro.core import baton

    ds = synth.make_dataset("deep", n=600, n_queries=16, seed=1)
    idx = baton.build_index(ds.vectors, p=4, r=8, l_build=16, pq_m=8,
                            pq_k=16, head_fraction=0.05, seed=1)
    cfg = baton.BatonParams(L=16, W=2, k=5, pool=32, slots=8, pair_cap=2,
                            n_starts=2)
    _, _, sim = baton.run_simulated(idx, ds.queries, cfg)
    mesh = jax.make_mesh((4,), ("part",))
    _, _, spmd = baton.run_spmd(idx, ds.queries, cfg, mesh)
    keys = ("n_supersteps", "local_steps", "adc_rows")
    counts = {k: (sim[k], spmd[k]) for k in keys}
    assert sim["n_supersteps"] > 0, counts
    assert all(a == b for a, b in counts.values()), counts
    print("COUNTS-OK", counts)
    """
)


def test_spmd_counts_its_steps_as_the_simulation_does():
    """``run_spmd`` on four virtual CPU devices returns the super-step and
    local-step counts (and so the ADC rows) that ``run_simulated`` does."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", _COUNTS_SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COUNTS-OK" in r.stdout
