"""Shared benchmark substrate: cached index builds + Deployment factories.

Scale knobs via env: BENCH_N (points), BENCH_Q (queries), BENCH_P (servers).
Graphs and indices are built once per process and kept in memory;
the global graph + PQ are shared between BatANN and ScatterGather (the
paper builds both over the same partitioning method [12]).  Index
construction routes through the ``repro.api`` engines; the figure functions
consume :func:`baton_deployment` / :func:`sg_deployment` — a cached index
wrapped in a ``repro.api.Deployment`` under a per-variant ``ServeConfig``.
"""

from __future__ import annotations

import os

import numpy as np

from repro import api
from repro.core import baton, partition as part_mod, ref, scatter_gather, vamana
from repro.data import synth

BENCH_N = int(os.environ.get("BENCH_N", 20000))
BENCH_Q = int(os.environ.get("BENCH_Q", 256))
BENCH_P = int(os.environ.get("BENCH_P", 8))
DATASET = os.environ.get("BENCH_DATASET", "deep")
R = int(os.environ.get("BENCH_R", 32))


def dataset() -> synth.Dataset:
    return synth.make_dataset(DATASET, n=BENCH_N, n_queries=BENCH_Q, seed=0)


# built graphs, assignments and indices, kept for the life of the process
_INDEX_CACHE: dict = {}


def global_graph(ds) -> vamana.VamanaGraph:
    if "graph" not in _INDEX_CACHE:
        knn = ref.brute_force_knn(ds.vectors, ds.vectors, 17)[:, 1:]
        _INDEX_CACHE["graph"] = vamana.build_from_knn(ds.vectors, knn, r=R,
                                                      alpha=1.2)
    return _INDEX_CACHE["graph"]


def assignment(g, p: int) -> np.ndarray:
    key = ("assign", p)
    if key not in _INDEX_CACHE:
        _INDEX_CACHE[key] = part_mod.ldg_partition(g.neighbors, p, passes=3,
                                                   seed=0)
    return _INDEX_CACHE[key]


def _bench_index_spec(engine: str, p: int) -> api.IndexSpec:
    return api.IndexSpec(engine=engine, p=p, r=R, knn_k=17, pq_m=24,
                         pq_k=256, head_fraction=0.01, seed=0)


def baton_index(p: int | None = None) -> baton.BatonIndex:
    p = p or BENCH_P
    key = ("baton", p)
    if key not in _INDEX_CACHE:
        ds = dataset()
        g = global_graph(ds)
        a = assignment(g, p)
        idx = api.BatonEngine().build(ds, _bench_index_spec("baton", p),
                                      graph=g, assign=a)
        _INDEX_CACHE[key] = (ds, idx)
    return _INDEX_CACHE[key]


def sg_index(p: int | None = None) -> scatter_gather.ScatterGatherIndex:
    p = p or BENCH_P
    key = ("sg", p)
    if key not in _INDEX_CACHE:
        ds = dataset()
        g = global_graph(ds)
        a = assignment(g, p)
        # per-partition graphs with the same fast builder (same quality)
        idx = api.ScatterGatherEngine().build(
            ds, _bench_index_spec("scatter_gather", p), graph=g, assign=a)
        _INDEX_CACHE[key] = (ds, idx)
    return _INDEX_CACHE[key]


# ---------------------------------------------------------------------------
# Deployment factories: cached index + per-variant ServeConfig
# ---------------------------------------------------------------------------


def _bench_config(engine: str, p: int, **search) -> api.ServeConfig:
    return api.ServeConfig(
        name=f"bench-{engine}-p{p}",
        data=api.DataSpec(name=DATASET, n=BENCH_N, n_queries=BENCH_Q),
        index=_bench_index_spec(engine, p),
        search=api.SearchParams(**search),
    )


def baton_deployment(p: int | None = None, **search) -> api.Deployment:
    """The cached baton index under a ServeConfig search variant."""
    p = p or BENCH_P
    ds, idx = baton_index(p)
    return api.Deployment.from_parts(
        _bench_config("baton", p, **search), api.BatonEngine(index=idx), ds)


def sg_deployment(p: int | None = None, **search) -> api.Deployment:
    """The cached scatter-gather index under a ServeConfig search variant."""
    p = p or BENCH_P
    ds, idx = sg_index(p)
    return api.Deployment.from_parts(
        _bench_config("scatter_gather", p, **search),
        api.ScatterGatherEngine(index=idx), ds)


# ---------------------------------------------------------------------------
# scale knobs + sweep helpers (the modeled-QPS/latency arithmetic lives in
# repro.api.engine — Engine.model / Engine.cluster_traces — not here)
# ---------------------------------------------------------------------------


# event-simulator scale knobs (fig9_sim / fig13): arrivals per simulated
# rate point and per saturation-search probe
SIM_ARRIVALS = int(os.environ.get("BENCH_SIM_ARRIVALS", 5000))
SIM_SAT_ARRIVALS = int(os.environ.get("BENCH_SIM_SAT_ARRIVALS", 800))

# executable-tier scale knobs (fig20): real serve_async workers, and
# wall-clock arrivals injected at the sweep's highest rate point (lower
# points are scaled down proportionally so every point costs about the
# same wall time)
EXEC_WORKERS = int(os.environ.get("BENCH_EXEC_WORKERS", 2))
EXEC_ARRIVALS = int(os.environ.get("BENCH_EXEC_ARRIVALS", 72))


def recall_at_095(l_values, recalls, values):
    """Interpolate `values` at recall 0.95 along the L sweep."""
    recalls = np.asarray(recalls, float)
    values = np.asarray(values, float)
    if recalls.max() < 0.95:
        return float(values[-1])
    if recalls.min() >= 0.95:
        return float(values[0])
    i = int(np.searchsorted(recalls, 0.95))
    r0, r1 = recalls[i - 1], recalls[i]
    w = (0.95 - r0) / max(r1 - r0, 1e-9)
    return float(values[i - 1] * (1 - w) + values[i] * w)
