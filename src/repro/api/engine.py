"""The ``Engine`` protocol: one uniform surface over the repo's search engines.

BatANN's headline claims are *comparative* — baton vs the SPANN-style
scatter-gather baseline at matched recall — so the two engines (plus a
brute-force oracle) sit behind a single protocol:

* ``build(dataset, IndexSpec) -> index`` — construct the engine's index
  (or ``attach`` a prebuilt one, e.g. the benchmarks' cached indices);
* ``search(queries, SearchParams) -> SearchResult`` — run the engine and
  return ids/dists plus a *uniform* per-query stats dict (every engine
  reports the ``STAT_KEYS`` counters; engine-specific extras ride along);
* ``model(stats, params, dim)`` — the engine's closed-form QPS/latency
  through the calibrated :class:`repro.io_sim.disk.CostModel`;
* ``cluster_traces(stats, params, dim)`` — replayable per-query traces for
  the discrete-event cluster simulator (``repro.cluster``);
* ``index_state() / load_index(tree, meta)`` — the array tree + scalar
  metadata used by ``Deployment.save``/``load`` (checkpoint/ckpt.py).

Every adapter is a thin veneer over the legacy module — ``BatonEngine``
over ``core.baton``, ``ScatterGatherEngine`` over ``core.scatter_gather``
— with *bit-identical* outputs (pinned by tests/test_api.py), so swapping
engines in a :class:`repro.api.Deployment` is a one-line config change.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Protocol, runtime_checkable

import numpy as np

from repro.core import baton, ref, scatter_gather, vamana
from repro.core.state import envelope_bytes
from repro.io_sim.disk import DEFAULT as COST, CostModel

# the uniform per-query counter schema every engine's stats dict carries
STAT_KEYS = ("hops", "inter_hops", "dist_comps", "reads", "lut_builds")

# scatter/gather message sizes of the baseline (paper §6.5 accounting)
SG_SCATTER_BYTES = 512


@dataclasses.dataclass
class SearchResult:
    """Uniform search output: ids/dists plus the engine's stats dict.

    ``stats`` always contains the ``STAT_KEYS`` per-query counter arrays;
    engines may add extras (baton: ``trace``/``n_supersteps``/
    ``local_steps``/``adc_rows``/``delivered``;
    scatter-gather: ``max_part_hops`` and per-partition branch counters).
    """

    ids: np.ndarray         # (B, k) int32 global ids
    dists: np.ndarray       # (B, k) float32
    stats: dict
    wall_s: float = 0.0

    def counters(self) -> dict:
        """Mean per-query value of each uniform counter."""
        return {k: float(np.mean(self.stats[k])) for k in STAT_KEYS}


def _vectors_of(dataset) -> np.ndarray:
    """Accept a synth.Dataset or a bare (N, d) array."""
    return np.ascontiguousarray(getattr(dataset, "vectors", dataset),
                                np.float32)


def _build_graph(vectors: np.ndarray, spec, build_s: dict) -> vamana.VamanaGraph:
    """Global graph per ``IndexSpec.graph_mode`` (see configs.batann_serve);
    records the seconds of each step in ``build_s``."""
    if spec.graph_mode == "knn":
        t0 = time.perf_counter()
        knn = ref.brute_force_knn(vectors, vectors, spec.knn_k)[:, 1:]
        build_s["knn"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        g = vamana.build_from_knn(vectors, knn, r=spec.r, alpha=spec.alpha)
        build_s["graph"] = time.perf_counter() - t0
        return g
    if spec.graph_mode == "vamana":
        t0 = time.perf_counter()
        g = vamana.build(vectors, r=spec.r, l_build=spec.l_build,
                         alpha=spec.alpha, seed=spec.seed)
        build_s["graph"] = time.perf_counter() - t0
        return g
    raise ValueError(f"graph_mode must be knn|vamana: {spec.graph_mode}")


@runtime_checkable
class Engine(Protocol):
    """Structural protocol — any object with these methods is an Engine."""

    name: str

    def build(self, dataset, spec): ...

    def attach(self, index): ...

    def search(self, queries, params) -> SearchResult: ...

    def model(self, stats: dict, params, dim: int) -> tuple[float, float]: ...

    def cluster_traces(self, stats: dict, params, dim: int) -> list: ...

    def index_state(self) -> tuple[dict, dict]: ...

    def load_index(self, tree: dict, meta: dict): ...


class BatonEngine:
    """The paper's engine: distributed state-passing search (core.baton)."""

    name = "baton"
    has_traces = True

    def __init__(self, index: "baton.BatonIndex | None" = None,
                 cost: CostModel = COST):
        self.index = index
        self.cost = cost

    # --- build / attach ----------------------------------------------------
    def build(self, dataset, spec, graph=None, assign=None):
        vectors = _vectors_of(dataset)
        build_s = {}
        if graph is None and spec.graph_mode == "knn":
            graph = _build_graph(vectors, spec, build_s)
        self.index = baton.build_index(
            vectors, p=spec.p, r=spec.r, l_build=spec.l_build,
            alpha=spec.alpha, pq_m=spec.pq_m, pq_k=spec.pq_k,
            head_fraction=spec.head_fraction, partitioner=spec.partitioner,
            seed=spec.seed, graph=graph, codes_mode=spec.codes_mode,
            assign=assign,
        )
        self.index.build_s = {**build_s, **self.index.build_s}
        return self.index

    def attach(self, index):
        self.index = index
        return self

    # --- search ------------------------------------------------------------
    def baton_params(self, sp) -> baton.BatonParams:
        return baton.BatonParams(
            L=sp.L, W=sp.W, k=sp.k, pool=sp.pool, slots=sp.slots,
            pair_cap=sp.pair_cap, result_cap=sp.result_cap,
            n_starts=sp.n_starts, ship_lut=sp.ship_lut,
            lut_wire_dtype=sp.lut_wire_dtype, lazy_queue_lut=sp.lazy_queue_lut,
            fused=sp.fused, adc_impl=sp.adc_impl, merge_impl=sp.merge_impl,
        )

    def search(self, queries, params) -> SearchResult:
        t0 = time.time()
        ids, dists, stats = baton.run_simulated(
            self.index, np.asarray(queries, np.float32),
            self.baton_params(params),
            sector_codes=self.index.part_nbr_codes is not None,
        )
        return SearchResult(ids=ids, dists=dists, stats=stats,
                            wall_s=time.time() - t0)

    # --- cost model --------------------------------------------------------
    def envelope_bytes(self, dim: int, params) -> int:
        pq_m, pq_k = self.index.codebook.shape[:2]
        return envelope_bytes(dim, params.L, params.pool, m=pq_m, k_pq=pq_k,
                              ship_lut=params.ship_lut,
                              lut_dtype=params.lut_wire_dtype)

    def model(self, stats: dict, params, dim: int) -> tuple[float, float]:
        env = self.envelope_bytes(dim, params)
        luts = float(np.mean(stats.get("lut_builds", 0.0)))
        qps = self.cost.cluster_qps(
            n_servers=self.index.p,
            reads_per_query=float(np.mean(stats["reads"])),
            dist_comps_per_query=float(np.mean(stats["dist_comps"])),
            inter_hops_per_query=float(np.mean(stats["inter_hops"])),
            envelope_bytes=env,
            lut_builds_per_query=luts,
        )
        lat = self.cost.query_latency_s(
            hops=float(np.mean(stats["hops"])),
            inter_hops=float(np.mean(stats["inter_hops"])),
            reads=float(np.mean(stats["reads"])),
            dist_comps=float(np.mean(stats["dist_comps"])),
            envelope_bytes=env,
            lut_builds=luts,
        )
        return qps, lat

    def bottleneck(self, stats: dict, params, dim: int) -> str:
        return self.cost.bottleneck(
            self.index.p, float(np.mean(stats["reads"])),
            float(np.mean(stats["dist_comps"])),
            float(np.mean(stats["inter_hops"])),
            self.envelope_bytes(dim, params),
        )

    def cluster_traces(self, stats: dict, params, dim: int) -> list:
        from repro import cluster

        return cluster.from_baton_stats(
            stats, self.envelope_bytes(dim, params))

    # --- checkpoint state --------------------------------------------------
    def index_state(self) -> tuple[dict, dict]:
        idx = self.index
        tree = {
            "part_vectors": idx.part_vectors,
            "part_neighbors": idx.part_neighbors,
            "codes": idx.codes,
            "codebook": idx.codebook,
            "node2part": idx.node2part,
            "node2local": idx.node2local,
            "head_vectors": idx.head_vectors,
            "head_neighbors": idx.head_neighbors,
            "head_sample_ids": idx.head_sample_ids,
            "assign": idx.assign,
            "graph_neighbors": idx.graph.neighbors,
        }
        if idx.part_nbr_codes is not None:
            tree["part_nbr_codes"] = idx.part_nbr_codes
        meta = {
            "n": int(idx.n), "p": int(idx.p), "dim": int(idx.dim),
            "head_medoid": int(idx.head_medoid),
            "graph_medoid": int(idx.graph.medoid),
            "graph_R": int(idx.graph.R),
            "graph_L_build": int(idx.graph.L_build),
            "graph_alpha": float(idx.graph.alpha),
        }
        return tree, meta

    def load_index(self, tree: dict, meta: dict):
        graph = vamana.VamanaGraph(
            neighbors=tree["graph_neighbors"], medoid=meta["graph_medoid"],
            R=meta["graph_R"], L_build=meta["graph_L_build"],
            alpha=meta["graph_alpha"],
        )
        self.index = baton.BatonIndex(
            n=meta["n"], p=meta["p"], dim=meta["dim"],
            part_vectors=tree["part_vectors"],
            part_neighbors=tree["part_neighbors"],
            codes=tree["codes"], codebook=tree["codebook"],
            node2part=tree["node2part"], node2local=tree["node2local"],
            head_vectors=tree["head_vectors"],
            head_neighbors=tree["head_neighbors"],
            head_sample_ids=tree["head_sample_ids"],
            head_medoid=meta["head_medoid"], assign=tree["assign"],
            graph=graph, part_nbr_codes=tree.get("part_nbr_codes"),
        )
        return self.index


class ScatterGatherEngine:
    """The §3.1 baseline: scatter to all partitions, gather exact top-k."""

    name = "scatter_gather"
    has_traces = True

    def __init__(self, index: "scatter_gather.ScatterGatherIndex | None" = None,
                 cost: CostModel = COST):
        self.index = index
        self.cost = cost

    # --- build / attach ----------------------------------------------------
    def build(self, dataset, spec, graph=None, assign=None):
        """Same partitioning as the baton engine (paper §6 Baselines); each
        partition gets an independent graph with the same construction
        (``graph_mode="knn"`` is the benchmarks' fast kNN-pruned path —
        bit-identical to the legacy ``benchmarks/common.sg_index`` given
        the same graph/assign)."""
        self.index = scatter_gather.build_index(
            _vectors_of(dataset), p=spec.p, r=spec.r, l_build=spec.l_build,
            alpha=spec.alpha, pq_m=spec.pq_m, pq_k=spec.pq_k,
            partitioner=spec.partitioner, seed=spec.seed, assign=assign,
            global_graph=graph, graph_mode=spec.graph_mode,
            knn_k=spec.knn_k,
        )
        return self.index

    def attach(self, index):
        self.index = index
        return self

    # --- search ------------------------------------------------------------
    def search(self, queries, params) -> SearchResult:
        t0 = time.time()
        ids, dists, stats = scatter_gather.run_simulated(
            self.index, np.asarray(queries, np.float32),
            L=params.L, W=params.W, k=params.k, pool=params.pool,
        )
        # uniform schema: one LUT build per scattered branch (what the
        # cluster-trace builder charges); the legacy stats omit the key
        if "lut_builds" not in stats:
            stats["lut_builds"] = np.full(
                ids.shape[0], self.index.p, np.int64)
        return SearchResult(ids=ids, dists=dists, stats=stats,
                            wall_s=time.time() - t0)

    # --- cost model --------------------------------------------------------
    def envelope_bytes(self, dim: int, params) -> int:
        return SG_SCATTER_BYTES    # scatter/reply messages, not a baton state

    def model(self, stats: dict, params, dim: int) -> tuple[float, float]:
        p = self.index.p
        qps = self.cost.cluster_qps(
            n_servers=p,
            reads_per_query=float(np.mean(stats["reads"])),
            dist_comps_per_query=float(np.mean(stats["dist_comps"])),
            inter_hops_per_query=2.0,          # scatter + gather messages
            envelope_bytes=SG_SCATTER_BYTES,
        )
        # latency driven by the slowest partition (paper §6.5)
        lat = self.cost.query_latency_s(
            hops=float(np.mean(stats["max_part_hops"])),
            inter_hops=2.0,
            reads=float(np.mean(stats["reads"])),
            dist_comps=float(np.mean(stats["dist_comps"]))
            / max(self.cost.threads_per_server, 1),
            envelope_bytes=SG_SCATTER_BYTES,
        )
        return qps, lat

    def bottleneck(self, stats: dict, params, dim: int) -> str:
        return self.cost.bottleneck(
            self.index.p, float(np.mean(stats["reads"])),
            float(np.mean(stats["dist_comps"])), 2.0, SG_SCATTER_BYTES)

    def cluster_traces(self, stats: dict, params, dim: int) -> list:
        from repro import cluster

        return cluster.from_scatter_gather_stats(stats, self.index.p)

    # --- checkpoint state --------------------------------------------------
    def index_state(self) -> tuple[dict, dict]:
        idx = self.index
        tree = {
            "part_vectors": idx.part_vectors,
            "part_neighbors": idx.part_neighbors,
            "part_codes": idx.part_codes,
            "part_medoid": idx.part_medoid,
            "local2global": idx.local2global,
            "codebook": idx.codebook,
            "assign": idx.assign,
        }
        meta = {"n": int(idx.n), "p": int(idx.p), "dim": int(idx.dim)}
        return tree, meta

    def load_index(self, tree: dict, meta: dict):
        self.index = scatter_gather.ScatterGatherIndex(
            n=meta["n"], p=meta["p"], dim=meta["dim"],
            part_vectors=tree["part_vectors"],
            part_neighbors=tree["part_neighbors"],
            part_codes=tree["part_codes"], part_medoid=tree["part_medoid"],
            local2global=tree["local2global"], codebook=tree["codebook"],
            assign=tree["assign"],
        )
        return self.index


@dataclasses.dataclass
class ExactIndex:
    """Brute-force 'index': the raw vectors (single in-memory server)."""

    n: int
    p: int
    dim: int
    vectors: np.ndarray


class ExactEngine:
    """Brute-force oracle: exact k-NN over the raw vectors.

    The recall=1.0 reference for engine comparisons; its cost model charges
    a full scan's distance comparisons on one in-memory server (no disk, no
    hand-offs).
    """

    name = "exact"
    has_traces = False      # in-memory oracle: no disk traces to replay

    def __init__(self, index: "ExactIndex | None" = None,
                 cost: CostModel = COST):
        self.index = index
        self.cost = cost

    def build(self, dataset, spec):
        vectors = _vectors_of(dataset)
        self.index = ExactIndex(n=vectors.shape[0], p=1,
                                dim=vectors.shape[1], vectors=vectors)
        return self.index

    def attach(self, index):
        self.index = index
        return self

    def search(self, queries, params) -> SearchResult:
        t0 = time.time()
        queries = np.asarray(queries, np.float32)
        ids, dists = ref.exact_knn(self.index.vectors, queries, params.k)
        b = queries.shape[0]
        zeros = np.zeros(b, np.int64)
        stats = {
            "hops": zeros, "inter_hops": zeros, "reads": zeros,
            "dist_comps": np.full(b, self.index.n, np.int64),
            "lut_builds": zeros,
        }
        return SearchResult(ids=ids, dists=dists, stats=stats,
                            wall_s=time.time() - t0)

    def envelope_bytes(self, dim: int, params) -> int:
        return 0

    def model(self, stats: dict, params, dim: int) -> tuple[float, float]:
        dcs = float(np.mean(stats["dist_comps"]))
        qps = self.cost.cluster_qps(
            n_servers=1, reads_per_query=0.0, dist_comps_per_query=dcs)
        lat = self.cost.query_latency_s(
            hops=0.0, inter_hops=0.0, reads=0.0, dist_comps=dcs,
            envelope_bytes=0)
        return qps, lat

    def bottleneck(self, stats: dict, params, dim: int) -> str:
        return "cpu"

    def cluster_traces(self, stats: dict, params, dim: int) -> list:
        raise NotImplementedError(
            "ExactEngine is an in-memory oracle; no disk traces to replay")

    def index_state(self) -> tuple[dict, dict]:
        idx = self.index
        return ({"vectors": idx.vectors},
                {"n": int(idx.n), "p": int(idx.p), "dim": int(idx.dim)})

    def load_index(self, tree: dict, meta: dict):
        self.index = ExactIndex(n=meta["n"], p=meta["p"], dim=meta["dim"],
                                vectors=tree["vectors"])
        return self.index


ENGINES = {
    BatonEngine.name: BatonEngine,
    ScatterGatherEngine.name: ScatterGatherEngine,
    ExactEngine.name: ExactEngine,
}


def get_engine(name: str, index=None) -> Engine:
    """Engine by config name (``IndexSpec.engine``), optionally pre-attached."""
    if name not in ENGINES:
        raise KeyError(f"unknown engine '{name}'; known: {sorted(ENGINES)}")
    eng = ENGINES[name]()
    if index is not None:
        eng.attach(index)
    return eng
