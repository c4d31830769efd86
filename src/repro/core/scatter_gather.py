"""Scatter-gather baseline (§3.1, Fig. 1) — the paper's comparison system.

The dataset is partitioned with the *same* method as BatANN (§6 Baselines);
each partition builds an independent Vamana index over its own points with
the same construction parameters.  At query time every query is scattered to
all P partitions, each searches its local index with the same inter-query
balancing machinery, and the per-partition top-k are merged ("gather and
reduce") by exact distance.

Counters are summed across partitions — reproducing the paper's headline
observation (Fig. 10) that scatter-gather compute and disk I/O grow ∝ P.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as part_mod, pq, vamana
from repro.core.beam_search import Shard, search_disk
from repro.core.state import NO_ID, init_state


@dataclasses.dataclass
class ScatterGatherIndex:
    n: int
    p: int
    dim: int
    part_vectors: np.ndarray    # (P, Npmax, d)
    part_neighbors: np.ndarray  # (P, Npmax, R) LOCAL ids
    part_codes: np.ndarray      # (P, Npmax, M) per-partition PQ codes
    part_medoid: np.ndarray     # (P,) local medoid ids
    local2global: np.ndarray    # (P, Npmax)
    codebook: np.ndarray        # shared PQ codebook
    assign: np.ndarray


def build_index(
    vectors: np.ndarray,
    p: int,
    r: int = 32,
    l_build: int = 64,
    alpha: float = 1.2,
    pq_m: int = 16,
    pq_k: int = 256,
    partitioner: str = "ldg",
    seed: int = 0,
    assign: np.ndarray | None = None,
    global_graph: "vamana.VamanaGraph | None" = None,
    graph_mode: str = "vamana",
    knn_k: int = 17,
) -> ScatterGatherIndex:
    """Independent per-partition graphs over a shared partitioning.

    ``graph_mode`` picks the per-partition (and, for LDG partitioning, the
    global) graph construction: ``"vamana"`` runs the full incremental
    build; ``"knn"`` prunes exact kNN candidates (``knn_k`` per node) with
    ``vamana.build_from_knn`` — the fast path the benchmarks use.
    """
    if graph_mode not in ("knn", "vamana"):
        raise ValueError(f"graph_mode must be knn|vamana: {graph_mode}")
    vectors = np.ascontiguousarray(vectors, np.float32)
    n, d = vectors.shape

    def build_graph(pts: np.ndarray, s: int) -> "vamana.VamanaGraph":
        if graph_mode == "knn":
            from repro.core import ref

            knn = ref.brute_force_knn(pts, pts, knn_k)[:, 1:]
            return vamana.build_from_knn(pts, knn, r=r, alpha=alpha)
        return vamana.build(pts, r=r, l_build=l_build, alpha=alpha, seed=s)

    if assign is None:
        if partitioner == "kmeans":
            assign = part_mod.balanced_kmeans(vectors, p, seed=seed)
        elif partitioner == "random":
            assign = part_mod.random_partition(n, p, seed=seed)
        else:
            # paper: same partitioning method as BatANN [12] -> needs a graph
            g = (global_graph if global_graph is not None
                 else build_graph(vectors, seed))
            assign = part_mod.ldg_partition(g.neighbors, p, seed=seed)

    _, _, local2global, sizes = part_mod.build_maps(assign, p)
    npmax = local2global.shape[1]
    part_vectors = np.zeros((p, npmax, d), np.float32)
    part_neighbors = np.full((p, npmax, r), NO_ID, np.int32)
    part_medoid = np.zeros((p,), np.int32)

    cb = pq.train(vectors, m=pq_m, k=pq_k, seed=seed)
    codes = pq.encode(cb, vectors)
    part_codes = np.zeros((p, npmax, codes.shape[1]), np.uint8)

    for pi in range(p):
        ids = local2global[pi]
        ok = ids >= 0
        sub = vectors[ids[ok]]
        g = build_graph(sub, seed + pi)
        part_vectors[pi, ok] = sub
        part_neighbors[pi, ok] = g.neighbors
        part_codes[pi, ok] = codes[ids[ok]]
        part_medoid[pi] = g.medoid

    return ScatterGatherIndex(
        n=n, p=p, dim=d,
        part_vectors=part_vectors, part_neighbors=part_neighbors,
        part_codes=part_codes, part_medoid=part_medoid,
        local2global=local2global, codebook=np.asarray(cb.centroids),
        assign=assign,
    )


@partial(jax.jit, static_argnames=("L", "W", "k", "pool", "max_hops"))
def _search_partitions(part_vectors, part_neighbors, part_codes, part_medoid,
                       queries, codebook, L: int, W: int, k: int, pool: int,
                       max_hops: int):
    """Every query searched on every partition: (P, B, k) local ids, dists
    and (P, B, 4) counters.  The index arrives as arguments, so one
    compilation serves every call with the same shapes."""
    npmax = part_vectors.shape[1]

    def search_partition(vec, nbr, codes, medoid, q):
        shard = Shard(
            vectors=vec, neighbors=nbr, codes=codes,
            node2part=jnp.zeros((npmax,), jnp.int32),
            node2local=jnp.arange(npmax, dtype=jnp.int32),
        )
        lut = pq.build_lut(codebook, q[None])[0]
        starts = medoid[None].astype(jnp.int32)
        sd = pq.adc(lut[None], codes[starts])[0]
        st = init_state(q, starts, sd, L=L, P=pool)
        out = search_disk(st, shard, codebook, w=W, max_hops=max_hops)
        return (
            out.pool_ids[:k], out.pool_dists[:k],
            jnp.stack([out.counters.hops, out.counters.inter_hops,
                       out.counters.dist_comps, out.counters.reads]),
        )

    return jax.vmap(                     # over partitions
        jax.vmap(search_partition, in_axes=(None, None, None, None, 0)),
        in_axes=(0, 0, 0, 0, None),
    )(part_vectors, part_neighbors, part_codes, part_medoid, queries)


def run_simulated(
    index: ScatterGatherIndex, queries: np.ndarray, L: int = 64, W: int = 8,
    k: int = 10, pool: int = 256, max_hops: int = 512,
):
    """Scatter every query to all P local indices; merge exact top-k.

    Returns (ids (B,k), dists (B,k), stats) where counters are summed over
    partitions (the paper's accounting for this baseline, §6.3).
    """
    P = index.p
    queries = np.asarray(queries, np.float32)
    B = queries.shape[0]
    ids_l, dists, stats = _search_partitions(
        jnp.asarray(index.part_vectors), jnp.asarray(index.part_neighbors),
        jnp.asarray(index.part_codes), jnp.asarray(index.part_medoid),
        jnp.asarray(queries), jnp.asarray(index.codebook),
        L=L, W=W, k=k, pool=pool, max_hops=max_hops,
    )                                    # (P, B, k), (P, B, k), (P, B, 4)
    npmax = index.part_vectors.shape[1]

    # local ids -> global ids
    l2g = jnp.asarray(index.local2global)  # (P, Npmax)
    gids = jnp.take_along_axis(
        l2g[:, None, :], jnp.clip(ids_l, 0, npmax - 1), axis=2
    )
    gids = jnp.where(ids_l == NO_ID, NO_ID, gids)

    # gather & reduce: merge P*k candidates by exact distance
    gids = jnp.swapaxes(gids, 0, 1).reshape(B, P * k)
    gdist = jnp.swapaxes(dists, 0, 1).reshape(B, P * k)
    order = jnp.argsort(gdist, axis=1)[:, :k]
    out_ids = np.asarray(jnp.take_along_axis(gids, order, axis=1))
    out_dists = np.asarray(jnp.take_along_axis(gdist, order, axis=1))

    per_part = np.asarray(stats).astype(np.int64)      # (P, B, 4)
    st = per_part.sum(0)                               # (B, 4) summed over P
    return out_ids, out_dists, {
        "hops": st[:, 0], "inter_hops": st[:, 1],
        "dist_comps": st[:, 2], "reads": st[:, 3],
        # per-query latency is driven by the *slowest* partition (§6.5)
        "max_part_hops": per_part[:, :, 0].max(0),
        # per-partition branch traces (B, P) — the cluster simulator replays
        # each query's scatter fan-out through per-server queues with these
        "part_hops": per_part[:, :, 0].T,
        "part_dist_comps": per_part[:, :, 2].T,
        "part_reads": per_part[:, :, 3].T,
        # distinct-sector footprint per branch: every read of a query is a
        # fresh sector (explored-flag invariant), so footprint == reads;
        # kept separate so the simulator's cache tier stays trace-driven
        "part_sectors": per_part[:, :, 3].T,
    }
