"""Oracles: exact k-NN (jitted, on the device), recall, reference beam search.

Everything in here is the ground truth that the optimized system (and every
Pallas kernel) is validated against.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def pairwise_sq_l2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(A, d) x (B, d) -> (A, B) squared euclidean distances."""
    a = np.asarray(a, dtype=np.float32)
    b = np.asarray(b, dtype=np.float32)
    a2 = (a * a).sum(-1)[:, None]
    b2 = (b * b).sum(-1)[None, :]
    return np.maximum(a2 + b2 - 2.0 * (a @ b.T), 0.0)


# Exact kNN tiles: a (Q_BLOCK, DB_BLOCK) squared-L2 tile per step (128 MiB
# of f32 at these sizes); each tile's best k are merged into a running top-k.
Q_BLOCK = 2048
DB_BLOCK = 16384


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@partial(jax.jit, static_argnames=("k", "n", "db_block"))
def _knn_tiles(vectors, queries, k: int, n: int, db_block: int):
    """(Npad, d) x (Qpad/qb, qb, d) -> best-k (ids, dists) per query row.

    ``n`` is the real database size; rows at or past it are padding and
    never selected.  Every matmul runs at ``Precision.HIGHEST``: the
    default on TPU rounds f32 operands to bf16, which a reference must not.
    """
    hi = jax.lax.Precision.HIGHEST
    n_db = vectors.shape[0] // db_block
    x2 = jnp.sum(vectors * vectors, -1)

    def one_query_block(q):
        qb = q.shape[0]
        q2 = jnp.sum(q * q, -1)[:, None]

        def body(b, carry):
            best_d, best_i = carry
            start = b * db_block
            x = jax.lax.dynamic_slice_in_dim(vectors, start, db_block)
            xx = jax.lax.dynamic_slice_in_dim(x2, start, db_block)
            d = q2 + xx[None, :] - 2.0 * jnp.dot(q, x.T, precision=hi)
            col = start + jnp.arange(db_block, dtype=jnp.int32)
            d = jnp.where(col[None, :] < n, jnp.maximum(d, 0.0), jnp.inf)
            neg, pos = jax.lax.top_k(-d, k)                       # tile's best
            all_d = jnp.concatenate([best_d, -neg], 1)
            all_i = jnp.concatenate([best_i, start + pos], 1)
            neg, pos = jax.lax.top_k(-all_d, k)
            return -neg, jnp.take_along_axis(all_i, pos, 1)

        init = (jnp.full((qb, k), jnp.inf, jnp.float32),
                jnp.full((qb, k), -1, jnp.int32))
        return jax.lax.fori_loop(0, n_db, body, init)

    return jax.lax.map(one_query_block, queries)


def exact_knn(vectors, queries, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Exact squared-L2 k-NN on the default device: ((Q, k) ids, dists).

    Ascending by distance.  Tiles of ``Q_BLOCK`` queries by ``DB_BLOCK``
    points bound device memory independently of N, so the same routine
    builds a 1M-point kNN graph on one chip and the ground truth of a
    test-sized dataset on the CPU.
    """
    vectors = np.asarray(vectors, np.float32)
    queries = np.asarray(queries, np.float32)
    n, nq = vectors.shape[0], queries.shape[0]
    if not 0 < k <= n:
        raise ValueError(f"k must be in [1, {n}]: {k}")
    db_block = min(DB_BLOCK, _round_up(n, 8))
    qb = min(Q_BLOCK, _round_up(nq, 8))
    vp = np.zeros((_round_up(n, db_block), vectors.shape[1]), np.float32)
    vp[:n] = vectors
    qp = np.zeros((_round_up(nq, qb), queries.shape[1]), np.float32)
    qp[:nq] = queries
    d, i = _knn_tiles(jnp.asarray(vp), jnp.asarray(qp.reshape(-1, qb, qp.shape[1])),
                      k=k, n=n, db_block=db_block)
    return (np.asarray(i).reshape(-1, k)[:nq],
            np.asarray(d).reshape(-1, k)[:nq])


def brute_force_knn(vectors, queries, k: int) -> np.ndarray:
    """Exact k-NN ids (:func:`exact_knn` without the distances)."""
    return exact_knn(vectors, queries, k)[0]


def recall_at_k(result_ids: np.ndarray, gt_ids: np.ndarray, k: int) -> float:
    """Mean fraction of the true top-k recovered (standard recall@k)."""
    hits = 0
    q = result_ids.shape[0]
    for i in range(q):
        hits += len(set(result_ids[i, :k].tolist()) & set(gt_ids[i, :k].tolist()))
    return hits / (q * k)


def greedy_beam_search_ref(
    vectors: np.ndarray,
    neighbors: np.ndarray,
    query: np.ndarray,
    start: int,
    L: int,
    k: int,
) -> tuple[np.ndarray, dict]:
    """Reference Algorithm 1 (full-precision, W=1) in plain python.

    Returns (top-k ids, stats) where stats counts hops and distance comps.
    Used as the oracle for the fixed-shape lax implementation.
    """
    def dist(i):
        d = vectors[i] - query
        return float(np.dot(d, d))

    pool = {start: dist(start)}  # id -> dist
    explored: set[int] = set()
    hops = 0
    dcs = 1
    while True:
        frontier = [i for i in sorted(pool, key=pool.get)[:L] if i not in explored]
        if not frontier:
            break
        u = min(frontier, key=lambda i: pool[i])
        explored.add(u)
        hops += 1
        for v in neighbors[u]:
            v = int(v)
            if v < 0 or v in pool:
                continue
            pool[v] = dist(v)
            dcs += 1
        # truncate pool to best L
        keep = sorted(pool, key=pool.get)[:L]
        pool = {i: pool[i] for i in set(keep) | explored}
    best = sorted(explored, key=pool.get)[:k]
    return np.array(best, dtype=np.int32), {"hops": hops, "dist_comps": dcs}
