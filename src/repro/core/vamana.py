"""Vamana graph construction (DiskANN [18]) — batched, JAX-accelerated.

Build parameters follow the paper (§6 Graph Construction): R=64, L=128,
alpha=1.2 at full scale; tests/benchmarks use proportionally smaller R/L.
The builder follows ParlayANN's batch-insert formulation (the paper uses
ParlayANN for its 1B graphs): points are inserted in geometrically growing
batches; each batch beam-searches the current graph, robust-prunes its
visited set into an adjacency list, then reverse edges are added (with
overflow pruning).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import beam_search
from repro.core.state import NO_ID


@dataclasses.dataclass
class VamanaGraph:
    neighbors: np.ndarray   # (N, R) int32, NO_ID padded
    medoid: int
    R: int
    L_build: int
    alpha: float

    @property
    def n(self) -> int:
        return self.neighbors.shape[0]

    def degree_stats(self) -> dict:
        deg = (self.neighbors >= 0).sum(1)
        return {"mean": float(deg.mean()), "max": int(deg.max()), "min": int(deg.min())}

    def insert_batch(
        self,
        vectors: np.ndarray,
        new_ids: np.ndarray,
        live_mask: np.ndarray | None = None,
        l_insert: int | None = None,
        max_hops: int = 128,
    ) -> None:
        """In-place streaming insert (the ParlayANN batch-insert loop body).

        ``vectors`` is the full (N', d) array *including* the new points;
        ``new_ids`` are the rows to link in.  Each new point beam-searches
        the live graph from the medoid, robust-prunes its visited set into
        its adjacency row, then reverse edges are added with overflow
        pruning — exactly the ``build()`` loop body, applied to an already
        navigable graph.  ``live_mask`` (N',) masks tombstoned rows out of
        the candidate pool so no new edge ever points at a deleted node.
        Grows ``self.neighbors`` to ``vectors.shape[0]`` rows on demand.
        """
        vectors = np.ascontiguousarray(vectors, np.float32)
        new_ids = np.asarray(new_ids, np.int64)
        if new_ids.size == 0:
            return
        n_new = vectors.shape[0]
        if n_new > self.neighbors.shape[0]:
            grown = np.full((n_new, self.R), NO_ID, np.int32)
            grown[: self.neighbors.shape[0]] = self.neighbors
            self.neighbors = grown
        # rows being (re-)inserted start with a clean slate
        self.neighbors[new_ids] = NO_ID

        jvec = jnp.asarray(vectors)
        jn = jnp.asarray(self.neighbors)
        start_ids = jnp.asarray([self.medoid], jnp.int32)
        L = int(l_insert) if l_insert else max(self.L_build, self.R)
        res = _batched_search(jvec, jn, jnp.asarray(vectors[new_ids]),
                              start_ids, L=L, max_hops=max_hops)
        cand_ids = np.concatenate(
            [np.asarray(res.visited_ids), np.asarray(res.beam_ids)], axis=1
        )
        cand_dists = np.concatenate(
            [np.asarray(res.visited_dists), np.asarray(res.beam_dists)], axis=1
        )
        if live_mask is not None:
            live_mask = np.asarray(live_mask, bool)
            dead = (cand_ids < 0) | ~live_mask[
                np.clip(cand_ids, 0, live_mask.shape[0] - 1)
            ]
            cand_ids = np.where(dead, NO_ID, cand_ids).astype(np.int32)
            cand_dists = np.where(dead, np.inf, cand_dists)
        pruned = np.asarray(
            _robust_prune_batch(
                jnp.asarray(vectors[new_ids]), jnp.asarray(cand_ids),
                jnp.asarray(cand_dists), jvec, r=self.R, alpha=self.alpha,
            )
        )
        self.neighbors[new_ids] = pruned
        _add_reverse_edges(vectors, jvec, self.neighbors, new_ids, pruned,
                           self.R, self.alpha)


@partial(jax.jit, static_argnames=("r", "alpha"))
def _robust_prune_batch(p_vecs, cand_ids, cand_dists, vectors, r: int, alpha: float):
    """Vectorized RobustPrune (DiskANN Alg. 3) over a batch of points.

    p_vecs: (B, d); cand_ids/cand_dists: (B, C) sorted or not; returns (B, R).
    """
    B, C = cand_ids.shape
    cand_vecs = vectors[jnp.clip(cand_ids, 0, vectors.shape[0] - 1)]  # (B, C, d)
    alive = cand_ids != NO_ID
    # a point must never link to itself: kill exact-match candidates
    self_d = jnp.sum((cand_vecs - p_vecs[:, None, :]) ** 2, -1)
    alive &= self_d > 0.0
    dists = jnp.where(alive, cand_dists, jnp.inf)

    def body(i, carry):
        alive, dists, out = carry
        j = jnp.argmin(dists, axis=1)                      # (B,) best alive
        ok = jnp.take_along_axis(alive, j[:, None], 1)[:, 0]
        pick = jnp.where(ok, jnp.take_along_axis(cand_ids, j[:, None], 1)[:, 0], NO_ID)
        out = out.at[:, i].set(pick)
        pv = jnp.take_along_axis(cand_vecs, j[:, None, None], 1)[:, 0]  # (B, d)
        dd = jnp.sum((cand_vecs - pv[:, None, :]) ** 2, -1)            # (B, C)
        kill = (alpha * dd <= cand_dists) & ok[:, None]
        alive2 = alive & ~kill
        alive2 = alive2 & (cand_ids != pick[:, None])
        dists = jnp.where(alive2, cand_dists, jnp.inf)
        return alive2, dists, out

    out = jnp.full((B, r), NO_ID, jnp.int32)
    _, _, out = jax.lax.fori_loop(0, r, body, (alive, dists, out))
    return out


@partial(jax.jit, static_argnames=("L", "max_hops"))
def _batched_search(vectors, neighbors, queries, start_ids, L, max_hops):
    return jax.vmap(
        lambda q: beam_search.search_inmem(
            vectors, neighbors, q, start_ids, L=L, max_hops=max_hops
        )
    )(queries)


def _exact_dists(vectors: np.ndarray, p: np.ndarray, ids: np.ndarray) -> np.ndarray:
    v = vectors[np.clip(ids, 0, vectors.shape[0] - 1)]
    d = ((v - p[:, None, :]) ** 2).sum(-1)
    return np.where(ids < 0, np.inf, d)


def build(
    vectors: np.ndarray,
    r: int = 32,
    l_build: int = 64,
    alpha: float = 1.2,
    max_batch: int = 1024,
    seed: int = 0,
    max_hops: int = 128,
) -> VamanaGraph:
    vectors = np.ascontiguousarray(vectors, dtype=np.float32)
    n = vectors.shape[0]
    jvec = jnp.asarray(vectors)
    medoid = int(np.argmin(((vectors - vectors.mean(0)) ** 2).sum(-1)))

    neighbors = np.full((n, r), NO_ID, dtype=np.int32)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    order = order[order != medoid]

    start_ids = jnp.asarray([medoid], dtype=jnp.int32)
    pos, bs = 0, 1
    while pos < len(order):
        ids = order[pos : pos + bs]
        pos += len(ids)
        bs = min(bs * 2, max_batch)

        jn = jnp.asarray(neighbors)
        res = _batched_search(
            jvec, jn, jnp.asarray(vectors[ids]), start_ids, L=l_build,
            max_hops=max_hops,
        )
        cand_ids = np.concatenate(
            [np.asarray(res.visited_ids), np.asarray(res.beam_ids)], axis=1
        )
        cand_dists = np.concatenate(
            [np.asarray(res.visited_dists), np.asarray(res.beam_dists)], axis=1
        )
        pruned = np.asarray(
            _robust_prune_batch(
                jnp.asarray(vectors[ids]), jnp.asarray(cand_ids),
                jnp.asarray(cand_dists), jvec, r=r, alpha=alpha,
            )
        )
        neighbors[ids] = pruned
        _add_reverse_edges(vectors, jvec, neighbors, ids, pruned, r, alpha)

    return VamanaGraph(neighbors=neighbors, medoid=medoid, R=r, L_build=l_build,
                       alpha=alpha)


def _add_reverse_edges(vectors, jvec, neighbors, src_ids, pruned, r, alpha):
    """For every new edge p->q, try to add q->p (prune q's list on overflow)."""
    edges_q, edges_p = [], []
    for row, p in enumerate(src_ids):
        for q in pruned[row]:
            if q >= 0:
                edges_q.append(q)
                edges_p.append(p)
    if not edges_q:
        return
    eq = np.asarray(edges_q)
    ep = np.asarray(edges_p, dtype=np.int32)
    o = np.argsort(eq, kind="stable")
    eq, ep = eq[o], ep[o]
    uq, starts = np.unique(eq, return_index=True)
    ends = np.append(starts[1:], len(eq))

    overflow_q, overflow_cands = [], []
    for qi, s, e in zip(uq, starts, ends):
        add = ep[s:e]
        cur = neighbors[qi]
        free = np.where(cur < 0)[0]
        new = np.setdiff1d(add, cur[cur >= 0], assume_unique=False)
        if len(new) == 0:
            continue
        if len(new) <= len(free):
            neighbors[qi, free[: len(new)]] = new
        else:
            cand = np.concatenate([cur[cur >= 0], new])
            overflow_q.append(qi)
            overflow_cands.append(cand)
    if overflow_q:
        widths = np.array([len(c) for c in overflow_cands])
        cids = np.full((len(overflow_q), widths.max()), NO_ID, dtype=np.int32)
        for i, c in enumerate(overflow_cands):
            cids[i, : len(c)] = c
        _prune_rows(vectors, jvec, neighbors, np.asarray(overflow_q), cids,
                    widths, r, alpha)


# Candidates (rows x candidate columns) pruned per device call.  The
# (rows, candidates, d) f32 gather of ``_robust_prune_batch`` must stay far
# below device memory even when every row of a 1M-point graph needs pruning
# and a few hub rows carry thousands of candidates.
PRUNE_ELEMS = 1 << 18


def _prune_rows(vectors, jvec, neighbors, rows, cids, widths, r, alpha):
    """``neighbors[rows] = RobustPrune(rows, cids)``, in bounded calls.

    Row i's candidates are ``cids[i, :widths[i]]`` (the rest is ``NO_ID``).
    Rows are grouped by their width rounded up to a power of two, so a hub
    row does not widen every call, and each group is cut into calls of at
    most ``PRUNE_ELEMS`` candidates; a group of more than one call pads its
    last call with empty rows, so its calls share one shape.  Rows are
    independent and ``NO_ID`` columns are never picked, so neither the
    grouping nor the padding changes any row's result."""
    bins = np.maximum(16, 1 << np.ceil(np.log2(np.maximum(widths, 1))).astype(int))
    for c in np.unique(bins):
        sel = np.flatnonzero(bins == c)
        bs = min(max(8, PRUNE_ELEMS // c), len(sel))
        for s in range(0, len(sel), bs):
            part = sel[s:s + bs]
            ids = rows[part]
            cc = np.full((bs, c), NO_ID, np.int32)
            w = min(c, cids.shape[1])
            cc[: len(part), :w] = cids[part, :w]
            qv = np.zeros((bs, vectors.shape[1]), np.float32)
            qv[: len(part)] = vectors[ids]
            cd = _exact_dists(vectors, qv, cc)
            pr = np.asarray(
                _robust_prune_batch(
                    jnp.asarray(qv), jnp.asarray(cc), jnp.asarray(cd), jvec,
                    r=r, alpha=alpha,
                )
            )
            neighbors[ids] = pr[: len(part)]


def build_from_knn(
    vectors: np.ndarray,
    knn_ids: np.ndarray,
    r: int = 32,
    alpha: float = 1.2,
    n_random_long: int = 4,
    seed: int = 0,
) -> VamanaGraph:
    """Alternative fast builder: alpha-prune (kNN ∪ random long edges).

    Used when an exact/approx kNN graph is already available; produces a
    navigable graph with Vamana-like long edges at a fraction of the cost.
    """
    n = vectors.shape[0]
    rng = np.random.default_rng(seed)
    longe = rng.integers(0, n, size=(n, n_random_long)).astype(np.int32)
    cand = np.concatenate([knn_ids.astype(np.int32), longe], axis=1)
    jvec = jnp.asarray(np.ascontiguousarray(vectors, np.float32))
    out = np.full((n, r), NO_ID, np.int32)
    _prune_rows(vectors, jvec, out, np.arange(n), cand,
                np.full(n, cand.shape[1]), r, alpha)
    medoid = int(np.argmin(((vectors - vectors.mean(0)) ** 2).sum(-1)))
    g = VamanaGraph(neighbors=out, medoid=medoid, R=r, L_build=0, alpha=alpha)
    # ensure medoid reaches out (it always has out-edges by construction) and
    # add reverse edges for connectivity
    _add_reverse_edges(vectors, jvec, g.neighbors, np.arange(n), out, r, alpha)
    return g
