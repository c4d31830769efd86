"""The plain reference and the comparison that decides ``correct``.

The reference is exact squared-L2 k-NN in float64 numpy over the whole
corpus: nothing of the program is imported, and it runs after the window
on the host.  A served answer is judged by three numbers:

* ``recall_miss``: 1 - recall@k over the compared queries.  A served id
  is a hit when it is a distinct corpus id whose exact distance is at most
  the true k-th distance (so ties at the k-th place count either way).
  This covers the head entry, the ADC beam, hand-offs, routing and
  delivery.
* ``dist_gap``: the widest gap between a served distance and the exact
  distance of the id served with it, relative to the query's true k-th
  distance.  This covers the re-rank from full vectors.
* ``lost``: queries admitted and never answered (no result, or a result
  without a single id).

The control puts :func:`knn_lower_precision` (the same search done in
bfloat16, the precision below the configuration's float32) in the
program's place; it has to come out as not correct.
"""

from __future__ import annotations

import numpy as np

CHECKS = ("recall_miss", "dist_gap", "lost")
_BLOCK_ELEMS = 1 << 26          # corpus rows x queries per float64 block


def exact_knn(corpus: np.ndarray, queries: np.ndarray, k: int):
    """((Q, k) ids, (Q, k) float64 squared distances), ascending."""
    x = np.asarray(corpus, np.float64)
    xn = np.einsum("nd,nd->n", x, x)
    q_all = np.asarray(queries, np.float64)
    block = max(1, _BLOCK_ELEMS // len(x))
    ids = np.empty((len(q_all), k), np.int64)
    dists = np.empty((len(q_all), k), np.float64)
    for s in range(0, len(q_all), block):
        q = q_all[s:s + block]
        d = xn[None, :] - 2.0 * (q @ x.T) + np.einsum("qd,qd->q", q, q)[:, None]
        top = np.argpartition(d, k - 1, axis=1)[:, :k]
        td = np.take_along_axis(d, top, 1)
        order = np.argsort(td, axis=1, kind="stable")
        ids[s:s + block] = np.take_along_axis(top, order, 1)
        dists[s:s + block] = np.take_along_axis(td, order, 1)
    return ids, dists


def served_distances(corpus: np.ndarray, queries: np.ndarray,
                     ids: np.ndarray) -> np.ndarray:
    """Exact float64 distance of each served id (NaN where out of range)."""
    n = len(corpus)
    ok = (ids >= 0) & (ids < n)
    rows = np.asarray(corpus, np.float64)[np.where(ok, ids, 0)]
    diff = rows - np.asarray(queries, np.float64)[:, None, :]
    d = np.einsum("qkd,qkd->qk", diff, diff)
    return np.where(ok, d, np.nan)


def compare(corpus, queries, ids, dists, answered, k: int) -> dict:
    """The three numbers for served ``(ids, dists)`` of ``queries``.

    ``answered`` marks the queries that got a result; the others count as
    lost and as missing all k neighbours."""
    ids = np.asarray(ids)[:, :k]
    dists = np.asarray(dists, np.float64)[:, :k]
    answered = np.asarray(answered, bool) & (ids >= 0).any(axis=1)
    _, true_d = exact_knn(corpus, queries, k)
    kth = true_d[:, -1]
    exact = served_distances(corpus, queries, ids)
    hits = 0
    for i in np.flatnonzero(answered):
        row = ids[i]
        valid = ~np.isnan(exact[i])
        good = set(row[valid & (exact[i] <= kth[i] * (1 + 1e-12))].tolist())
        hits += len(good)
    gap = np.abs(dists - exact) / np.maximum(kth, 1e-30)[:, None]
    gap = gap[answered][~np.isnan(exact[answered])]
    return {
        "recall_miss": 1.0 - hits / (k * len(queries)),
        "dist_gap": float(gap.max()) if gap.size else float("inf"),
        "lost": int((~answered).sum()),
    }


def lost(ids, answered) -> int:
    """Queries admitted and never answered: no result, or no id in it."""
    return int((~(np.asarray(answered, bool)
                  & (np.asarray(ids) >= 0).any(axis=1))).sum())


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct when every number is at or under its limit."""
    return all(numbers[c] <= limits[c] for c in CHECKS)


def knn_lower_precision(corpus, queries, k: int, block: int = 8192):
    """The control: exact k-NN computed in bfloat16 on the default device
    (inputs, differences, sums and distances all bfloat16)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one(xb, q):
        diff = xb[None, :, :] - q[:, None, :]
        d = jnp.sum(diff * diff, axis=-1, dtype=jnp.bfloat16)
        neg, idx = jax.lax.top_k(-d, k)
        return -neg, idx

    q = jnp.asarray(queries, jnp.bfloat16)
    best_d = best_i = None
    for s in range(0, len(corpus), block):
        xb = jnp.asarray(np.asarray(corpus[s:s + block]), jnp.bfloat16)
        d, i = one(xb, q)
        i = i + s
        if best_d is None:
            best_d, best_i = d, i
        else:
            cd = jnp.concatenate([best_d, d], 1)
            ci = jnp.concatenate([best_i, i], 1)
            neg, pos = jax.lax.top_k(-cd, k)
            best_d, best_i = -neg, jnp.take_along_axis(ci, pos, 1)
    return (np.asarray(best_i, np.int64),
            np.asarray(best_d.astype(jnp.float32), np.float64))
