"""A closed loop of whole batches through ``Deployment.search``.

Parameters (the mix file): ``batch``, the queries of one call; ``pool``,
a multiple of it.  The pool (``traffic.pool``) is cut into fixed batches
of ``batch`` queries.  The window serves them back to back, one pass over
the pool after another, until ``--seconds`` have passed; it is those whole
batches, timed from the first submit to the last result.  The seed orders
the batches of each pass and the queries inside each batch, so every seed
gets the same batches (the super-step program lasts until a batch's
slowest query is delivered, and batches that a seed drew anew from one
pool spread ``qps`` by 5% on a TPU v5e where one seed repeated within
0.3%).  The warm-up serves one batch of fresh queries from the seed.
"""

from __future__ import annotations

import time

import numpy as np

from corpus import rng
from traffic import Records, pool, span

COUNTERS = ("hops", "inter_hops", "dist_comps", "reads", "lut_builds")


def batch_order(n_pool: int, batch: int, seed: int):
    """``next_batch()`` -> the pool indices of the window's next batch."""
    if n_pool % batch:
        raise ValueError(f"pool {n_pool} is not a multiple of batch {batch}")
    fixed = np.arange(n_pool).reshape(-1, batch)
    g = rng(seed, "window")
    queue = []

    def next_batch() -> np.ndarray:
        if not queue:
            queue.extend(fixed[i][g.permutation(batch)]
                         for i in g.permutation(len(fixed)))
        return queue.pop(0)

    return next_batch


class ClosedBatches:
    def __init__(self, dep, corpus, mix: dict, seed: int):
        self.dep, self.corpus, self.mix, self.seed = dep, corpus, mix, seed
        self.pool = pool(corpus, mix)
        self.next_batch = batch_order(len(self.pool), int(mix["batch"]), seed)

    def warm_up(self, seconds: float) -> None:
        self.dep.search(self.corpus.queries(rng(self.seed, "warmup"),
                                            int(self.mix["batch"])))

    def window(self, seconds: float) -> Records:
        qs, ids, dists, stats, batches = [], [], [], [], []
        start = time.perf_counter()
        with span("window"):
            while True:
                q = self.pool[self.next_batch()]
                t0 = time.perf_counter()
                with span("batch"):
                    res = self.dep.search(q)
                t1 = time.perf_counter()
                qs.append(q)
                ids.append(res.ids)
                dists.append(res.dists)
                stats.append(res.stats)
                batches.append({"t0": t0 - start, "t1": t1 - start,
                                "n": len(q),
                                "n_supersteps": res.stats["n_supersteps"]})
                if t1 - start >= seconds:
                    break
        n = sum(len(q) for q in qs)
        return Records(
            entry="search", window_s=t1 - start, queries=np.concatenate(qs),
            ids=np.concatenate(ids), dists=np.concatenate(dists),
            answered=np.ones(n, bool), admitted=np.ones(n, bool),
            counters={key: np.concatenate([s[key] for s in stats])
                      for key in COUNTERS},
            batches=batches)

    def close(self) -> None:
        pass


def make(dep, corpus, mix: dict, seed: int) -> ClosedBatches:
    return ClosedBatches(dep, corpus, mix, seed)
