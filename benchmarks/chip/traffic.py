"""What every traffic driver shares: the records a window returns, the
host spans it marks, and the mix's pool of queries.

A mix is a data file, ``mixes/<name>.json``, whose ``driver`` key names a
module ``drivers/<driver>.py``, found by file name.  The module's
``make(dep, corpus, mix, seed)`` returns an object with

* ``warm_up(seconds)``: run every shape the window will use (set-up);
* ``window(seconds) -> Records``: drive the entry for ``seconds``;
* ``close()``: stop whatever the driver started.

Every other key of the mix is the driver's parameter.  A new traffic mix
of a driver that exists is a data file; a new kind of traffic is a driver
file and a data file, and touches no file that is there.

Host spans named ``bench:*`` go into the profiler's trace when one is
taken; the window is ``bench:window``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from corpus import rng


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"bench:{name}")


@dataclasses.dataclass
class Records:
    """What one window did, for the comparison and the metric readers."""

    entry: str                    # the entry the window drove
    window_s: float
    queries: np.ndarray           # (n, d) every query offered
    ids: np.ndarray               # (n, k) served ids (-1: none)
    dists: np.ndarray             # (n, k) served distances
    answered: np.ndarray          # (n,) bool: a result came back
    admitted: np.ndarray          # (n,) bool: not rejected at admission
    counters: dict                # per-query program counters
    batches: list = dataclasses.field(default_factory=list)
    extra: dict = dataclasses.field(default_factory=dict)  # the driver's own

    @property
    def attempted(self) -> int:
        return len(self.queries)

    @property
    def failed(self) -> int:
        return int((~self.answered).sum())


def pool(corpus, mix: dict) -> np.ndarray:
    """The mix's ``pool`` queries, drawn from the corpus' ``data_seed``: the
    same for every seed, which only orders them."""
    return corpus.queries(rng(corpus.data_seed, "pool"), int(mix["pool"]))
