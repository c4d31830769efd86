"""Baton hand-offs between partitions per answered query (the
``inter_hops`` counter), mean over the window."""

import numpy as np


def read(run):
    c = run.records.counters.get("inter_hops")
    if c is None or not len(c):
        return None
    return float(np.mean(c))
