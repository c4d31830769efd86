"""Super-steps of ``baton.run_supersteps`` per batch (its
``n_supersteps`` counter), mean over the window's batches."""

import numpy as np


def read(run):
    b = run.records.batches
    if not b:
        return None
    return float(np.mean([x["n_supersteps"] for x in b]))
