"""Host time per batch (ms): the benchmark's span around each
``Deployment.search`` call minus the device's busy time inside it, mean
over the traced window's batches."""

import numpy as np


def read(run):
    tr = run.trace
    spans = tr.spans_named("batch") if tr is not None else []
    if not spans:
        return None
    host = [(e - s) * 1e-9 - tr.busy_s(s, e) for s, e in spans]
    return float(np.mean(host)) * 1e3
