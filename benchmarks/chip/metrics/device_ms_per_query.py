"""Device busy time in the traced window (ms), per query answered in it."""


def read(run):
    if run.trace is None or run.records.entry != "search":
        return None
    return run.trace.busy_s() * 1e3 / float(run.records.answered.sum())
