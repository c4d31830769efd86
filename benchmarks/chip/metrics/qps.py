"""Queries answered per second over the whole window of a closed batch
loop, from the first submit to the last result (host clock)."""


def read(run):
    r = run.records
    if r.entry != "search":
        return None
    return float(r.answered.sum()) / r.window_s
