"""The control's readings for a cell's limits, on the chip at the cell's
own size.

    python3 benchmarks/chip/calibrate.py --workload deep-1m.batch \
        --control-seeds 0,21,22

For each of ``--control-seeds`` it draws as many queries as a run of the
cell compares, as the cell's pool is drawn from its ``data_seed`` (so the
configuration's own ``data_seed`` reads the pool itself), puts the
reference computed in bfloat16 (``reference.knn_lower_precision``) in the
program's place, and prints the compared numbers: the control's readings,
which have to fail the limits.  The program's readings are the runs' own
(``run.py``).  One JSON line per reading on standard output.
"""

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import corpus as corpus_mod
    import harness
    import reference

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU; nothing run", file=sys.stderr)
        return 2
    harness._compile_cache(harness.ROOT)
    spec = harness.load_spec()
    c = harness.cell(spec, args.workload)
    cfg, mix = harness.config(spec, c), harness.mix(c)
    k = cfg["search"]["k"]
    corp = corpus_mod.Corpus(cfg["data"])
    n_cmp = int(mix["compare"])
    for seed in (int(s) for s in args.control_seeds.split(",") if s):
        q = corp.queries(corpus_mod.rng(seed, "pool"), n_cmp)
        t0 = time.perf_counter()
        ids, dists = reference.knn_lower_precision(corp.vectors, q, k)
        got = reference.compare(corp.vectors, q, ids, dists,
                                np.ones(len(q), bool), k)
        print(json.dumps({"seed": seed, "reading": "control",
                          "queries": n_cmp,
                          "seconds": time.perf_counter() - t0, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
