"""Run one benchmark cell once, on the chip, and print its result line.

    python3 benchmarks/chip/run.py --workload deep-1m.batch --seed 7 \
        --seconds 51 --trace 0

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` ``breakdown``), then ``checks``, each
number compared beside its limit.  The set-up phases, the compilations
inside the window, recall and peak memory go to standard error, and so do
the checks, as its last lines.  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        out = harness.execute(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"run.py: {e}; nothing run")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
