"""What the benchmark's tests share: the benchmark's modules on the path,
and a tiny size at which a cell's whole run fits a CPU test."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a cell shrunk to what the CPU runs in seconds: the same code paths, with
# the corpus, partitions, beam and batch cut down
SHRINK = {
    "config": {"data": {"n": 2000}, "index": {"p": 4, "r": 16, "pq_m": 8},
               "search": {"L": 64, "slots": 8}},
    "mix": {"batch": 32, "pool": 64, "compare": 0},
}
SEED = 2**31 + 977
