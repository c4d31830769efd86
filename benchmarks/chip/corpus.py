"""The corpus of a configuration, and the queries of a run.

The configuration's ``data.generator`` names a module
``generators/<name>.py``, found by file name, whose ``draw(data, g)``
returns the (N, d) float32 vectors and whose ``query_noise(data)`` the
standard deviation, in the same units, by which a query departs from the
corpus point it is drawn near.  This module stores them in the dataset's
published dtype (``data.dtype``): float32 as drawn, or an integer dtype
with the corpus' range scaled onto the dtype's (uint8 0..255, int8
-128..127).  The configuration's ``data_seed`` fixes the corpus, and a
mix's pool of queries; ``--seed`` draws their order and the warm-up
queries.  The program's shapes follow its data (the largest partition
sizes every per-partition array), so a corpus drawn, or only reordered,
per seed recompiled the search in every run and changed its work by 8-28%
(measured on a TPU v5e); one corpus per configuration gives every seed the
same sizes and the same index.

Queries are perturbations of random corpus points, as in
``repro.data.synth``, with one departure: the noise is scaled to the
stored units, and queries of an integer dataset are rounded to its dtype,
as BIGANN's are.  (In ``synth`` a uint8 query moves a fraction of one unit
from its corpus point, so every search would start on its own answer.)

Every random draw comes from ``numpy.random.SeedSequence([seed, stream])``,
one stream per purpose (the CRC-32 of its name, so a driver added later
names its own), so a seed of any size gives the same inputs in every run
and the streams do not overlap.
"""

from __future__ import annotations

import importlib.util
import zlib
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed), zlib.crc32(stream.encode())]))


def load(kind: str, name: str, bench: Path = HERE):
    """The module ``<bench>/<kind>/<name>.py``, found by file name."""
    path = Path(bench) / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Corpus:
    """(N, d) vectors in the compute representation (float32), with what
    the query generator needs to draw near them."""

    def __init__(self, data: dict, bench: Path = HERE):
        self.data_seed = int(data["data_seed"])
        gen = load("generators", data["generator"], bench)
        x = gen.draw(data, rng(self.data_seed, "corpus"))
        self.dtype = np.dtype(data["dtype"])
        if np.issubdtype(self.dtype, np.integer):
            info = np.iinfo(self.dtype)
            lo, hi = float(x.min()), float(x.max())
            # the range split into as many equal bins as the dtype has values
            self.scale = (info.max - info.min + 1.0) / max(hi - lo, 1e-9)
            x = np.clip(np.floor((x - lo) * self.scale) + info.min,
                        info.min, info.max)
        elif self.dtype == np.float32:
            self.scale = 1.0
        else:
            raise ValueError(f"unsupported dtype {self.dtype}")
        self.vectors = np.ascontiguousarray(x, np.float32)
        self.noise = gen.query_noise(data) * self.scale

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    def queries(self, g: np.random.Generator, count: int) -> np.ndarray:
        """``count`` fresh queries: random corpus points plus noise."""
        base = self.vectors[g.integers(0, self.n, size=count)]
        q = base + self.noise * g.normal(size=base.shape).astype(np.float32)
        if np.issubdtype(self.dtype, np.integer):
            info = np.iinfo(self.dtype)
            q = np.clip(np.rint(q), info.min, info.max)
        return np.ascontiguousarray(q, np.float32)
