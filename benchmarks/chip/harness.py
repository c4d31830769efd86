"""One run of one cell: find its files by name, set up, measure, check.

Everything is found by name from ``BENCHMARK.json``:

* the cell's configuration is the file its ``configs`` entry names, and
  its corpus the module ``generators/<data.generator>.py`` (``corpus.py``);
* its traffic is ``mixes/<traffic>.json``, whose ``driver`` names the
  module ``drivers/<driver>.py`` that drives it (``traffic.py``);
* each metric is ``metrics/<name>.py``, whose ``read(run)`` returns a
  number, or ``None`` where the run has nothing for it to read.

A run reports the cell's end-to-end metrics with ``--trace 0`` and its
per-layer metrics with ``--trace 1``, each metric being the cell's where
its ``workloads`` list names the cell (or has no such list).
"""

from __future__ import annotations

import dataclasses
import gc
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH_REL = Path("benchmarks/chip")
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import corpus as corpus_mod  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from traces import reduce as reduce_trace, xplane_file  # noqa: E402


class NoChip(Exception):
    """The machine lacks the chips the cell asks for."""


# --- finding things by name ---------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell(spec: dict, name: str) -> dict:
    return _by_name(spec["workloads"], name, "workload")


def config(spec: dict, cell_: dict, root: Path = ROOT) -> dict:
    entry = _by_name(spec["configs"], cell_["config"], "config")
    with open(Path(root) / entry["file"]) as f:
        return json.load(f)


def mix(cell_: dict, root: Path = ROOT) -> dict:
    with open(Path(root) / BENCH_REL / "mixes" / f"{cell_['traffic']}.json") as f:
        return json.load(f)


def metrics_for(spec: dict, cell_name: str, per_layer: bool) -> list:
    """The metric entries a run of the cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not per_layer:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and (m["moves"] in moved or "workloads" in m)]


def reader(name: str, root: Path = ROOT):
    return corpus_mod.load("metrics", name, Path(root) / BENCH_REL).read


def driver(mix_: dict, root: Path = ROOT):
    """The module that drives ``mix_``: ``drivers/<mix_["driver"]>.py``."""
    return corpus_mod.load("drivers", mix_["driver"], Path(root) / BENCH_REL)


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    with open(Path(root) / BENCH_REL / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table["devices"][device_kind]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, val in over.items():
        out[key] = (_merge(out[key], val)
                    if isinstance(val, dict) and isinstance(out.get(key), dict)
                    else val)
    return out


# --- one run ---------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric reader is given."""

    records: "traffic.Records"
    setup_s: float
    trace: "object | None" = None        # traces.Trace of a --trace 1 run


class _Compiles:
    """Counts the compilations and traces JAX reports inside a ``with``."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        self.counts = {"compiles": 0, "traces": 0}

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def _compile_cache(root: Path) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / BENCH_REL / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


BUILD_SOURCES = ("src/repro/core", "src/repro/api")


def index_dir(cfg: dict, root: Path = ROOT) -> Path:
    """Where the built index of ``cfg`` is kept in this checkout: keyed by
    the configuration's name, a hash of its data and index sections, and a
    hash of the program's build sources."""
    h = hashlib.sha256(json.dumps(
        {"data": cfg["data"], "index": cfg["index"]},
        sort_keys=True).encode())
    for d in BUILD_SOURCES:
        for f in sorted((Path(root) / d).glob("*.py")):
            h.update(f.name.encode())
            h.update(f.read_bytes())
    return Path(root) / BENCH_REL / ".index_cache" / \
        f"{cfg['name']}-{h.hexdigest()[:16]}"


def serve_config(cfg: dict):
    from repro.configs.batann_serve import ServeConfig

    return ServeConfig.from_dict({
        "name": cfg["name"], "index": cfg["index"], "search": cfg["search"]})


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, root: Path = ROOT, log=print,
            require_chip: bool = True, cache: bool = True,
            shrink: "dict | None" = None) -> dict:
    """Set up, measure and check one run; returns the result line's dict.

    The first run of a configuration in a checkout builds its index and
    keeps it (``index_dir``); later runs load it.  ``cache=False`` (tests)
    keeps neither the index nor compiled programs.  ``shrink`` (tests)
    overrides parts of the configuration and the mix, as
    ``{"config": {...}, "mix": {...}}``."""
    import jax

    spec = load_spec(root)
    c = cell(spec, workload)
    cfg, mx = config(spec, c, root), mix(c, root)
    if shrink:
        cfg = _merge(cfg, shrink.get("config", {}))
        mx = _merge(mx, shrink.get("mix", {}))
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            raise NoChip(f"no TPU: JAX found {dev.platform}")
        if len(devices) < c["chips"]:
            raise NoChip(f"{workload} needs {c['chips']} chips, "
                         f"found {len(devices)}")
    chip = peaks(dev.device_kind, root) if require_chip else None
    used = devices[:c["chips"]]
    if cache:
        log(f"compile cache: {_compile_cache(root)}")

    from repro.api import Deployment

    phases = {}
    t0 = time.perf_counter()
    corp = corpus_mod.Corpus(cfg["data"], Path(root) / BENCH_REL)
    phases["corpus"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    dep = Deployment.from_config(
        serve_config(cfg), dataset=corp,
        index_cache=str(index_dir(cfg, root)) if cache else None)
    phases["index"] = time.perf_counter() - t0
    build_phases = {k: round(v, 3) for k, v in dep.build_s.items()}
    t0 = time.perf_counter()
    drv = driver(mx, root).make(dep, corp, mx, seed)
    drv.warm_up(seconds)
    phases["warmup"] = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start

    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(logdir)
    try:
        with _Compiles() as compiles:
            records = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    tr = None
    if trace:
        t0 = time.perf_counter()
        tr = reduce_trace(xplane_file(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t0:.3f}s")
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
    peak = int(max(mem))
    drv.close()
    k = dep.config.search.k
    del drv, dep
    gc.collect()

    log("setup " + json.dumps({
        "setup_s": setup_s, "process": setup_s - sum(phases.values()),
        **{k_: round(v, 3) for k_, v in phases.items()},
        "build_phases": build_phases or "loaded from the index cache"}))
    log(f"window: {compiles.counts['compiles']} compilations inside it "
        f"(want 0), {compiles.counts['traces']} jaxpr traces; "
        f"{records.attempted} offered, {int(records.answered.sum())} "
        f"answered, {int((~records.admitted).sum())} rejected, "
        f"{records.window_s:.3f}s")
    if records.batches:
        log("batches " + json.dumps([round(b["t1"] - b["t0"], 3)
                                     for b in records.batches]))
    hbm = (f" ({peak / chip['hbm_bytes']:.4%} of HBM)" if chip else "")
    log(f"memory_peak_bytes={peak}{hbm}")

    # a rejected arrival is failed, not wrong: the answers of the admitted
    # ones are compared, and an admitted one never answered is lost
    t0 = time.perf_counter()
    admitted = np.flatnonzero(records.admitted)
    n_cmp = int(mx["compare"]) or len(admitted)
    sample = np.sort(corpus_mod.rng(seed, "sample").permutation(
        admitted)[:n_cmp])
    checks = reference.compare(
        corp.vectors, records.queries[sample], records.ids[sample],
        records.dists[sample], records.answered[sample], k)
    checks["lost"] = reference.lost(records.ids[admitted],
                                    records.answered[admitted])
    log(f"reference over {len(sample)} queries: "
        f"recall@{k}={1 - checks['recall_miss']:.6f} "
        f"in {time.perf_counter() - t0:.3f}s")
    limits = cfg["limits"]
    correct = reference.verdict(checks, limits)

    run = Run(records=records, setup_s=setup_s, trace=tr)
    metrics = {}
    for m in metrics_for(spec, workload, per_layer=trace):
        value = reader(m["name"], root)(run)
        if value is None:
            if trace:
                continue
            raise ValueError(f"metric {m['name']} read nothing in "
                             f"{workload}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": records.attempted,
           "failed": records.failed, "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.idle_gaps()}
    out["checks"] = {name: {"value": checks[name], "limit": limits[name]}
                     for name in reference.CHECKS}
    return out
