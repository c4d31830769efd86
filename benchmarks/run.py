"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes both
``artifacts/bench.csv`` and machine-readable ``artifacts/bench.json``
(keyed by row name, so the BENCH_* trajectory is diffable across PRs).
Scale via env: BENCH_N / BENCH_Q / BENCH_P (defaults 20000/256/8).

``--only <suite>[,<suite>]`` runs a subset (``--list`` names them) — the
bench-smoke CI job and local iteration don't need the full sweep.  A suite
that raises still writes its ``<tag>_FAILED`` row, and the run then exits
nonzero.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import traceback

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, _ROOT)                       # benchmarks package
sys.path.insert(0, os.path.join(_ROOT, "src"))  # repro package
ARTIFACTS = os.path.join(_ROOT, "artifacts")

# suite tag -> "module.function" within the benchmarks package.  Module-
# level (with lazy resolution in main) so `--list`, tools/check_docs.py's
# PAPER_MAP coverage check, and tests can read the tags without importing
# jax; docs/PAPER_MAP.md must cover every tag here (CI-checked).
SUITES = (
    ("fig3", "figures.fig3_inter_partition_hops"),
    ("fig4", "figures.fig4_w_ablation_hops"),
    ("fig5", "figures.fig5_w_efficiency"),
    ("fig7", "figures.fig7_single_server"),
    ("fig9", "figures.fig9_throughput_qps_recall"),
    ("fig9sim", "figures.fig9_sim_scaling"),
    ("fig10", "figures.fig10_efficiency"),
    ("fig11", "figures.fig11_scalability"),
    ("fig12", "figures.fig12_latency_recall"),
    ("fig13", "figures.fig13_latency_vs_send_rate"),
    ("fig14", "figures.fig14_w_throughput"),
    ("fig15cache", "figures.fig15_cache_hit_sweep"),
    ("fig16repl", "figures.fig16_replication_skew"),
    ("fig17strag", "figures.fig17_straggler"),
    ("fig18elastic", "figures.fig18_elastic"),
    ("fig19fault", "figures.fig19_fault_recovery"),
    ("fig20execsim", "figures.fig20_exec_vs_sim"),
    ("fig21batch", "figures.fig21_batch_sweep"),
    ("fig22fresh", "figures.fig22_freshness"),
    ("sec8", "figures.sec8_ship_vs_recompute"),
    ("kernels", "bench_kernels.kernel_rows"),
    ("superstep", "bench_kernels.superstep_rows"),
    ("advbatch", "bench_kernels.advance_batch_rows"),
    ("analysis", "bench_analysis.analysis_rows"),
)


def _resolve(spec: str):
    mod, fn = spec.rsplit(".", 1)
    return getattr(importlib.import_module(f"benchmarks.{mod}"), fn)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated suite tags to run (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="print the suite tags and exit")
    args = ap.parse_args()

    if args.list:
        print("\n".join(tag for tag, _ in SUITES))
        return
    selected = SUITES
    if args.only:
        want = [t.strip() for t in args.only.split(",") if t.strip()]
        known = [tag for tag, _ in SUITES]
        unknown = [t for t in want if t not in known]
        if unknown:
            # Validate BEFORE resolving: suite modules import jax and the
            # whole bench stack, so a typo'd tag must not pay (or crash
            # inside) those imports.  One line, every valid tag listed.
            raise SystemExit(
                f"error: unknown suite tag(s): {', '.join(unknown)} "
                f"(valid: {', '.join(known)})")
        selected = [(tag, spec) for tag, spec in SUITES if tag in want]
    from repro import compile_cache

    compile_cache.enable()
    suites = [(tag, _resolve(spec)) for tag, spec in selected]
    all_rows = []
    print("name,us_per_call,derived")
    for tag, fn in suites:
        t0 = time.time()
        try:
            rows = fn()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            rows = [(f"{tag}_FAILED", -1.0, "error")]
        for name, us, derived in rows:
            line = f"{name},{us:.1f},{derived}"
            print(line, flush=True)
            all_rows.append((name, us, derived))
        print(f"# {tag} done in {time.time()-t0:.0f}s", flush=True)

    out = ARTIFACTS
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench.csv"), "w") as f:
        f.write("name,us_per_call,derived\n")
        f.writelines(f"{n},{us:.1f},{d}\n" for n, us, d in all_rows)
    with open(os.path.join(out, "bench.json"), "w") as f:
        json.dump(
            {n: {"us_per_call": round(us, 1), "derived": d}
             for n, us, d in all_rows},
            f, indent=2, sort_keys=True,
        )
        f.write("\n")
    failed = [n for n, _, _ in all_rows if n.endswith("_FAILED")]
    if failed:
        raise SystemExit(f"error: failed suite(s): {', '.join(failed)}")


if __name__ == "__main__":
    main()
