"""Serving launcher for the BatANN index (the paper's workload).

    PYTHONPATH=src python -m repro.launch.serve --config batann-serve \
        [--n 20000 --servers 8 --queries 256 --L 64 --W 8 ...]

Config-driven: ``--config <name>`` picks a :class:`ServeConfig` preset
(``configs.registry.get_serve_config``); every other flag is an *override*
over that config.  The pipeline itself — dataset → index → search → cost
model → cluster simulation — is ``repro.api.Deployment``, shared with the
examples and the benchmark figures.

Builds (or loads a cached) index over synthetic vectors and serves a batch
of queries, reporting recall + the paper's efficiency counters + modeled
cluster QPS/latency; ``--index-cache DIR`` persists the built index keyed
by the config's dataset+index sections (``ServeConfig.index_key``), so
re-runs with the same index config skip the build.
"""

from __future__ import annotations

import argparse
import time

from repro import compile_cache
from repro.api import Deployment
from repro.configs.registry import get_serve_config, serve_config_ids


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="batann-serve",
                    help=f"ServeConfig preset to start from "
                         f"(known: {serve_config_ids()}); every other flag "
                         f"overrides a config field")
    ap.add_argument("--index-cache", default=None, metavar="DIR",
                    help="load a cached index from DIR (keyed by the "
                         "config's dataset+index sections) or build and "
                         "save one there")
    ap.add_argument("--engine", default=None,
                    choices=["baton", "scatter_gather", "exact"],
                    help="one-line engine swap: the baton engine (default), "
                         "the scatter-gather baseline, or the brute-force "
                         "oracle")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--servers", type=int, default=None)
    ap.add_argument("--queries", type=int, default=None)
    ap.add_argument("--L", type=int, default=None)
    ap.add_argument("--W", type=int, default=None)
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--sector-codes", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="AiSAQ sector layout (no replicated PQ array)")
    ap.add_argument("--ship-lut", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="§8 alternative: ship the PQ LUT inside the "
                         "hand-off envelope instead of rebuilding on arrival "
                         "(bigger wire, zero recompute)")
    ap.add_argument("--lut-wire", default=None,
                    choices=["f32", "f16", "i8"],
                    help="wire dtype of the shipped LUT (§8 quantized "
                         "variants: f16 halves, i8 quarters the LUT bytes)")
    ap.add_argument("--lazy-lut", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="build queued queries' PQ LUTs at refill instead "
                         "of keeping a (Q, M, K) array resident")
    ap.add_argument("--partitioner", default=None,
                    choices=["ldg", "kmeans", "random"])
    ap.add_argument("--send-rate", type=float, default=None,
                    help="open-loop send rate (QPS) for the discrete-event "
                         "cluster simulator: replays the measured per-query "
                         "traces through per-server SSD/CPU/slot/NIC queues "
                         "and reports p50/p99 under load (0 = skip)")
    ap.add_argument("--arrival", default=None,
                    choices=["poisson", "burst", "skew", "diurnal"],
                    help="arrival process for --send-rate / --exec-rate")
    ap.add_argument("--sim-arrivals", type=int, default=None,
                    help="queries to simulate at --send-rate")
    ap.add_argument("--cache-sectors", type=int, default=None,
                    help="per-server LRU sector-cache capacity for the "
                         "event simulator (0 = no cache tier)")
    ap.add_argument("--warm-cache", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="pre-touch every trace's sector footprint before "
                         "the simulated run")
    ap.add_argument("--replicas", default=None,
                    help="replica copies per partition: an int (ring "
                         "placement, least-loaded pick at slot-acquire "
                         "time) or 'hot:<budget>' to replicate only the "
                         "hottest partitions under an extra-copy budget")
    ap.add_argument("--straggler", default=None,
                    help="per-server SSD service-time multipliers, e.g. "
                         "'0:4.0,2:1.5' slows server 0 by 4x and 2 by 1.5x")
    ap.add_argument("--sat-criterion", default=None,
                    choices=["latency", "backlog", "both"],
                    help="saturation-knee criterion for the reported "
                         "saturation QPS (backlog = horizon-independent "
                         "queue-depth trend)")
    ap.add_argument("--elastic", default=None, metavar="t0:n0,t1:n1",
                    help="elastic placement schedule for the event "
                         "simulator: at time t (seconds) the serving tier "
                         "scales to n servers, e.g. '0:4,0.5:8' starts on "
                         "4 servers and scales to 8 at t=0.5s; moved "
                         "partitions are re-homed (bytes streamed over the "
                         "source NIC, dual-homed until the copy lands)")
    ap.add_argument("--faults", default=None, metavar="t:event:server,..",
                    help="fault schedule for the event simulator: "
                         "'0.2:crash:1,0.4:recover:1' crashes server 1 at "
                         "t=0.2s (dropping every resident baton; clients "
                         "re-issue around failed replicas) and recovers it "
                         "at t=0.4s; events: crash, recover, slow:<mult>, "
                         "flaky_nic:<p>")
    ap.add_argument("--retry", type=int, default=None,
                    help="client re-issues per query under faults "
                         "(deadline-triggered, exponential backoff)")
    ap.add_argument("--hedge-ms", type=float, default=None,
                    help="issue one hedged duplicate for queries still "
                         "unresolved after this many ms (first result "
                         "wins; needs --faults)")
    ap.add_argument("--exec-workers", type=int, default=None,
                    help="ALSO run the executable async tier "
                         "(repro.serve_async) with this many real "
                         "partition-owning workers and report measured "
                         "wall-clock latency/QPS next to the modeled "
                         "numbers (0 = modeled only)")
    ap.add_argument("--exec-mode", default=None,
                    choices=["thread", "process"],
                    help="worker isolation for --exec-workers: threads "
                         "(shared jit cache) or spawned processes")
    ap.add_argument("--exec-rate", type=float, default=None,
                    help="wall-clock open-loop rate (QPS) for the exec "
                         "tier's client; 0 = closed-loop batch (every "
                         "query completes; the bit-parity path)")
    ap.add_argument("--exec-arrivals", type=int, default=None,
                    help="arrivals to inject at --exec-rate (schedule "
                         "shape comes from --arrival)")
    ap.add_argument("--exec-batch", type=int, default=None,
                    help="per-worker micro-batch: batons advanced per "
                         "loop iteration in one jit dispatch (answers "
                         "stay bit-identical at any batch; 1 = the "
                         "one-at-a-time loop)")
    ap.add_argument("--insert-frac", type=float, default=None,
                    help="fraction of the dataset held back at build time "
                         "and streamed in as live Vamana inserts "
                         "(core.mutate.MutableIndex); reports mutated-index "
                         "recall vs a from-scratch rebuild (0 = frozen)")
    ap.add_argument("--delete-frac", type=float, default=None,
                    help="fraction of the base points tombstoned after the "
                         "inserts land (never returned; consolidation "
                         "splices and reclaims their rows)")
    ap.add_argument("--ingest-rate", type=float, default=None,
                    help="open-loop write rate (inserts/s) for the event "
                         "simulator's ingest stage: writes contend with "
                         "reads for SSD channels and NICs and the report "
                         "gains freshness lag (needs --send-rate)")
    return ap


def config_from_args(args):
    """The preset named by ``--config`` with every passed flag overlaid."""
    cfg = get_serve_config(args.config)
    return cfg.with_updates(
        data={"n": args.n, "n_queries": args.queries},
        index={
            "engine": args.engine,
            "p": args.servers,
            "partitioner": args.partitioner,
            "codes_mode": (None if args.sector_codes is None
                           else "sector" if args.sector_codes
                           else "replicated"),
        },
        search={
            "L": args.L, "W": args.W, "k": args.k, "slots": args.slots,
            "ship_lut": args.ship_lut,
            "lut_wire_dtype": args.lut_wire,
            "lazy_queue_lut": args.lazy_lut,
        },
        sim={
            "send_rate": args.send_rate, "arrival": args.arrival,
            "n_arrivals": args.sim_arrivals,
            "cache_sectors": args.cache_sectors,
            "warm_cache": args.warm_cache,
            "replicas": args.replicas, "straggler": args.straggler,
            "sat_criterion": args.sat_criterion,
            "elastic": args.elastic,
            "faults": args.faults, "retry": args.retry,
            "hedge_ms": args.hedge_ms,
        },
        exec={
            "workers": args.exec_workers, "mode": args.exec_mode,
            "send_rate": args.exec_rate, "arrival": args.arrival,
            "n_arrivals": args.exec_arrivals, "batch": args.exec_batch,
        },
        mutate={
            "insert_frac": args.insert_frac,
            "delete_frac": args.delete_frac,
            "ingest_rate": args.ingest_rate,
        },
    )


def main():
    compile_cache.enable()
    ap = build_argparser()
    args = ap.parse_args()
    try:
        cfg = config_from_args(args)
    except ValueError as e:           # bad override -> usage error, not a
        ap.error(str(e))              # traceback after the index build

    t0 = time.time()
    dep = Deployment.from_config(cfg, index_cache=args.index_cache)
    # n_servers comes from the deployment, not the config: the exact
    # oracle serves from one in-memory server whatever index.p says
    print(f"[serve] index built in {time.time()-t0:.0f}s "
          f"({cfg.data.n} pts, {dep.n_servers} servers, "
          f"{'sector' if cfg.index.codes_mode == 'sector' else 'replicated'} "
          f"codes)")

    rep = dep.run()
    print(f"[serve] {cfg.data.n_queries} queries in {rep.wall_s:.1f}s "
          f"(simulated {dep.n_servers} servers, {rep.engine} engine)")

    c = rep.counters
    print(f"  recall@{rep.k}={rep.recall:.3f} hops={c['hops']:.1f} "
          f"inter={c['inter_hops']:.2f} "
          f"reads={c['reads']:.1f} "
          f"dcs={c['dist_comps']:.0f}")
    print(f"  modeled: QPS={rep.modeled_qps:.0f} "
          f"latency={rep.modeled_latency_s*1e3:.2f}ms "
          f"bottleneck={rep.bottleneck}")

    if rep.sim is not None:
        s = rep.sim
        print(f"  simulated @{s['rate_qps']:.0f} qps ({s['arrival']}, "
              f"{s['completed']}/{s['offered']} completed, "
              f"{s['scenario']}): "
              f"mean={s['mean_s']*1e3:.2f}ms p50={s['p50_s']*1e3:.2f}ms "
              f"p95={s['p95_s']*1e3:.2f}ms p99={s['p99_s']*1e3:.2f}ms "
              f"(saturation~{s['saturation_qps']:.0f} qps, "
              f"{s['sat_criterion']})")
        if cfg.sim.cache_sectors > 0:
            print(f"  cache: hit_rate={s['cache_hit_rate']:.3f} "
                  f"dram={s['cache_memory_bytes']/1e6:.1f}MB")
        if s["replica_memory_bytes"] > 0:
            print(f"  replicas: {s['replicas']} "
                  f"extra_storage={s['replica_memory_bytes']/1e6:.1f}MB"
                  f"/partition-set")
        if s["elastic"]:
            print(f"  elastic: {s['elastic']} "
                  f"rehomed={s['rehome_events']} partitions "
                  f"migrated={s['migration_bytes']/1e6:.1f}MB over NIC")
        if s["faults"]:
            print(f"  faults: {s['faults']} "
                  f"lost={s['lost']} reissued={s['reissued']} "
                  f"failover_hops={s['failover_hops']} "
                  f"hedge_wins={s['hedge_wins']}")

    if cfg.exec.workers > 0:
        e = dep.run_exec()
        mode = "closed-loop" if e["rate_qps"] == 0 else (
            f"@{e['rate_qps']:.0f} qps {e['arrival']}")
        rej = f", {e['rejected']} rejected" if e["rejected"] else ""
        print(f"  executed ({e['workers']} {e['mode']} workers x "
              f"batch {e['batch']}, {mode}, "
              f"{e['completed']}/{e['offered']} completed{rej}): "
              f"mean={e['mean_s']*1e3:.2f}ms p50={e['p50_s']*1e3:.2f}ms "
              f"p99={e['p99_s']*1e3:.2f}ms "
              f"throughput={e['throughput_qps']:.0f} qps "
              f"({e['advance_calls']} dispatches)")
        print(f"  exec wire: {e['handoffs']} hand-offs x "
              f"{e['wire_bytes_per_handoff']}B measured "
              f"(model prices {e['envelope_bytes']}B), "
              f"{e['wire_batons']} batons in {e['wire_frames']} frames + "
              f"{e['local_handoffs']} same-worker short-circuits, "
              f"parity={'OK' if e['parity'] else 'MISMATCH'}")

    if cfg.mutate.enabled or cfg.mutate.ingest_rate > 0:
        m = dep.run_mutating()
        print(f"  mutated ({m['n_inserted']} inserts, {m['n_deleted']} "
              f"tombstones, {m['n_live']} live of {m['n_base']} base): "
              f"recall@{cfg.search.k}={m['mut_recall']:.3f} vs "
              f"rebuilt={m['rebuilt_recall']:.3f} "
              f"(gap={m['recall_gap']:+.3f}), "
              f"deleted_in_results={m['deleted_in_results']}, "
              f"frozen_parity={'OK' if m['parity'] else 'MISMATCH'}")
        if m["ingest_offered"] > 0:
            print(f"  ingest @{m['ingest_rate']:.0f} writes/s: "
                  f"{m['ingest_completed']}/{m['ingest_offered']} landed "
                  f"({m['ingest_rejected']} rejected), "
                  f"freshness_lag={m['freshness_lag_s']*1e3:.3f}ms "
                  f"p99={m['freshness_p99_s']*1e3:.3f}ms, "
                  f"read QPS under writes={m['sim_qps']:.0f}")


if __name__ == "__main__":
    main()
