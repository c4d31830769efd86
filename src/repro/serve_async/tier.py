"""The async serving tier: workers + open-loop client + wall-clock results.

``AsyncServingTier`` turns a built ``BatonIndex`` into a running host-level
service: ``n_workers`` partition-owning workers (threads or spawned
processes), per-worker two-class inboxes with ``SlotStage`` admission
semantics, and a client that injects queries — either closed-loop (the
batch client behind ``search``: blocking admission, every query completes)
or open-loop from a ``cluster.workload`` arrival schedule (``serve``:
bounded queues reject under overload, exactly what the simulator's knee
measures from the other direction).

Guarantees (tested):

* **Answer parity** — ``search(queries)`` returns (ids, dists) and the
  five ``STAT_FIELDS`` counters bit-identical to ``baton.run_simulated``
  (= ``Engine.search``) at *any* (worker count × micro-batch):
  partitioning is by partition, not worker, so folding partitions onto
  fewer workers changes only where batons queue, and the ``batch``-sized
  drain (``runtime.advance_batch``) advances independent states with
  row-masked selects, so batching changes only how many states share a
  jit dispatch — never what any of them computes.
* **Conservation** — every offered arrival ends as exactly one of
  {completed, rejected}; hand-offs are never dropped.
* **Determinism** — one worker processes admissions in arrival order and
  chases each baton to completion before the next admission, so the
  completion order itself is reproducible run-to-run.

Wall-clock per-query latency, windowed throughput, and the measured wire
bytes per hand-off (vs the modeled ``envelope_bytes``) come back in
``ExecRunResult``.
"""

from __future__ import annotations

import dataclasses
import queue as _queue
import threading
import time

import jax
import numpy as np

from repro.core.state import STAT_FIELDS, envelope_bytes
from repro.serve_async import queues, runtime, sanitize, wire
from repro.serve_async import worker as worker_mod

INTER_HOPS_COL = STAT_FIELDS.index("inter_hops")


@dataclasses.dataclass
class ExecRunResult:
    """One client run: per-arrival answers, wall-clock timing, accounting."""

    ids: np.ndarray           # (n, k) int32; -1 rows for rejected arrivals
    dists: np.ndarray         # (n, k) float32; +inf rows for rejected
    stats: np.ndarray         # (n, N_STATS) int64 engine counters
    latencies_s: np.ndarray   # (n,) wall-clock, NaN for rejected
    arrive_s: np.ndarray      # (n,) injection time (relative to run start)
    done_s: np.ndarray        # (n,) completion time, NaN for rejected
    trace_idx: np.ndarray     # (n,) which query each arrival replayed
    accepted: np.ndarray      # (n,) bool — admitted (False = rejected)
    offered: int
    completed: int
    makespan_s: float
    rate_qps: float           # requested open-loop rate (0 = closed loop)
    wire_bytes_per_handoff: int   # measured encoded baton size
    envelope_bytes: int           # the model's priced size (same leaves)
    batch: int = 1            # per-worker micro-batch the tier ran with
    advance_calls: int = 0    # jit dispatches issued by all workers
    local_handoffs: int = 0   # same-worker hops (short-circuit, no codec)
    wire_frames: int = 0      # serialized messages (coalesced hand-offs)
    wire_batons: int = 0      # batons inside those messages
    wire_bytes: int = 0       # total frame bytes incl. per-record framing

    @property
    def admitted(self) -> int:
        return int(self.accepted.sum())

    @property
    def rejected(self) -> int:
        return self.offered - self.admitted

    @property
    def handoffs(self) -> int:
        # every inter_hops increment crossed a queue exactly once — as a
        # baton inside a serialized frame (wire_batons) or as a same-worker
        # in-memory short-circuit (local_handoffs)
        return int(self.stats[:, INTER_HOPS_COL].sum())

    def _done(self) -> np.ndarray:
        return self.latencies_s[~np.isnan(self.latencies_s)]

    @property
    def mean_s(self) -> float:
        d = self._done()
        return float(d.mean()) if len(d) else float("nan")

    def percentile_s(self, q: float) -> float:
        d = self._done()
        return float(np.percentile(d, q)) if len(d) else float("nan")

    @property
    def throughput_qps(self) -> float:
        return self.completed / self.makespan_s if self.makespan_s > 0 else 0.0

    def throughput_in(self, t0: float, t1: float) -> float:
        """Completions per second inside the wall-clock window [t0, t1)."""
        ok = ~np.isnan(self.done_s)
        n = int(((self.done_s[ok] >= t0) & (self.done_s[ok] < t1)).sum())
        return n / max(t1 - t0, 1e-9)

    def stats_dict(self) -> dict:
        return {f: self.stats[:, i] for i, f in enumerate(STAT_FIELDS)}


class AsyncServingTier:
    """N partition-owning workers serving baton queries over a built index."""

    def __init__(self, index, params, n_workers: int, mode: str = "thread",
                 slots: "int | None" = None, admit_headroom: int = 2,
                 queue_cap: int = 64, batch: int = 1,
                 sector_codes: "bool | None" = None):
        if mode not in ("thread", "process"):
            raise ValueError(f"mode must be thread|process: {mode}")
        if mode == "process" and jax.default_backend() != "cpu":
            # this process already holds the accelerator: spawned workers
            # could not open it and would serve from another backend
            raise ValueError(
                f"process mode runs only on the CPU backend (this process "
                f"holds the {jax.default_backend()}); use mode='thread'")
        if not 1 <= n_workers <= index.p:
            raise ValueError(
                f"n_workers must be in [1, p={index.p}]: {n_workers}")
        if batch < 1:
            raise ValueError(f"batch must be >= 1: {batch}")
        if sector_codes is None:
            sector_codes = index.part_nbr_codes is not None
        self.index, self.cfg = index, params
        self.p, self.n_workers, self.mode = index.p, n_workers, mode
        self.batch = batch
        slots = slots if slots is not None else params.slots
        # partitions fold onto workers exactly as Placement.fold folds them
        # onto fewer servers
        self.part2worker = tuple(pp % n_workers for pp in range(index.p))
        pq_m, pq_k = index.codebook.shape[:2]
        self.envelope_bytes = envelope_bytes(
            index.dim, params.L, params.pool, m=pq_m, k_pq=pq_k,
            ship_lut=params.ship_lut, lut_dtype=params.lut_wire_dtype)
        # measured wire size: encode one (seeded, empty) baton — all leaves
        # are fixed-shape so every hand-off message is the same length
        import jax.numpy as jnp

        self._codebook = jnp.asarray(index.codebook)
        dummy = runtime.seed_state(
            jnp.zeros((index.dim,), jnp.float32),
            jnp.full((params.n_starts,), -1, jnp.int32),
            jnp.full((params.n_starts,), jnp.inf, jnp.float32),
            jnp.zeros((pq_m, pq_k), jnp.float32), 0, 0,
            params.L, params.pool,
        )
        self.wire_bytes_per_handoff = len(
            wire.encode_baton(runtime.pack_for_wire(dummy, params)))

        owned = {w: [pp for pp in range(self.p) if self.part2worker[pp] == w]
                 for w in range(n_workers)}
        if mode == "thread":
            self._results = _queue.SimpleQueue()
            self._inboxes = [
                queues.ThreadInbox(slots, admit_headroom, queue_cap)
                for _ in range(n_workers)
            ]
            shards = {pp: runtime.partition_shard(index, pp, sector_codes)
                      for pp in range(self.p)}
            self._shards = shards
            self._workers = [
                worker_mod.start_thread_worker(
                    w, {pp: shards[pp] for pp in owned[w]}, self._codebook,
                    params, self._inboxes[w], self._inboxes,
                    self.part2worker, self._results, batch)
                for w in range(n_workers)
            ]
        else:
            import multiprocessing as mp

            ctx = mp.get_context("spawn")
            self._results = ctx.Queue()
            self._inboxes = [
                queues.ProcessInbox(ctx, slots, admit_headroom, queue_cap)
                for _ in range(n_workers)
            ]
            self._workers = []
            for w in range(n_workers):
                arrays = {pp: self._shard_arrays(pp, sector_codes)
                          for pp in owned[w]}
                proc = ctx.Process(
                    target=worker_mod.process_worker_main, daemon=True,
                    args=(w, owned[w], arrays, index.codebook,
                          dataclasses.asdict(params), self._inboxes[w],
                          self._inboxes, self.part2worker, self._results,
                          batch),
                )
                proc.start()
                self._workers.append(proc)
        # close() may race between the user thread and __exit__/atexit
        # paths; the lock makes the closed check-then-act atomic so stop()
        # and join() run exactly once.
        self._close_lock = threading.Lock()
        self._closed = False

    def _shard_arrays(self, part: int, sector_codes: bool) -> dict:
        ix = self.index
        if sector_codes:
            return dict(
                vectors=ix.part_vectors[part],
                neighbors=ix.part_neighbors[part],
                codes=np.zeros((1, ix.codes.shape[1]), np.uint8),
                node2part=ix.node2part, node2local=ix.node2local,
                nbr_codes=ix.part_nbr_codes[part],
            )
        return dict(
            vectors=ix.part_vectors[part], neighbors=ix.part_neighbors[part],
            codes=ix.codes, node2part=ix.node2part,
            node2local=ix.node2local, nbr_codes=None,
        )

    def warmup(self) -> None:
        """Compile every advance variant this tier can dispatch, off the
        clock.

        jax caches one executable per (batch shape x partition-shard
        shape) pair; workers round micro-batch groups down to powers of
        two, so one dummy advance per (partition, pow2 size <= batch) —
        plus the scalar path — covers every shape a run can hit.  The
        dummy states carry invalid starts, so each warm advance traces and
        compiles the full body but exits its while_loop after one masked
        iteration.  Thread workers share this thread's compile cache; in
        process mode workers own their caches, so this is a no-op there
        and a throwaway first run warms them instead.
        """
        if self.mode != "thread":
            return
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        pq_m, pq_k = self.index.codebook.shape[:2]
        dummy = runtime.seed_state(
            jnp.zeros((self.index.dim,), jnp.float32),
            jnp.full((cfg.n_starts,), -1, jnp.int32),
            jnp.full((cfg.n_starts,), jnp.inf, jnp.float32),
            jnp.zeros((pq_m, pq_k), jnp.float32), 0, 0,
            cfg.L, cfg.pool,
        )
        for pp in range(self.p):
            shard = self._shards[pp]
            jax.block_until_ready(runtime.advance_state(
                dummy, shard, pp, cfg.W, cfg.max_local_steps)[0].beam_ids)
            size = 2
            while size <= self.batch:
                sts = runtime.stack_states([dummy] * size)
                jax.block_until_ready(runtime.advance_batch(
                    sts, shard, pp, cfg.W, cfg.max_local_steps,
                    adc_impl=cfg.adc_impl,
                    merge_impl=cfg.merge_impl)[0].beam_ids)
                size *= 2

    # ------------------------------------------------------------- client --
    def run(self, queries: np.ndarray, times_s=None, trace_idx=None,
            time_scale: float = 1.0, rate_qps: float = 0.0,
            drain_timeout_s: float = 120.0) -> ExecRunResult:
        """Inject arrivals and collect results (first result wins).

        ``times_s=None`` is the closed-loop batch client: admission blocks
        (backpressure, no rejection) and every arrival completes.  With an
        arrival schedule the client is open-loop: it sleeps to each
        ``times_s[a] * time_scale`` and a full admission queue *rejects*.
        """
        if self._closed:
            raise RuntimeError("tier is closed")
        queries = np.ascontiguousarray(np.asarray(queries, np.float32))
        b = len(queries)
        trace_idx = (np.arange(b, dtype=np.int64) if trace_idx is None
                     else np.asarray(trace_idx, np.int64))
        n = len(trace_idx)
        cfg = self.cfg
        starts, start_d = self.index.head_starts(queries, cfg.n_starts)
        import jax.numpy as jnp

        from repro.core import pq as _pq
        luts = np.asarray(_pq.build_lut(self._codebook, jnp.asarray(queries)))

        ids = np.full((n, cfg.k), -1, np.int32)
        dists = np.full((n, cfg.k), np.inf, np.float32)
        stats = np.zeros((n, len(STAT_FIELDS)), np.int64)
        arrive = np.full(n, np.nan)
        done_s = np.full(n, np.nan)
        accepted = np.zeros(n, bool)
        n_done = [0]
        stop = threading.Event()

        # hand-off/dispatch accounting: counters persist across runs on the
        # same tier, so diff a snapshot (all hand-offs have landed once the
        # drain below completes — nothing is in flight at the diff)
        counters0 = [ib.counter_snapshot() for ib in self._inboxes]

        t0 = time.perf_counter()

        def collect():
            while True:
                try:
                    msg = self._results.get(timeout=0.05)
                except _queue.Empty:
                    if stop.is_set():
                        return
                    continue
                _, a, _qid, r_ids, r_dists, r_stats, t_done = msg
                if not np.isnan(done_s[a]):
                    continue                      # first result wins
                ids[a], dists[a], stats[a] = r_ids, r_dists, r_stats
                done_s[a] = t_done - t0
                n_done[0] += 1

        collector = threading.Thread(target=collect, daemon=True)
        collector.start()

        homes = trace_idx % self.p        # the engine's qid % P round-robin
        for a in range(n):
            j = int(trace_idx[a])
            inbox = self._inboxes[self.part2worker[int(homes[a])]]
            msg = (a, j, int(homes[a]), queries[j], starts[j], start_d[j],
                   luts[j])
            if times_s is None:
                while not inbox.offer_admit(msg):
                    time.sleep(1e-4)
                accepted[a] = True
            else:
                target = float(times_s[a]) * time_scale
                now = time.perf_counter() - t0
                if target > now:
                    time.sleep(target - now)
                accepted[a] = inbox.offer_admit(msg)
            arrive[a] = time.perf_counter() - t0

        target_done = int(accepted.sum())
        last_progress, seen = time.perf_counter(), 0
        while n_done[0] < target_done:
            if n_done[0] > seen:
                seen, last_progress = n_done[0], time.perf_counter()
            if time.perf_counter() - last_progress > drain_timeout_s:
                stop.set()
                raise RuntimeError(
                    f"exec tier stalled: {n_done[0]}/{target_done} done")
            time.sleep(1e-3)
        stop.set()
        collector.join()

        makespan = float(np.nanmax(done_s)) if target_done else 0.0
        latencies = done_s - arrive
        totals = {name: 0 for name in queues.COUNTER_NAMES}
        for before, ib in zip(counters0, self._inboxes):
            after = ib.counter_snapshot()
            for name in totals:
                totals[name] += after[name] - before[name]
        result = ExecRunResult(
            ids=ids, dists=dists, stats=stats, latencies_s=latencies,
            arrive_s=arrive, done_s=done_s, trace_idx=trace_idx,
            accepted=accepted, offered=n, completed=target_done,
            makespan_s=makespan, rate_qps=rate_qps,
            wire_bytes_per_handoff=self.wire_bytes_per_handoff,
            envelope_bytes=self.envelope_bytes,
            batch=self.batch,
            advance_calls=totals["advance_calls"],
            local_handoffs=totals["local_batons"],
            wire_frames=totals["wire_frames"],
            wire_batons=totals["wire_batons"],
            wire_bytes=totals["wire_bytes"],
        )
        if sanitize.enabled():
            sanitize.check_invariants(result, self._inboxes)
        return result

    def search(self, queries: np.ndarray) -> ExecRunResult:
        """Closed-loop batch search — answers bit-identical to
        ``Engine.search`` on the same queries (the parity guarantee)."""
        res = self.run(queries)
        assert res.completed == len(queries), "closed-loop run lost queries"
        return res

    def serve(self, queries: np.ndarray, workload,
              time_scale: float = 1.0) -> ExecRunResult:
        """Open-loop run of a ``cluster.workload`` schedule (arrival ``a``
        replays ``queries[workload.trace_idx[a]]`` at
        ``times_s[a] * time_scale`` wall seconds)."""
        return self.run(
            queries, times_s=workload.times_s, trace_idx=workload.trace_idx,
            time_scale=time_scale,
            rate_qps=workload.rate_qps / max(time_scale, 1e-12))

    def capacity_qps(self, queries: np.ndarray,
                     n_arrivals: "int | None" = None) -> float:
        """Measured closed-loop throughput (the exec analogue of the
        simulator's ``capacity_qps`` bound)."""
        b = len(queries)
        n = n_arrivals or b
        res = self.run(queries, trace_idx=np.arange(n) % b)
        return res.throughput_qps

    # -------------------------------------------------------------- admin --
    def close(self) -> None:
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        for inbox in self._inboxes:
            inbox.stop()
        for w in self._workers:
            w.join(timeout=10.0)
        if self.mode == "process":
            for w in self._workers:
                if w.is_alive():
                    w.terminate()

    def __enter__(self) -> "AsyncServingTier":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
