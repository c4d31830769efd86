"""BatANN distributed state-passing search (§4) — the paper's contribution.

Execution model (TPU adaptation of the paper's asynchronous TCP relay):

* Every device owns one graph partition (its "server"/"SSD shard").
* Each device holds a fixed number S of query-state *slots* — the paper's
  fixed-count inter-query balancing (§5): finished slots are refilled from a
  local query queue immediately.
* The search runs in **super-steps**:
    1. refill   — start queued queries in free slots (head-index entry points
                  precomputed per §4.2; beam seeded with PQ distances).  The
                  query's PQ lookup table is built exactly once — at enqueue
                  (``init_device_state``) — and carried in ``QueryState.lut``
                  ever after, so the per-super-step LUT rebuild of the naive
                  engine disappears (O(1) builds per query instead of
                  O(super-steps); ``Counters.lut_builds`` proves it),
    2. advance  — inner ``while_loop``: every resident state explores all
                  *local* nodes among its top-W frontier (Alg. 2) until every
                  state is done or blocked on remote data.  The default hot
                  path is **slot-batched**: one fused candidate-scoring call
                  (``pq.adc_slots`` gather, or the Pallas MXU one-hot kernel
                  via ``BatonParams.adc_impl``) and single-pass sort-merges
                  (``merge_into_beam_fused``; ``BatonParams.merge_impl``
                  routes them through the bitonic top-k kernel) cover all S
                  resident states per iteration.  ``BatonParams.fused=False``
                  keeps the original per-slot reference path for equivalence
                  testing — both return bit-identical results,
    3. route    — blocked states are handed off to the owner of their top
                  frontier node over a capacity-bounded ``all_to_all`` (the
                  paper's opportunistic message batching).  A deterministic
                  credit protocol (want/free all_gather -> waterfill grant)
                  guarantees receivers always have free slots: ungranted
                  states simply retry next super-step (backpressure).
                  Done states return to the query's home device over a
                  *separate result channel* carrying only (qid, top-k,
                  counters) — the paper's client-return arrow ③ and also its
                  §8 "Reducing Message Size" optimization.  Results need no
                  slots, so the done channel always drains (liveness).
                  ``BatonParams.ship_lut`` picks the other §8 tradeoff: ship
                  the (M·K·4-byte) LUT inside the envelope, or drop it from
                  the wire and have the receiver rebuild it from the query
                  embedding on arrival (+1 ``lut_builds`` per hand-off).  The
                  envelope-bytes consequence flows through
                  ``state.envelope_bytes`` into the io_sim cost model.
    4. deliver  — arrived results are written to the output arrays.
* Global termination: psum of (resident states + queued queries) == 0.

The same per-device functions are driven two ways: ``run_simulated`` (vmap
over the partition axis — single-host benchmarks; bit-identical math) and
``run_spmd`` (``make_spmd_fn`` under shard_map over a real mesh axis, one
partition per device — multi-device runs and the 512-chip dry-run).

What a profiler sees (nothing is recorded unless one runs):

* every device op of the search program carries one of the ``PHASES`` as a
  ``jax.named_scope`` in its ``op_name``; a scope opened around a loop names
  the loop's own control, and inside the loop the innermost scope names an
  op;
* each stage of a search call is a host span ``baton:<stage>`` (``head``,
  ``split``, ``init``, ``upload``, ``supersteps``, ``collect``) whose
  ``call`` argument numbers the calls of this process;
* ``stats`` counts ``n_supersteps``, ``local_steps`` (local-advance loop
  bodies: per super-step, the most any partition ran — under vmap every
  body runs all P partitions, under SPMD the others wait for the slowest at
  the exchange; the device states carry it as ``DeviceState.local_steps``)
  and ``adc_rows`` (``local_steps`` x the P·S·W·R candidate rows each such
  body scores).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import math
import time
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PSpec

from repro.core import beam_search, head_index, partition as part_mod, pq, vamana
from repro.core.beam_search import (
    Shard, seed_beam_fused, select_frontier, step_disk, step_disk_batched,
)
from repro.core.state import (
    INF, N_STATS, N_TRACE, NO_ID, Counters, HopTrace, QueryState, empty_state,
)

# the named scopes of the search program's device ops, in super-step order
PHASES = ("refill", "frontier", "sectors", "adc", "merge", "deliver",
          "exchange")


# ---------------------------------------------------------------------------
# configuration & index
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BatonParams:
    L: int = 64              # beam width (candidate pool length)
    W: int = 8               # I/O pipeline width (§4.4)
    k: int = 10              # results per query
    pool: int = 256          # rerank pool (full-precision result list)
    slots: int = 16          # S — resident states per device (§5: 8/thread)
    pair_cap: int = 4        # C — states per (src,dst) pair per super-step
    result_cap: int = 8      # result-channel capacity per (src,dst) pair
    n_starts: int = 4        # head-index entry points
    max_local_steps: int = 128
    max_supersteps: int = 512
    # --- hot-path implementation knobs (all default to the fused path) ----
    fused: bool = True       # slot-batched scoring + single-pass merges;
    #                          False = per-slot seed path (equivalence ref)
    adc_impl: str = "gather"  # "gather" (XLA gather; the default on every
    #                          platform) | "mxu" (dense Pallas one-hot,
    #                          ulp-level diffs) | "mxu_tiled" (slot-tiled
    #                          Pallas, bit-identical to gather)
    merge_impl: str = "lexsort"  # "lexsort" | "bitonic" (Pallas top-k)
    ship_lut: bool = False   # §8: ship the LUT in the envelope (True) vs
    #                          rebuild on arrival (False — the paper's
    #                          4-8 KB envelope; +1 lut_build per hand-off)
    lut_wire_dtype: str = "f32"  # §8 cont.: quantize the *shipped* LUT —
    #                          "f16" halves its wire bytes, "i8" (int8 codes
    #                          + per-subspace f32 scales) quarters them, both
    #                          at a bounded distance-error cost (only used
    #                          with ship_lut)
    lazy_queue_lut: bool = False  # build queued queries' LUTs at *refill*
    #                          (S masked builds per super-step) instead of
    #                          keeping a (Q, M, K) f32 array resident for the
    #                          whole run (~24.6 KB/query at M=24, K=256);
    #                          results and counters are identical
    trace_cap: int = 32      # residency segments recorded per query for the
    #                          cluster simulator (repro.cluster); overflow
    #                          folds into the last segment

    def __post_init__(self):
        if self.adc_impl not in ("gather", "mxu", "mxu_tiled"):
            raise ValueError(
                f"adc_impl must be gather|mxu|mxu_tiled: {self.adc_impl}")
        if self.merge_impl not in ("lexsort", "bitonic"):
            raise ValueError(
                f"merge_impl must be lexsort|bitonic: {self.merge_impl}"
            )
        if self.lut_wire_dtype not in ("f32", "f16", "i8"):
            raise ValueError(
                f"lut_wire_dtype must be f32|f16|i8: {self.lut_wire_dtype}"
            )
        if self.trace_cap < 1:
            raise ValueError(f"trace_cap must be >= 1: {self.trace_cap}")

    @property
    def refill_headroom(self) -> int:
        # keep a few slots free for in-transit states (liveness, §DESIGN-7)
        return max(1, self.pair_cap)


@dataclasses.dataclass
class BatonIndex:
    """Host-side index bundle (numpy); per-partition leaves stacked on axis 0."""

    n: int
    p: int                     # number of partitions / devices
    dim: int
    part_vectors: np.ndarray   # (P, Npmax, d) float32
    part_neighbors: np.ndarray  # (P, Npmax, R) int32 global ids
    codes: np.ndarray          # (N, M) uint8 — replicated
    codebook: np.ndarray       # (M, K, dsub) float32 — replicated
    node2part: np.ndarray      # (N,) int32 — replicated
    node2local: np.ndarray     # (N,) int32 — replicated
    head_vectors: np.ndarray   # replicated head index (§4.2)
    head_neighbors: np.ndarray
    head_sample_ids: np.ndarray
    head_medoid: int
    assign: np.ndarray         # (N,) partition assignment
    graph: "vamana.VamanaGraph"
    part_nbr_codes: "np.ndarray | None" = None  # (P, Npmax, R, M) sector mode
    build_s: dict = dataclasses.field(default_factory=dict)  # phase -> seconds

    def stacked_shards(self, sector_codes: bool = False) -> Shard:
        """Shard pytree: (P,)-leading per-partition leaves + replicated maps.

        ``sector_codes=True`` uses the AiSAQ layout: neighbor codes ride in
        the sectors and the replicated code array shrinks to a placeholder.
        """
        if sector_codes:
            assert self.part_nbr_codes is not None, "build with codes_mode='sector'"
            return Shard(
                vectors=jnp.asarray(self.part_vectors),
                neighbors=jnp.asarray(self.part_neighbors),
                codes=jnp.zeros((1, self.codes.shape[1]), jnp.uint8),
                node2part=jnp.asarray(self.node2part),
                node2local=jnp.asarray(self.node2local),
                nbr_codes=jnp.asarray(self.part_nbr_codes),
            )
        return Shard(
            vectors=jnp.asarray(self.part_vectors),
            neighbors=jnp.asarray(self.part_neighbors),
            codes=jnp.asarray(self.codes),
            node2part=jnp.asarray(self.node2part),
            node2local=jnp.asarray(self.node2local),
        )

    def head_starts(self, queries: np.ndarray, n_starts: int):
        ids, dists = head_index.search(
            jnp.asarray(self.head_vectors), jnp.asarray(self.head_neighbors),
            jnp.asarray(self.head_sample_ids), jnp.asarray(self.head_medoid),
            jnp.asarray(queries, dtype=jnp.float32), n_starts=n_starts,
        )
        return np.asarray(ids), np.asarray(dists)


def build_index(
    vectors: np.ndarray,
    p: int,
    r: int = 32,
    l_build: int = 64,
    alpha: float = 1.2,
    pq_m: int = 16,
    pq_k: int = 256,
    head_fraction: float = 0.01,
    partitioner: str = "ldg",
    seed: int = 0,
    graph: "vamana.VamanaGraph | None" = None,
    codes_mode: str = "replicated",    # or "sector" (AiSAQ layout, §Perf)
    assign: "np.ndarray | None" = None,  # pre-computed partition assignment
) -> BatonIndex:
    """Build the global graph, partition it, lay out per-partition sectors.

    The returned index's ``build_s`` holds the wall seconds of each phase
    it ran (``graph``, ``partition``, ``pq``, ``head``)."""
    vectors = np.ascontiguousarray(vectors, np.float32)
    n, d = vectors.shape
    build_s = {}
    t0 = time.perf_counter()
    if graph is None:
        graph = vamana.build(vectors, r=r, l_build=l_build, alpha=alpha, seed=seed)
        build_s["graph"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    if assign is not None:
        assign = np.asarray(assign, np.int32)
    elif partitioner == "ldg":
        assign = part_mod.ldg_partition(graph.neighbors, p, seed=seed)
    elif partitioner == "kmeans":
        assign = part_mod.balanced_kmeans(vectors, p, seed=seed)
    else:
        assign = part_mod.random_partition(n, p, seed=seed)

    node2part, node2local, local2global, _ = part_mod.build_maps(assign, p)
    npmax = local2global.shape[1]
    part_vectors = np.zeros((p, npmax, d), np.float32)
    part_neighbors = np.full((p, npmax, graph.neighbors.shape[1]), NO_ID, np.int32)
    for pi in range(p):
        ids = local2global[pi]
        ok = ids >= 0
        part_vectors[pi, ok] = vectors[ids[ok]]
        part_neighbors[pi, ok] = graph.neighbors[ids[ok]]
    build_s["partition"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    cb = pq.train(vectors, m=pq_m, k=pq_k, seed=seed)
    codes = pq.encode(cb, vectors)
    build_s["pq"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    head = head_index.build(vectors, fraction=head_fraction, seed=seed)
    build_s["head"] = time.perf_counter() - t0

    part_nbr_codes = None
    if codes_mode == "sector":
        part_nbr_codes = np.zeros(
            part_neighbors.shape + codes.shape[1:], np.uint8
        )
        safe = np.clip(part_neighbors, 0, n - 1)
        part_nbr_codes[:] = codes[safe]

    return BatonIndex(
        n=n, p=p, dim=d,
        part_vectors=part_vectors, part_neighbors=part_neighbors,
        codes=codes, codebook=np.asarray(cb.centroids),
        node2part=node2part, node2local=node2local,
        head_vectors=head.vectors, head_neighbors=head.neighbors,
        head_sample_ids=head.sample_ids, head_medoid=head.medoid,
        assign=assign, graph=graph, part_nbr_codes=part_nbr_codes,
        build_s=build_s,
    )


# ---------------------------------------------------------------------------
# per-device state & messages
# ---------------------------------------------------------------------------


class DeviceState(NamedTuple):
    states: QueryState         # every leaf has leading (S,) axis
    queue_emb: jnp.ndarray     # (Q, d)
    queue_qid: jnp.ndarray     # (Q,)  -1 = padding
    queue_starts: jnp.ndarray  # (Q, n_starts) global entry ids
    queue_start_d: jnp.ndarray  # (Q, n_starts) head-index exact distances
    queue_lut: jnp.ndarray     # (Q, M, K) per-query PQ LUTs, built once —
    #                            or a (1, M, K) placeholder when
    #                            BatonParams.lazy_queue_lut builds them at
    #                            refill instead (ROADMAP memory follow-up)
    queue_head: jnp.ndarray    # () — next queue row to start
    out_ids: jnp.ndarray       # (Q, k)
    out_dists: jnp.ndarray     # (Q, k)
    out_stats: jnp.ndarray     # (Q, N_STATS) — see state.STAT_FIELDS
    out_trace: jnp.ndarray     # (Q, T, N_TRACE) — see state.TRACE_FIELDS
    delivered: jnp.ndarray     # (Q,) bool
    local_steps: jnp.ndarray   # () local-advance bodies so far (docstring)


class ResultMsg(NamedTuple):
    """Client-return message ③ — tiny, slot-free (always deliverable)."""

    qid: jnp.ndarray           # () int32, -1 = empty
    ids: jnp.ndarray           # (k,)
    dists: jnp.ndarray         # (k,)
    stats: jnp.ndarray         # (N_STATS,)
    trace: jnp.ndarray         # (T, N_TRACE) packed HopTrace


def _empty_results(cfg: BatonParams, shape) -> ResultMsg:
    return ResultMsg(
        qid=jnp.full(shape, -1, jnp.int32),
        ids=jnp.full(shape + (cfg.k,), NO_ID, jnp.int32),
        dists=jnp.full(shape + (cfg.k,), INF, jnp.float32),
        stats=jnp.zeros(shape + (N_STATS,), jnp.int32),
        trace=jnp.full(shape + (cfg.trace_cap, N_TRACE), -1, jnp.int32),
    )


def _batched_empty_states(
    d: int, cfg: BatonParams, shape, m: int | None = None,
    k_pq: int | None = None, lut_dtype=jnp.float32,
    with_lut_scale: bool = False,
) -> QueryState:
    one = empty_state(d, cfg.L, cfg.pool, m=m, k_pq=k_pq,
                      lut_dtype=lut_dtype, trace_cap=cfg.trace_cap,
                      with_lut_scale=with_lut_scale)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, shape + x.shape), one)


def init_device_state(queries, qids, starts, start_d, cfg: BatonParams,
                      codebook) -> DeviceState:
    """Per-device state.  Builds every queued query's PQ LUT here — the one
    and only ``build_lut`` on the query's lifetime (ship mode).  With
    ``cfg.lazy_queue_lut`` the (Q, M, K) array is replaced by a (1, M, K)
    placeholder and LUTs are built at refill instead (same math, same
    counters — the build is just deferred to slot-seed time)."""
    q, d = queries.shape
    codebook = jnp.asarray(codebook)
    m, k_pq = codebook.shape[0], codebook.shape[1]
    if cfg.lazy_queue_lut:
        queue_lut = jnp.zeros((1, m, k_pq), jnp.float32)
    else:
        queue_lut = pq.build_lut(codebook, jnp.asarray(queries, jnp.float32))
    return DeviceState(
        states=_batched_empty_states(d, cfg, (cfg.slots,), m=m, k_pq=k_pq),
        queue_emb=jnp.asarray(queries, jnp.float32),
        queue_qid=jnp.asarray(qids, jnp.int32),
        queue_starts=jnp.asarray(starts, jnp.int32),
        queue_start_d=jnp.asarray(start_d, jnp.float32),
        queue_lut=queue_lut,
        queue_head=jnp.int32(0),
        out_ids=jnp.full((q, cfg.k), NO_ID, jnp.int32),
        out_dists=jnp.full((q, cfg.k), INF, jnp.float32),
        out_stats=jnp.zeros((q, N_STATS), jnp.int32),
        out_trace=jnp.full((q, cfg.trace_cap, N_TRACE), -1, jnp.int32),
        delivered=jnp.zeros((q,), bool),
        local_steps=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# super-step phases (pure, per-device; vmap/shard_map applied by drivers)
# ---------------------------------------------------------------------------


def refill(dev: DeviceState, cfg: BatonParams, my_part, codebook=None):
    """Start queued queries in free slots (paper §5 fixed-count balancing).

    The seeded state adopts the query's precomputed LUT from the queue
    (``lut_builds`` starts at 1 — the build at enqueue).  With
    ``cfg.lazy_queue_lut`` the LUTs are built *here* instead — S masked
    builds per super-step against the replicated codebook — trading a small
    recurring compute cost for not keeping (Q, M, K) floats resident
    (ROADMAP memory follow-up); the counter still reads 1 build/query."""
    q_total = dev.queue_qid.shape[0]
    free = ~dev.states.active                                   # (S,)
    n_active = jnp.sum(dev.states.active.astype(jnp.int32))
    # keep headroom for in-transit states, but never starve: at least one
    # slot is always refillable (covers the slots=1 sequential baseline)
    usable = max(cfg.slots - cfg.refill_headroom, 1)
    budget = jnp.maximum(usable - n_active, 0)
    n_left = jnp.maximum(q_total - dev.queue_head, 0)
    n_start = jnp.minimum(budget, n_left)

    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1          # (S,)
    take = free & (free_rank < n_start)
    row = jnp.clip(dev.queue_head + free_rank, 0, q_total - 1)

    emb = dev.queue_emb[row]                                    # (S, d)
    qid = dev.queue_qid[row]
    starts = dev.queue_starts[row]                              # (S, n_starts)
    if cfg.lazy_queue_lut:
        assert codebook is not None, "lazy_queue_lut needs the codebook"
        lut = pq.build_lut(jnp.asarray(codebook), emb)          # (S, M, K)
    else:
        lut = dev.queue_lut[row]                                # (S, M, K)
    take = take & (qid >= 0)
    # entry-point distances come from the (full-precision, in-memory) head
    # index — no global PQ lookup needed, which keeps the sector-codes mode
    # free of any replicated code array.
    sd = jnp.where(starts == NO_ID, INF, dev.queue_start_d[row])

    def seed_one(st, e, s_ids, s_d, q, lu, t):
        L, P = cfg.L, cfg.pool
        # fused single-sort seeding (bit-identical to the old double-lexsort
        # merge_into_beam against an empty beam)
        bi, bd, be = seed_beam_fused(s_ids, s_d, L)
        trace = HopTrace.empty(cfg.trace_cap)
        trace = trace._replace(
            part=trace.part.at[0].set(jnp.int32(my_part)),
            lut_builds=trace.lut_builds.at[0].set(1),
        )
        new = QueryState(
            query=e, beam_ids=bi, beam_dists=bd, beam_expl=be,
            pool_ids=jnp.full((P,), NO_ID, jnp.int32),
            pool_dists=jnp.full((P,), INF, jnp.float32),
            counters=Counters.zeros()._replace(lut_builds=jnp.int32(1)),
            active=jnp.asarray(True), done=jnp.asarray(False),
            home=jnp.int32(my_part), qid=q, lut=lu, trace=trace,
        )
        return jax.tree.map(lambda a, b: jnp.where(t, a, b), new, st)

    states = jax.vmap(seed_one)(dev.states, emb, starts, sd, qid, lut, take)
    return dev._replace(states=states, queue_head=dev.queue_head + n_start)


def _frontier_ownership(state: QueryState, shard: Shard, cfg: BatonParams, my_part):
    """Alg. 2: which top-W frontier nodes are local; where to hand off."""
    fpos, fids, fvalid = select_frontier(state.beam_ids, state.beam_expl, cfg.W)
    owner = shard.node2part[jnp.clip(fids, 0, shard.node2part.shape[0] - 1)]
    local = fvalid & (owner == my_part)
    dest = jnp.where(fvalid[0], owner[0], my_part)  # owner of top node (line 5)
    return fpos, local, jnp.any(local), jnp.any(fvalid), dest


def _where_rows(pred, new, old):
    """Select whole per-slot rows: pred (S,) against leaves (S, ...)."""
    return jax.tree.map(
        lambda a, b: jnp.where(
            pred.reshape(pred.shape + (1,) * (a.ndim - 1)), a, b
        ),
        new, old,
    )


def local_advance(dev: DeviceState, shard: Shard, cfg: BatonParams, my_part):
    """Inner loop: explore local frontier nodes until every resident state is
    blocked on remote data or done (Alg. 2 lines 2-3, SIMD over slots).

    Per-slot LUTs come from ``states.lut`` (built once per query).  The
    default body is the fused slot-batched step; ``cfg.fused=False`` selects
    the per-slot reference path (bit-identical results).  Returns (devices,
    the loop bodies run)."""

    def frontier(st):
        return _frontier_ownership(st, shard, cfg, my_part)

    def one(st, lut):
        fpos, local, any_local, any_frontier, _ = frontier(st)
        runnable = st.active & ~st.done & any_frontier & any_local
        mask = local & runnable
        new = step_disk(st, shard, lut, mask, fpos, fused=False)
        _, _, v = select_frontier(new.beam_ids, new.beam_expl, 1)
        new = new._replace(done=new.done | ~jnp.any(v))
        # scalar `runnable` broadcasts against every leaf shape
        return jax.tree.map(lambda a, b: jnp.where(runnable, a, b), new, st), runnable

    def step_all(states):
        if not cfg.fused:
            return jax.vmap(one)(states, states.lut)
        fposs, local, any_local, any_frontier, _ = jax.vmap(frontier)(states)
        runnable = states.active & ~states.done & any_frontier & any_local
        masks = local & runnable[:, None]
        new = step_disk_batched(
            states, shard, states.lut, masks, fposs,
            adc_impl=cfg.adc_impl, merge_impl=cfg.merge_impl,
        )
        v = jax.vmap(
            lambda st: jnp.any(
                select_frontier(st.beam_ids, st.beam_expl, 1)[2]
            )
        )(new)
        new = new._replace(done=new.done | ~v)
        return _where_rows(runnable, new, states), runnable

    def cond(carry):
        _, it, progressed = carry
        return progressed & (it < cfg.max_local_steps)

    def body(carry):
        states, it, _ = carry
        states, ran = step_all(states)
        return states, it + 1, jnp.any(ran)

    def finalize(st):
        _, _, v = select_frontier(st.beam_ids, st.beam_expl, 1)
        return st._replace(done=st.done | (st.active & ~jnp.any(v)))

    with jax.named_scope("frontier"):
        states, steps, _ = jax.lax.while_loop(
            cond, body, (dev.states, jnp.int32(0), jnp.asarray(True))
        )
        states = jax.vmap(finalize)(states)
    return dev._replace(states=states), steps


def deliver_local(dev: DeviceState, cfg: BatonParams, my_part, n_parts: int):
    """Write out results of done states homed here; free their slots."""
    st = dev.states
    ready = st.active & st.done & (st.home == my_part)
    row = jnp.where(ready, st.qid // jnp.int32(n_parts), dev.out_ids.shape[0])
    k = cfg.k
    out_ids = dev.out_ids.at[row].set(st.pool_ids[:, :k], mode="drop")
    out_dists = dev.out_dists.at[row].set(st.pool_dists[:, :k], mode="drop")
    out_stats = dev.out_stats.at[row].set(st.counters.stacked(), mode="drop")
    out_trace = dev.out_trace.at[row].set(st.trace.stacked(), mode="drop")
    delivered = dev.delivered.at[row].set(True, mode="drop")
    states = st._replace(active=st.active & ~ready)
    return dev._replace(
        states=states, out_ids=out_ids, out_dists=out_dists,
        out_stats=out_stats, out_trace=out_trace, delivered=delivered,
    )


def pack_results(dev: DeviceState, cfg: BatonParams, my_part, n_parts: int):
    """Done states homed elsewhere -> (P, Cr) result messages; free slots."""
    S, Cr = cfg.slots, cfg.result_cap
    st = dev.states
    ready = st.active & st.done & (st.home != my_part)
    d_idx = jnp.where(ready, st.home, n_parts)
    onehot = jax.nn.one_hot(d_idx, n_parts + 1, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot
    my_rank = jnp.sum(rank * onehot, axis=1)
    granted = ready & (my_rank < Cr)
    c_idx = jnp.where(granted, my_rank, Cr)

    buf = _empty_results(cfg, (n_parts, Cr))
    msg = ResultMsg(
        qid=jnp.where(granted, st.qid, -1),
        ids=st.pool_ids[:, : cfg.k],
        dists=st.pool_dists[:, : cfg.k],
        stats=st.counters.stacked(),
        trace=st.trace.stacked(),
    )
    buf = jax.tree.map(
        lambda b, leaf: b.at[d_idx, c_idx].set(leaf, mode="drop"), buf, msg
    )
    states = st._replace(active=st.active & ~granted)
    return buf, dev._replace(states=states)


def merge_results(dev: DeviceState, inc: ResultMsg, cfg: BatonParams, n_parts: int):
    """Write received result messages into the output arrays."""
    ok = inc.qid >= 0
    row = jnp.where(ok, inc.qid // jnp.int32(n_parts), dev.out_ids.shape[0])
    return dev._replace(
        out_ids=dev.out_ids.at[row].set(inc.ids, mode="drop"),
        out_dists=dev.out_dists.at[row].set(inc.dists, mode="drop"),
        out_stats=dev.out_stats.at[row].set(inc.stats, mode="drop"),
        out_trace=dev.out_trace.at[row].set(inc.trace, mode="drop"),
        delivered=dev.delivered.at[row].set(True, mode="drop"),
    )


def plan_routes(dev: DeviceState, shard: Shard, cfg: BatonParams, my_part):
    """Hand-off destination per slot (-1 = stays resident)."""

    def one(st):
        _, _, _, _, dest = _frontier_ownership(st, shard, cfg, my_part)
        want_move = st.active & ~st.done & (dest != my_part)
        return jnp.where(want_move, dest, jnp.int32(-1))

    return jax.vmap(one)(dev.states)                            # (S,)


def grant_matrix(want: jnp.ndarray, free: jnp.ndarray, pair_cap: int):
    """Deterministic waterfill: want (P,P) [src,dst], free (P,) -> grant (P,P).

    Every device computes the identical matrix, so senders and receivers
    agree without extra communication (credit-based flow control)."""
    w = jnp.minimum(want, pair_cap)
    cum = jnp.cumsum(w, axis=0) - w                              # senders before me
    return jnp.clip(jnp.minimum(w, free[None, :] - cum), 0, pair_cap)


def pack_sends(dev: DeviceState, dest: jnp.ndarray, grant_row: jnp.ndarray,
               cfg: BatonParams, n_parts: int):
    """Move granted states into a (P, C, ...) send buffer; free their slots."""
    C = cfg.pair_cap
    movable = dest >= 0
    d_idx = jnp.where(movable, dest, n_parts)                    # n_parts = drop
    onehot = jax.nn.one_hot(d_idx, n_parts + 1, dtype=jnp.int32)  # (S, P+1)
    rank = jnp.cumsum(onehot, axis=0) - onehot                   # per-dest rank
    my_rank = jnp.sum(rank * onehot, axis=1)                     # (S,)
    granted = movable & (my_rank < grant_row[jnp.clip(d_idx, 0, n_parts - 1)])
    c_idx = jnp.where(granted, my_rank, C)                       # C = drop

    # count the hand-off on the state being sent (Fig. 3/4 metric)
    states = dev.states
    inter = states.counters.inter_hops + granted.astype(jnp.int32)
    states = states._replace(counters=states.counters._replace(inter_hops=inter))
    # close the residency segment: the next one runs on `dest` (trace
    # overflow beyond trace_cap folds into the last segment)
    tr = states.trace
    T = tr.part.shape[-1]
    next_seg = jnp.clip(tr.seg + 1, 0, T - 1)
    rows = jnp.arange(dest.shape[0])
    cur_part = tr.part[rows, next_seg]
    tr = tr._replace(
        part=tr.part.at[rows, next_seg].set(
            jnp.where(granted, dest.astype(jnp.int32), cur_part)
        ),
        seg=jnp.where(granted, next_seg, tr.seg),
    )
    states = states._replace(trace=tr)
    # only shipped copies are active on arrival
    shipped = states._replace(active=states.active & granted)
    lut_dtype = jnp.float32
    with_scale = False
    if cfg.ship_lut:
        m, k_pq = states.lut.shape[-2], states.lut.shape[-1]
        if cfg.lut_wire_dtype == "f16":
            # §8 "Reducing Message Size": ship a half-precision LUT — the
            # wire tree genuinely carries M·K·2 bytes; the receiver widens
            # back to f32 (bounded quantization error, tested).
            lut_dtype = jnp.float16
            shipped = shipped._replace(lut=shipped.lut.astype(jnp.float16))
        elif cfg.lut_wire_dtype == "i8":
            # §8 cont.: int8 LUT with per-subspace scales — the wire tree
            # carries M·K bytes + M f32 scales (~4× less than f32); the
            # receiver dequantizes (bounded per-subspace error, tested).
            lut_dtype = jnp.int8
            with_scale = True
            q8, scale = pq.quantize_lut_i8(shipped.lut)
            shipped = shipped._replace(lut=q8, lut_scale=scale)
    else:
        # §8 "Reducing Message Size": drop the LUT leaf from the send tree
        # entirely, so the all_to_all genuinely moves M·K·4 fewer bytes per
        # state (not just in the cost model); merge_recv rebuilds it.
        m = k_pq = None
        shipped = shipped._replace(lut=None)
    buf = _batched_empty_states(dev.queue_emb.shape[1], cfg, (n_parts, C),
                                m=m, k_pq=k_pq, lut_dtype=lut_dtype,
                                with_lut_scale=with_scale)
    buf = jax.tree.map(
        lambda b, leaf: b.at[d_idx, c_idx].set(leaf, mode="drop"), buf, shipped
    )
    states = states._replace(active=states.active & ~granted)
    return buf, dev._replace(states=states)


def merge_recv(dev: DeviceState, incoming: QueryState, cfg: BatonParams,
               codebook=None):
    """Place incoming states (flat (P*C,) batch) into free slots.

    In recompute mode (``cfg.ship_lut=False``) the LUT did not ride in the
    envelope: rebuild it here from the (always-shipped) query embedding and
    the replicated codebook, and count the build on the state."""
    S = cfg.slots
    inc_active = incoming.active                                 # (P*C,)
    if not cfg.ship_lut:
        # the wire tree arrived without a lut leaf (see pack_sends) —
        # rebuild and reattach.  The grant protocol admits at most `free`
        # (<= S) states per super-step, so compacting the active rows to the
        # front and building only min(S, P·C) LUTs covers every row that can
        # land in a slot: rebuild work scales with the *active* incoming
        # states, not the P·C wire capacity (matters at large P).
        assert codebook is not None, "recompute mode needs the codebook"
        codebook = jnp.asarray(codebook)
        pc = inc_active.shape[0]
        cap = min(S, pc)
        order = jnp.argsort(~inc_active, stable=True)            # active first
        sel = order[:cap]
        lut_sel = pq.build_lut(codebook, incoming.query[sel])    # (cap, M, K)
        lut = jnp.zeros((pc,) + lut_sel.shape[1:], lut_sel.dtype)
        lut = lut.at[sel].set(lut_sel)
        builds = incoming.counters.lut_builds + inc_active.astype(jnp.int32)
        # the rebuild belongs to the (just-opened) arrival segment
        tr = incoming.trace
        rows = jnp.arange(pc)
        segc = jnp.clip(tr.seg, 0, tr.part.shape[-1] - 1)
        tr = tr._replace(
            lut_builds=tr.lut_builds.at[rows, segc].add(
                inc_active.astype(jnp.int32)
            )
        )
        incoming = incoming._replace(
            lut=lut, trace=tr,
            counters=incoming.counters._replace(lut_builds=builds),
        )
    elif incoming.lut.dtype == jnp.int8:
        # quantized §8 int8 wire LUT: dequantize with the shipped
        # per-subspace scales, then drop the scale leaf so the landed state
        # matches the resident tree structure
        incoming = incoming._replace(
            lut=pq.dequantize_lut_i8(incoming.lut, incoming.lut_scale),
            lut_scale=None,
        )
    elif incoming.lut.dtype != jnp.float32:
        # quantized §8 f16 wire LUT: widen back to f32 for scoring
        incoming = incoming._replace(lut=incoming.lut.astype(jnp.float32))
    inc_rank = jnp.cumsum(inc_active.astype(jnp.int32)) - 1      # among active
    free = ~dev.states.active                                    # (S,)
    free_pos = jnp.sort(jnp.where(free, jnp.arange(S), S))       # first n_free ok
    tgt = jnp.where(inc_active, free_pos[jnp.clip(inc_rank, 0, S - 1)], S)

    states = jax.tree.map(
        lambda slot_leaf, inc_leaf: slot_leaf.at[tgt].set(inc_leaf, mode="drop"),
        dev.states, incoming,
    )
    return dev._replace(states=states)


def _trace_accumulate(dev: DeviceState, pre: Counters) -> DeviceState:
    """Charge this super-step's local work (counter deltas since ``pre``,
    taken right after refill) to every state's open residency segment."""
    st = dev.states
    tr = st.trace
    seg = jnp.clip(tr.seg, 0, tr.part.shape[-1] - 1)             # (S,)
    rows = jnp.arange(seg.shape[0])
    c = st.counters

    def add(leaf, delta):
        return leaf.at[rows, seg].add(delta)

    tr = tr._replace(
        hops=add(tr.hops, c.hops - pre.hops),
        reads=add(tr.reads, c.reads - pre.reads),
        dist_comps=add(tr.dist_comps, c.dist_comps - pre.dist_comps),
        # distinct-sector footprint: every read of a query touches a fresh
        # sector (explored-flag invariant), so the segment's footprint is
        # its read count — recorded separately so sector-packed layouts can
        # diverge, and so the cluster cache model is trace-driven
        sectors=add(tr.sectors, c.reads - pre.reads),
    )
    return dev._replace(states=st._replace(trace=tr))


def _superstep_local(dev, shard, cfg, my_part, n_parts, codebook=None):
    """Phases 1-2 + route planning (everything before communication).

    No per-super-step LUT build: every resident state carries its own LUT
    (seeded at refill from the once-per-query queue build, or built at
    refill under ``cfg.lazy_queue_lut``).  The last of its results is the
    number of local-advance loop bodies run."""
    with jax.named_scope("refill"):
        dev = refill(dev, cfg, my_part, codebook=codebook)
        pre = dev.states.counters
    dev, steps = local_advance(dev, shard, cfg, my_part)
    with jax.named_scope("deliver"):
        dev = _trace_accumulate(dev, pre)
        dev = deliver_local(dev, cfg, my_part, n_parts)
        res_buf, dev = pack_results(dev, cfg, my_part, n_parts)
    with jax.named_scope("exchange"):
        dest = plan_routes(dev, shard, cfg, my_part)             # (S,)
        want = jnp.zeros((n_parts,), jnp.int32).at[
            jnp.where(dest >= 0, dest, 0)
        ].add((dest >= 0).astype(jnp.int32))
        # conservative: a state counts as occupying its slot until sent
        free = cfg.slots - jnp.sum(dev.states.active.astype(jnp.int32))
        n_active = jnp.sum(dev.states.active.astype(jnp.int32))
        n_queue = jnp.maximum(dev.queue_qid.shape[0] - dev.queue_head, 0)
        remaining = n_active + n_queue
    return dev, res_buf, dest, want, free, remaining, steps


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

_CALLS = itertools.count()
_CALL = contextvars.ContextVar("baton_call", default=-1)


@contextlib.contextmanager
def _numbered_call():
    """Number one search call for the ``call`` argument of its spans."""
    token = _CALL.set(next(_CALLS))
    try:
        yield
    finally:
        _CALL.reset(token)


def _stage(name: str):
    """The host span ``baton:<name>`` of the current search call."""
    return jax.profiler.TraceAnnotation(f"baton:{name}", call=_CALL.get())


def _split_round_robin(index, queries, cfg):
    P = index.p
    B = queries.shape[0]
    pad = (-B) % P
    if pad:
        queries = np.concatenate([queries, queries[:pad]], 0)
    Bp = queries.shape[0]
    qids = np.arange(Bp, dtype=np.int32)
    with _stage("head"):
        starts, start_dists = index.head_starts(queries, cfg.n_starts)
    with _stage("split"):
        per = Bp // P
        q_dev = np.zeros((P, per, queries.shape[1]), np.float32)
        qid_dev = np.full((P, per), -1, np.int32)
        st_dev = np.full((P, per, cfg.n_starts), NO_ID, np.int32)
        sd_dev = np.full((P, per, cfg.n_starts), np.inf, np.float32)
        for d in range(P):
            sel = qids[qids % P == d]
            q_dev[d, : len(sel)] = queries[sel]
            qid_dev[d, : len(sel)] = sel
            st_dev[d, : len(sel)] = starts[sel]
            sd_dev[d, : len(sel)] = start_dists[sel]
    return q_dev, qid_dev, st_dev, sd_dev, B, Bp, per


def _step_counts(index: BatonIndex, cfg: BatonParams, n_supersteps,
                 devs: DeviceState) -> dict:
    """The program's step counters as ``stats`` entries (module docstring)."""
    rows = index.p * cfg.slots * cfg.W * index.part_neighbors.shape[-1]
    local_steps = int(np.max(np.asarray(devs.local_steps)))
    return {"n_supersteps": int(n_supersteps), "local_steps": local_steps,
            "adc_rows": local_steps * rows}


def _collect(devs, qid_dev, cfg, B, Bp, P, per, counts):
    from repro.core.state import STAT_FIELDS

    out_ids = np.asarray(devs.out_ids).reshape(P * per, -1)
    out_dists = np.asarray(devs.out_dists).reshape(P * per, -1)
    out_stats = np.asarray(devs.out_stats).reshape(P * per, N_STATS)
    out_trace = np.asarray(devs.out_trace).reshape(
        P * per, cfg.trace_cap, N_TRACE
    )
    qid_flat = np.asarray(qid_dev).reshape(-1)
    ids = np.full((Bp, cfg.k), -1, np.int32)
    dists = np.full((Bp, cfg.k), np.inf, np.float32)
    stats = np.zeros((Bp, N_STATS), np.int64)
    trace = np.full((Bp, cfg.trace_cap, N_TRACE), -1, np.int64)
    ok = qid_flat >= 0
    ids[qid_flat[ok]] = out_ids[ok]
    dists[qid_flat[ok]] = out_dists[ok]
    stats[qid_flat[ok]] = out_stats[ok]
    trace[qid_flat[ok]] = out_trace[ok]
    ids, dists, stats = ids[:B], dists[:B], stats[:B]
    out = {f: stats[:, i] for i, f in enumerate(STAT_FIELDS)}
    out["trace"] = trace[:B]
    out.update(counts)
    out["delivered"] = float(np.asarray(devs.delivered).mean())
    return ids, dists, out


def run_simulated(index: BatonIndex, queries: np.ndarray, cfg: BatonParams,
                  sector_codes: bool = False):
    """Single-host driver: partition axis vmapped; routing via transpose.

    Bit-identical math to the SPMD path; the measurement substrate for every
    paper figure (counters are exact; time comes from io_sim's cost model).
    """
    with _numbered_call():
        devs, codebook, split = _initial_devices(index, queries, cfg)
        with _stage("upload"):
            shard = jax.block_until_ready(
                index.stacked_shards(sector_codes=sector_codes))
        with _stage("supersteps"):
            devs, n_supersteps = jax.block_until_ready(
                run_supersteps(devs, shard, codebook, cfg=cfg, P=index.p))
        with _stage("collect"):
            return _collect(devs, *split, _step_counts(
                index, cfg, n_supersteps, devs))


def _initial_devices(index: BatonIndex, queries: np.ndarray, cfg: BatonParams):
    """Queries dealt round-robin to the P devices' queues: (stacked device
    states, codebook, the arguments :func:`_collect` needs besides them)."""
    q_dev, qid_dev, st_dev, sd_dev, B, Bp, per = _split_round_robin(
        index, queries, cfg)
    with _stage("init"):
        codebook = jnp.asarray(index.codebook)
        devs = jax.block_until_ready(jax.vmap(
            lambda q, i, s, sd: init_device_state(q, i, s, sd, cfg, codebook)
        )(
            jnp.asarray(q_dev), jnp.asarray(qid_dev), jnp.asarray(st_dev),
            jnp.asarray(sd_dev)
        ))
    return devs, codebook, (qid_dev, cfg, B, Bp, index.p, per)


@partial(jax.jit, static_argnames=("cfg", "P"))
def run_supersteps(devs: DeviceState, shard: Shard, codebook, cfg: BatonParams,
                   P: int):
    """Super-steps of all P vmapped devices until every query is delivered.

    The index arrives as arguments (``shard``, ``codebook``), never as
    constants baked into the program, so one compilation per (cfg, P,
    shapes) serves every call.  Returns (final devices, super-step count);
    each super-step adds the local-advance bodies it ran (each runs all P
    partitions) to every device's ``local_steps``.
    """
    shard_axes = Shard(vectors=0, neighbors=0, codes=None, node2part=None,
                       node2local=None,
                       nbr_codes=None if shard.nbr_codes is None else 0)

    def superstep(devs):
        devs, res_buf, dest, want, free, remaining, steps = jax.vmap(
            lambda dv, sh, mp: _superstep_local(dv, sh, cfg, mp, P,
                                                codebook=codebook),
            in_axes=(0, shard_axes, 0),
        )(devs, shard, jnp.arange(P, dtype=jnp.int32))
        with jax.named_scope("exchange"):
            grant = grant_matrix(want, free, cfg.pair_cap)       # (P, P)
            bufs, devs = jax.vmap(
                lambda dv, de, gr: pack_sends(dv, de, gr, cfg, P)
            )(devs, dest, grant)
            # all_to_all == transpose of the (src, dst) axes in simulation
            inc_states = jax.tree.map(
                lambda x: jnp.swapaxes(x, 0, 1).reshape(
                    (P, P * cfg.pair_cap) + x.shape[3:]
                ),
                bufs,
            )
            inc_res = jax.tree.map(
                lambda x: jnp.swapaxes(x, 0, 1).reshape(
                    (P, P * cfg.result_cap) + x.shape[3:]
                ),
                res_buf,
            )
            devs = jax.vmap(
                lambda dv, inc: merge_recv(dv, inc, cfg, codebook)
            )(devs, inc_states)
            devs = jax.vmap(
                lambda dv, inc: merge_results(dv, inc, cfg, P))(devs, inc_res)
            # the vmapped local advance ran as many bodies as its longest
            devs = devs._replace(local_steps=devs.local_steps + jnp.max(steps))
            return devs, jnp.sum(remaining)

    def cond(c):
        _, it, rem = c
        return (rem > 0) & (it < cfg.max_supersteps)

    def body(c):
        devs, it, _ = c
        devs, rem = superstep(devs)
        return devs, it + 1, rem

    with jax.named_scope("exchange"):
        devs, n_supersteps, _ = jax.lax.while_loop(
            cond, body, (devs, jnp.int32(0), jnp.int32(1)))
    return devs, n_supersteps


def make_spmd_fn(cfg: BatonParams, n_parts: int, axis_name: str = "part"):
    """shard_map body for a mesh axis of size n_parts.

    dev: per-device state (sharded on axis 0 outside), shard: per-partition
    leaves sharded, maps/codes replicated.  Returns (final DeviceState,
    super-step count), as :func:`run_supersteps` does: each super-step adds
    the local-advance bodies of its slowest partition to ``local_steps``.
    """

    def fn(dev: DeviceState, shard: Shard, codebook):
        def cond(c):
            _, it, rem = c
            return (rem > 0) & (it < cfg.max_supersteps)

        def body(c):
            dev, it, _ = c
            dev, res_buf, dest, want, free, remaining, ran = \
                _superstep_local(dev, shard, cfg, my_part, n_parts,
                                 codebook=codebook)
            with jax.named_scope("exchange"):
                want_all = jax.lax.all_gather(want, axis_name)   # (P, P)
                free_all = jax.lax.all_gather(free, axis_name)   # (P,)
                grant = grant_matrix(want_all, free_all, cfg.pair_cap)
                buf, dev = pack_sends(dev, dest, grant[my_part], cfg, n_parts)
                inc = jax.tree.map(
                    lambda x: jax.lax.all_to_all(
                        x, axis_name, split_axis=0, concat_axis=0, tiled=True
                    ).reshape((n_parts * cfg.pair_cap,) + x.shape[2:]),
                    buf,
                )
                inc_res = jax.tree.map(
                    lambda x: jax.lax.all_to_all(
                        x, axis_name, split_axis=0, concat_axis=0, tiled=True
                    ).reshape((n_parts * cfg.result_cap,) + x.shape[2:]),
                    res_buf,
                )
                dev = merge_recv(dev, inc, cfg, codebook)
                dev = merge_results(dev, inc_res, cfg, n_parts)
                rem = jax.lax.psum(remaining, axis_name)
                dev = dev._replace(local_steps=dev.local_steps
                                   + jax.lax.pmax(ran, axis_name))
            return dev, it + 1, rem

        with jax.named_scope("exchange"):
            my_part = jax.lax.axis_index(axis_name).astype(jnp.int32)
            dev, n_supersteps, _ = jax.lax.while_loop(
                cond, body, (dev, jnp.int32(0), jnp.int32(1)))
        return dev, n_supersteps

    return fn


def _axis_size(mesh, axis_name) -> int:
    names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    return math.prod(mesh.shape[a] for a in names)


def _spmd_shardings(mesh, axis_name, sector_codes: bool):
    """(device states, shard, codebook) shardings: per-partition leaves split
    along ``axis_name``, global maps and the codebook replicated."""
    part, rep = NamedSharding(mesh, PSpec(axis_name)), NamedSharding(mesh, PSpec())
    shard = Shard(vectors=part, neighbors=part, codes=rep, node2part=rep,
                  node2local=rep, nbr_codes=part if sector_codes else None)
    return part, shard, rep


@lru_cache(maxsize=8)
def spmd_program(cfg: BatonParams, mesh, axis_name="part",
                 sector_codes: bool = False):
    """The search as one SPMD program: :func:`make_spmd_fn` under
    ``shard_map``, partition p on the p-th device along ``axis_name``.

    ``(devs, shard, codebook) -> (devs, n_supersteps)``, with the stacked
    device states and the per-partition shard leaves split along their
    leading axis and the rest replicated (the layout of
    :func:`run_supersteps`' arguments), and the count replicated.
    Cached per (cfg, mesh, axes), so repeated calls reuse one compilation.
    """
    fn = make_spmd_fn(cfg, n_parts=_axis_size(mesh, axis_name),
                      axis_name=axis_name)

    def body(dv, s, cb):
        s1 = s._replace(
            vectors=s.vectors[0], neighbors=s.neighbors[0],
            nbr_codes=None if s.nbr_codes is None else s.nbr_codes[0])
        out, n_supersteps = fn(jax.tree.map(lambda x: x[0], dv), s1, cb)
        return jax.tree.map(lambda x: x[None], out), n_supersteps

    shardings = _spmd_shardings(mesh, axis_name, sector_codes)
    specs = jax.tree.map(lambda s: s.spec, shardings,
                         is_leaf=lambda x: isinstance(x, NamedSharding))
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=specs,
                      out_specs=(specs[0], PSpec()),
                      check_vma=False),
        in_shardings=shardings, donate_argnums=(0,))


def run_spmd(index: BatonIndex, queries: np.ndarray, cfg: BatonParams, mesh):
    """Multi-device driver: one partition per device of ``mesh`` (all its
    axes, flattened), states routed with ``all_to_all``.

    Returns :func:`run_simulated`'s (ids, dists, stats), bit-identical to
    it.  ``stats["part_device"]`` lists, for each partition, the id of the
    device that holds its vectors, read from the placed array.
    """
    if mesh.size != index.p:
        raise ValueError(f"{index.p} partitions on {mesh.size} devices")
    axes = tuple(mesh.axis_names)
    with _numbered_call():
        devs, codebook, split = _initial_devices(index, queries, cfg)
        with _stage("upload"):
            args = jax.block_until_ready(jax.device_put(
                (devs, index.stacked_shards(), codebook),
                _spmd_shardings(mesh, axes, False)))
        placed = {s.index[0].start or 0: s.device.id
                  for s in args[1].vectors.addressable_shards}
        with _stage("supersteps"):
            out, n_supersteps = jax.block_until_ready(
                spmd_program(cfg, mesh, axes)(*args))
        with _stage("collect"):
            ids, dists, stats = _collect(out, *split, _step_counts(
                index, cfg, n_supersteps, out))
    stats["part_device"] = [placed.get(p) for p in range(index.p)]
    return ids, dists, stats
