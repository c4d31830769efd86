"""Fixed-shape beam search (Algorithm 1) + the W-wide I/O pipeline.

Two flavours:

* ``search_inmem`` — full-precision in-memory search used for graph
  construction and for the replicated head index (§4.2).  W=1, returns the
  visited set needed by robust-prune.
* ``step_disk`` / ``search_disk`` — the DiskANN-style disk search: PQ-guided
  beam, W parallel "sector reads" (Alg. 1 line 6), exact distances of read
  nodes accumulated into the rerank pool.  This single function is reused by
  the single-server baseline, the scatter-gather baseline, and (via the
  partition-aware frontier mask of Alg. 2) the distributed baton search.

Everything is shape-static and ``vmap``/``shard_map``-compatible.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import pq
from repro.core.state import INF, NO_ID, Counters, QueryState

# ---------------------------------------------------------------------------
# shared fixed-shape primitives
# ---------------------------------------------------------------------------


def merge_into_beam(beam_ids, beam_dists, beam_expl, cand_ids, cand_dists):
    """Insert candidates into the beam; dedup by id; keep best L by distance.

    Candidate padding must be (NO_ID, INF).  Returns (ids, dists, expl)
    sorted ascending by distance — so the beam is always distance-ordered.
    """
    L = beam_ids.shape[0]
    ids = jnp.concatenate([beam_ids, cand_ids])
    dists = jnp.concatenate([beam_dists, cand_dists])
    expl = jnp.concatenate([beam_expl, jnp.zeros(cand_ids.shape, bool)])

    # pass 1: group duplicates (same id adjacent; explored copy first).
    order = jnp.lexsort((dists, ~expl, ids))
    ids, dists, expl = ids[order], dists[order], expl[order]
    dup = jnp.concatenate([jnp.array([False]), ids[1:] == ids[:-1]])
    dists = jnp.where(dup, INF, dists)
    ids = jnp.where(dup, NO_ID, ids)
    expl = jnp.where(dup, False, expl)

    # pass 2: order by distance, truncate to L.
    order = jnp.lexsort((ids, dists))[:L]
    return ids[order], dists[order], expl[order]


def select_frontier(beam_ids, beam_expl, w: int):
    """Top-W nearest unexplored beam entries (beam is distance-sorted).

    Returns (positions (W,), ids (W,), valid (W,) bool).
    """
    L = beam_ids.shape[0]
    cand = (~beam_expl) & (beam_ids != NO_ID)
    pos = jnp.where(cand, jnp.arange(L), L)
    pos = jnp.sort(pos)[:w]
    valid = pos < L
    safe = jnp.clip(pos, 0, L - 1)
    return safe, jnp.where(valid, beam_ids[safe], NO_ID), valid


def merge_pool(pool_ids, pool_dists, new_ids, new_dists):
    """Insert exact-distance results into the fixed-size rerank pool."""
    P = pool_ids.shape[0]
    ids = jnp.concatenate([pool_ids, new_ids])
    dists = jnp.concatenate([pool_dists, new_dists])
    order = jnp.lexsort((dists, ids))
    ids, dists = ids[order], dists[order]
    dup = jnp.concatenate([jnp.array([False]), ids[1:] == ids[:-1]])
    dists = jnp.where(dup, INF, dists)
    ids = jnp.where(dup, NO_ID, ids)
    order = jnp.lexsort((ids, dists))[:P]
    return ids[order], dists[order]


def _contains(haystack_ids, needle_ids):
    """For each needle, is it present in haystack?  (H,) x (C,) -> (C,) bool."""
    eq = haystack_ids[None, :] == needle_ids[:, None]
    return jnp.any(eq & (needle_ids[:, None] != NO_ID), axis=1)


def _contains_rows(haystack_ids, needle_ids):
    """Row-wise _contains: (B, H) x (B, C) -> (B, C) bool."""
    eq = haystack_ids[:, None, :] == needle_ids[:, :, None]
    return jnp.any(eq & (needle_ids[:, :, None] != NO_ID), axis=2)


# ---------------------------------------------------------------------------
# fused merges — the inner-loop hot path
#
# ``merge_into_beam``/``merge_pool`` above pay two lexsorts per call: one to
# group duplicates, one to re-order by distance.  On the disk-search hot path
# the candidates are *already* deduplicated against the beam and the pool
# (step_disk masks them via _contains before scoring), so a single
# sort-by-(dist, id) is sufficient and bit-identical.  Both run batched over
# a leading row axis so the baton engine merges all S resident slots in one
# call; ``impl="bitonic"`` routes the selection through the Pallas bitonic
# top-k kernel (kernels/topk) instead of lexsort.
# ---------------------------------------------------------------------------


def _ordered_take(ids, dists, k: int, extra=None):
    """Best k rows-wise by (dist, id): one lexsort instead of two."""
    order = jnp.lexsort((ids, dists), axis=-1)[:, :k]
    take = lambda x: jnp.take_along_axis(x, order, axis=1)  # noqa: E731
    return take(ids), take(dists), (take(extra) if extra is not None else None)


def merge_into_beam_fused(beam_ids, beam_dists, beam_expl, cand_ids,
                          cand_dists, impl: str = "lexsort"):
    """Batched single-pass beam merge: (B, L) beam x (B, C) candidates.

    REQUIRES candidates deduplicated against the beam and among themselves
    (padding (NO_ID, INF) entries excepted) — step_disk guarantees this.
    Output order matches ``merge_into_beam`` bitwise under that precondition.
    """
    L = beam_ids.shape[-1]
    if impl == "bitonic":
        from repro.kernels.topk.ops import merge_topk

        # Carry the explored flag through the sort as a packed payload so
        # recovery is O(L) unpacking instead of an O(L²) id match against
        # the pre-merge beam (ROADMAP follow-up).  The flag rides in the
        # LOW bit — ``id*2 + flag`` is strictly monotone in id for distinct
        # ids, so the kernel's (dist, payload) tie order equals the lexsort
        # path's (dist, id) order bit-for-bit (a high-bit flag would let an
        # explored entry lose ties it should win).  Requires |id| < 2³⁰;
        # NO_ID (-1) packs to -2 and arithmetic-shifts back to -1.
        packed_beam = (beam_ids << 1) | beam_expl.astype(jnp.int32)
        packed_cand = cand_ids << 1              # candidates are unexplored
        packed, dists = merge_topk(packed_beam, beam_dists, packed_cand,
                                   cand_dists, L)
        return packed >> 1, dists, (packed & 1) == 1
    ids = jnp.concatenate([beam_ids, cand_ids], axis=1)
    dists = jnp.concatenate([beam_dists, cand_dists], axis=1)
    expl = jnp.concatenate(
        [beam_expl, jnp.zeros(cand_ids.shape, bool)], axis=1
    )
    ids, dists, expl = _ordered_take(ids, dists, L, extra=expl)
    return ids, dists, expl


def seed_beam_fused(start_ids, start_dists, L: int):
    """Seed an *empty* beam from head-index start candidates (refill path).

    ``merge_into_beam`` pays two (L+n)-length lexsorts; here the beam is
    empty, so it suffices to dedup the tiny start list (keep the
    best-distance copy per id) and run the single-sort fused merge.
    Bit-identical to ``merge_into_beam(empty_beam, starts)`` —
    tests/test_cluster_sim.py::test_seed_beam_fused_bit_identical.
    """
    order = jnp.lexsort((start_dists, start_ids))      # per-id best first
    si, sd = start_ids[order], start_dists[order]
    dup = jnp.concatenate([jnp.array([False]), si[1:] == si[:-1]])
    si = jnp.where(dup, NO_ID, si)
    sd = jnp.where(dup, INF, sd)
    ids, dists, expl = merge_into_beam_fused(
        jnp.full((1, L), NO_ID, jnp.int32),
        jnp.full((1, L), INF, jnp.float32),
        jnp.zeros((1, L), bool), si[None], sd[None],
    )
    return ids[0], dists[0], expl[0]


def merge_pool_fused(pool_ids, pool_dists, new_ids, new_dists,
                     impl: str = "lexsort"):
    """Batched single-pass pool merge; same precondition as the beam merge
    (new ids not already pooled — reads are unique by the explored-flag
    invariant)."""
    P = pool_ids.shape[-1]
    if impl == "bitonic":
        from repro.kernels.topk.ops import merge_topk

        return merge_topk(pool_ids, pool_dists, new_ids, new_dists, P)
    ids = jnp.concatenate([pool_ids, new_ids], axis=1)
    dists = jnp.concatenate([pool_dists, new_dists], axis=1)
    ids, dists, _ = _ordered_take(ids, dists, P)
    return ids, dists


# ---------------------------------------------------------------------------
# in-memory full-precision search (graph build + head index)
# ---------------------------------------------------------------------------


class InMemResult(NamedTuple):
    beam_ids: jnp.ndarray     # (L,) distance-sorted
    beam_dists: jnp.ndarray   # (L,)
    visited_ids: jnp.ndarray  # (V,) expanded nodes in expansion order
    visited_dists: jnp.ndarray
    hops: jnp.ndarray
    dist_comps: jnp.ndarray


@partial(jax.jit, static_argnames=("L", "max_hops"))
def search_inmem(
    vectors: jnp.ndarray,     # (N, d) float32
    neighbors: jnp.ndarray,   # (N, R) int32, NO_ID padding
    query: jnp.ndarray,       # (d,)
    start_ids: jnp.ndarray,   # (S,) int32
    L: int = 64,
    max_hops: int = 256,
) -> InMemResult:
    """Full-precision greedy beam search (W=1).  Oracle-checked in tests."""
    R = neighbors.shape[1]

    def dist_to(ids):
        v = vectors[jnp.clip(ids, 0, vectors.shape[0] - 1)]
        d = jnp.sum((v - query[None, :]) ** 2, -1)
        return jnp.where(ids == NO_ID, INF, d)

    s = start_ids.shape[0]
    beam_ids = jnp.full((L,), NO_ID, jnp.int32).at[:s].set(start_ids)
    beam_dists = dist_to(beam_ids)
    # dedup starting ids
    beam_ids, beam_dists, beam_expl = merge_into_beam(
        jnp.full((L,), NO_ID, jnp.int32), jnp.full((L,), INF), jnp.zeros((L,), bool),
        beam_ids, beam_dists,
    )

    visited_ids = jnp.full((max_hops,), NO_ID, jnp.int32)
    visited_dists = jnp.full((max_hops,), INF)

    def cond(c):
        beam_ids, beam_expl, *_, hops, _ = c
        _, _, valid = select_frontier(beam_ids, beam_expl, 1)
        return jnp.any(valid) & (hops < max_hops)

    def body(c):
        beam_ids, beam_expl, beam_dists, vis_i, vis_d, hops, dcs = c
        fpos, fids, fvalid = select_frontier(beam_ids, beam_expl, 1)
        u = fids[0]
        beam_expl = beam_expl.at[fpos[0]].set(True)
        vis_i = vis_i.at[hops].set(u)
        vis_d = vis_d.at[hops].set(beam_dists[fpos[0]])
        nbrs = neighbors[jnp.clip(u, 0, neighbors.shape[0] - 1)]
        nbrs = jnp.where(u == NO_ID, NO_ID, nbrs)
        # skip nodes already in beam or already expanded
        known = _contains(beam_ids, nbrs) | _contains(vis_i, nbrs)
        nbrs = jnp.where(known, NO_ID, nbrs)
        nd = dist_to(nbrs)
        dcs = dcs + jnp.sum(nbrs != NO_ID)
        beam_ids, beam_dists, beam_expl = merge_into_beam(
            beam_ids, beam_dists, beam_expl, nbrs, nd
        )
        return beam_ids, beam_expl, beam_dists, vis_i, vis_d, hops + 1, dcs

    beam_ids, beam_expl, beam_dists, visited_ids, visited_dists, hops, dcs = (
        jax.lax.while_loop(
            cond,
            body,
            (
                beam_ids, beam_expl, beam_dists, visited_ids, visited_dists,
                jnp.int32(0), jnp.int32(s),
            ),
        )
    )
    return InMemResult(beam_ids, beam_dists, visited_ids, visited_dists, hops, dcs)


# ---------------------------------------------------------------------------
# disk-style PQ-guided search (Alg. 1 with the W-wide I/O pipeline)
# ---------------------------------------------------------------------------


class Shard(NamedTuple):
    """One partition's 'SSD': sector-resident data, local-id indexed.

    For the single-server baseline there is one shard covering everything and
    node2local is the identity.  ``codes``/``node2part``/``node2local`` are
    global + replicated (paper §5 'Memory footprint').

    ``nbr_codes`` enables the AiSAQ-style sector layout (paper §5/§8 future
    work, our §Perf memory optimization): each sector also stores its
    neighbors' PQ codes (R x M bytes, still within the 4 KB sector budget),
    so the 32 GB replicated code array is not needed — ``codes`` may then be
    a (1, M) placeholder.
    """

    vectors: jnp.ndarray      # (Np, d) float32 — full-precision, on "disk"
    neighbors: jnp.ndarray    # (Np, R) int32 global ids — on "disk"
    codes: jnp.ndarray        # (N, M) uint8 — replicated PQ codes (in memory)
    node2part: jnp.ndarray    # (N,) int32 — replicated routing map
    node2local: jnp.ndarray   # (N,) int32 — global -> local slot on owner
    nbr_codes: jnp.ndarray | None = None  # (Np, R, M) uint8 — sector mode


def read_sectors(shard: Shard, gids: jnp.ndarray):
    """Simulated sector read: full vector + adjacency (+ neighbor codes in
    sector mode) for owned global ids."""
    loc = shard.node2local[jnp.clip(gids, 0, shard.node2local.shape[0] - 1)]
    loc = jnp.clip(loc, 0, shard.vectors.shape[0] - 1)
    # sectors may store vectors in the dataset's native dtype (uint8 for
    # BIGANN-class data) — distance math is always f32
    vecs = shard.vectors[loc].astype(jnp.float32)
    nbrs = shard.neighbors[loc]
    ok = gids != NO_ID
    ncodes = shard.nbr_codes[loc] if shard.nbr_codes is not None else None
    return (
        jnp.where(ok[:, None], vecs, 0.0),
        jnp.where(ok[:, None], nbrs, NO_ID),
        ncodes,
    )


def step_disk(
    state: QueryState,
    shard: Shard,
    lut: jnp.ndarray,          # (M, K) PQ lookup table for state.query
    frontier_mask: jnp.ndarray,  # (W,) bool — which frontier slots to expand
    frontier_pos: jnp.ndarray,   # (W,) beam positions of the frontier
    fused: bool = True,
    merge_impl: str = "lexsort",
) -> QueryState:
    """Expand the masked frontier nodes: read sectors, rerank, grow beam.

    The caller (single-node / baton / scatter-gather driver) picks the
    frontier and the mask — Alg. 2's locality heuristic lives there.
    ``fused=False`` selects the original double-lexsort merges (the seed
    reference path the fused merges are equivalence-tested against).
    """
    W = frontier_mask.shape[0]
    with jax.named_scope("sectors"):
        gids = jnp.where(frontier_mask, state.beam_ids[frontier_pos], NO_ID)
        vecs, nbrs, ncodes = read_sectors(shard, gids)           # (W,d),(W,R)
        # exact distances of the expanded nodes -> rerank pool
        ed = pq.ordered_sum((vecs - state.query[None, :]) ** 2)
        ed = jnp.where(gids == NO_ID, INF, ed)
    with jax.named_scope("merge"):
        if fused:
            pool_ids, pool_dists = merge_pool_fused(
                state.pool_ids[None], state.pool_dists[None], gids[None],
                ed[None], impl=merge_impl,
            )
            pool_ids, pool_dists = pool_ids[0], pool_dists[0]
        else:
            pool_ids, pool_dists = merge_pool(
                state.pool_ids, state.pool_dists, gids, ed
            )

        # mark frontier explored.  NOTE: frontier_pos contains duplicate
        # (clipped) indices for invalid lanes — the scatter must be
        # order-independent, so accumulate with add and OR the result (a
        # plain .set() lets a padding lane's no-op write erase a real mark
        # at the same position).
        mark = jnp.zeros_like(state.beam_expl, dtype=jnp.int32).at[
            frontier_pos].add(frontier_mask.astype(jnp.int32))
        beam_expl = state.beam_expl | (mark > 0)

        # candidate neighbors: dedup against beam and pool
        cand = nbrs.reshape(-1)                                  # (W*R,)
        known = _contains(state.beam_ids, cand) | _contains(pool_ids, cand)
        cand = jnp.where(known, NO_ID, cand)
    with jax.named_scope("adc"):
        # PQ distances: sector-resident neighbor codes (AiSAQ mode) or the
        # replicated global code array (paper baseline)
        if ncodes is not None:
            cand_codes = ncodes.reshape(-1, ncodes.shape[-1])    # (W*R, M)
        else:
            cand_codes = shard.codes[
                jnp.clip(cand, 0, shard.codes.shape[0] - 1)]
        cd_flat = pq.adc(lut[None], cand_codes)[0]
    with jax.named_scope("merge"):
        # dedup within candidates (same neighbor from two expanded nodes)
        order = jnp.lexsort((cand,))
        cs = cand[order]
        dupm = jnp.concatenate([jnp.array([False]), cs[1:] == cs[:-1]])
        cand = jnp.where(dupm, NO_ID, cs)
        cd = jnp.where(cand == NO_ID, INF, cd_flat[order])

        if fused:
            beam_ids, beam_dists, beam_expl = merge_into_beam_fused(
                state.beam_ids[None], state.beam_dists[None],
                beam_expl[None], cand[None], cd[None], impl=merge_impl,
            )
            beam_ids, beam_dists, beam_expl = (
                beam_ids[0], beam_dists[0], beam_expl[0]
            )
        else:
            beam_ids, beam_dists, beam_expl = merge_into_beam(
                state.beam_ids, state.beam_dists, beam_expl, cand, cd
            )

        n_read = jnp.sum(gids != NO_ID)
        c = state.counters
        counters = c._replace(
            hops=c.hops + (n_read > 0).astype(jnp.int32),
            dist_comps=c.dist_comps + jnp.sum(cand != NO_ID) + n_read,
            reads=c.reads + n_read,
        )
    return state._replace(
        beam_ids=beam_ids, beam_dists=beam_dists, beam_expl=beam_expl,
        pool_ids=pool_ids, pool_dists=pool_dists, counters=counters,
    )


def step_disk_batched(
    states: QueryState,        # every leaf has leading (S,) axis
    shard: Shard,
    luts: jnp.ndarray,         # (S, M, K) per-slot PQ LUTs
    masks: jnp.ndarray,        # (S, W) bool — frontier lanes to expand
    fposs: jnp.ndarray,        # (S, W) beam positions of the frontiers
    adc_impl: str = "gather",
    merge_impl: str = "lexsort",
) -> QueryState:
    """Slot-batched ``step_disk``: one super-step of work for all S resident
    states in single fused ops.

    Candidate PQ scoring is one (S, W·R) call — ``pq.adc_slots`` (XLA
    gather, the default, bit-identical to the per-slot path), the dense Pallas MXU
    one-hot kernel (``adc_impl="mxu"``, ulp-level differences), or the
    slot-tiled Pallas grid (``adc_impl="mxu_tiled"``, bit-identical to the
    gather without the dense route's S× FLOP overcommit) — instead of S
    vmapped gathers, and
    both merges run once over all rows.  Per-slot semantics, counters and
    returned values match vmapping ``step_disk`` exactly (equivalence-tested).
    """
    S, W = masks.shape
    with jax.named_scope("sectors"):
        gids = jnp.where(
            masks, jnp.take_along_axis(states.beam_ids, fposs, axis=1), NO_ID
        )                                                        # (S, W)
        vecs, nbrs, ncodes = read_sectors(shard, gids.reshape(-1))
        vecs = vecs.reshape(S, W, -1)                            # (S, W, d)
        R = nbrs.shape[-1]
        nbrs = nbrs.reshape(S, W, R)
        ed = pq.ordered_sum((vecs - states.query[:, None, :]) ** 2)  # (S, W)
        ed = jnp.where(gids == NO_ID, INF, ed)
    with jax.named_scope("merge"):
        pool_ids, pool_dists = merge_pool_fused(
            states.pool_ids, states.pool_dists, gids, ed, impl=merge_impl
        )

        # order-independent explored scatter (see step_disk note)
        mark = jnp.zeros_like(states.beam_expl, dtype=jnp.int32)
        mark = mark.at[jnp.arange(S)[:, None], fposs].add(
            masks.astype(jnp.int32))
        beam_expl = states.beam_expl | (mark > 0)

        cand = nbrs.reshape(S, W * R)
        known = _contains_rows(states.beam_ids, cand) | \
            _contains_rows(pool_ids, cand)
        cand = jnp.where(known, NO_ID, cand)

    # --- the fused scoring call: all S slots at once -----------------------
    with jax.named_scope("adc"):
        if ncodes is not None:
            cand_codes = ncodes.reshape(S, W * R, ncodes.shape[-1])
        else:
            cand_codes = shard.codes[
                jnp.clip(cand, 0, shard.codes.shape[0] - 1)]
        if adc_impl == "mxu":
            from repro.kernels.pq_adc.ops import pq_adc_slots

            cd_flat = pq_adc_slots(luts, cand_codes.astype(jnp.int32))
        elif adc_impl == "mxu_tiled":
            # slot-tiled Pallas grid: (S, C) work, bit-identical to the
            # gather
            from repro.kernels.pq_adc.ops import pq_adc_slots_tiled

            cd_flat = pq_adc_slots_tiled(luts, cand_codes.astype(jnp.int32))
        else:
            cd_flat = pq.adc_slots(luts, cand_codes)             # (S, W*R)

    with jax.named_scope("merge"):
        order = jnp.argsort(cand, axis=1, stable=True)
        cs = jnp.take_along_axis(cand, order, axis=1)
        dupm = jnp.concatenate(
            [jnp.zeros((S, 1), bool), cs[:, 1:] == cs[:, :-1]], axis=1
        )
        cand = jnp.where(dupm, NO_ID, cs)
        cd = jnp.where(
            cand == NO_ID, INF, jnp.take_along_axis(cd_flat, order, axis=1)
        )

        beam_ids, beam_dists, beam_expl = merge_into_beam_fused(
            states.beam_ids, states.beam_dists, beam_expl, cand, cd,
            impl=merge_impl,
        )

        n_read = jnp.sum(gids != NO_ID, axis=1)                  # (S,)
        c = states.counters
        counters = c._replace(
            hops=c.hops + (n_read > 0).astype(jnp.int32),
            dist_comps=c.dist_comps + jnp.sum(cand != NO_ID, axis=1) + n_read,
            reads=c.reads + n_read,
        )
    return states._replace(
        beam_ids=beam_ids, beam_dists=beam_dists, beam_expl=beam_expl,
        pool_ids=pool_ids, pool_dists=pool_dists, counters=counters,
    )


@partial(jax.jit, static_argnames=("w", "max_hops", "fused", "merge_impl"))
def search_disk(
    state: QueryState,
    shard: Shard,
    codebook: jnp.ndarray,     # (M, K, dsub)
    w: int = 8,
    max_hops: int = 512,
    fused: bool = True,
    merge_impl: str = "lexsort",
) -> QueryState:
    """Single-server disk search: run Alg. 1 until the beam is fully explored."""
    lut = pq.build_lut(codebook, state.query[None])[0]

    def cond(s):
        _, _, valid = select_frontier(s.beam_ids, s.beam_expl, 1)
        return jnp.any(valid) & (s.counters.hops < max_hops) & ~s.done

    def body(s):
        fpos, _, fvalid = select_frontier(s.beam_ids, s.beam_expl, w)
        return step_disk(s, shard, lut, fvalid, fpos, fused=fused,
                         merge_impl=merge_impl)

    out = jax.lax.while_loop(cond, body, state)
    return out._replace(done=jnp.asarray(True))


def topk_results(state: QueryState, k: int):
    """Final rerank (Alg. 1 line 11): k best exact-distance pool entries."""
    return state.pool_ids[:k], state.pool_dists[:k]
