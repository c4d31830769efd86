"""Batched bitonic top-k kernel — the beam-merge hot path.

Beam search merges (beam ∪ candidates) and keeps the best L by PQ distance on
every hop (Alg. 1 line 10).  On TPU the natural in-VMEM formulation is a
bitonic sorting network over the row of C = L + W·R entries: log²C
compare-exchange stages, each a full-width vector op (no data-dependent
control flow).  Indices ride along; ties break by index so the kernel is a
permutation (required for the dedup logic upstream).

The partner of lane i in a stage of distance j is lane i XOR j: i + j in the
lower half of each pair, i - j in the upper.  Both come from lane rotations
(``pltpu.roll``, which Mosaic lowers to a native rotate).  Rotating the lane
index alongside the data says which rotation brought the partner, so the
exchange does not depend on the rotation's direction convention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TB = 8  # rows per tile


def _bitonic_stage(vals, idxs, kk: int, jj: int):
    b, c = vals.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    lower = (lane & jj) == 0                          # lower half of pair?
    partner = jnp.where(lower, lane + jj, lane - jj)  # = lane XOR jj
    from_a = pltpu.roll(lane, jj, 1) == partner       # which rotation
    pv = jnp.where(from_a, pltpu.roll(vals, jj, 1), pltpu.roll(vals, c - jj, 1))
    pi = jnp.where(from_a, pltpu.roll(idxs, jj, 1), pltpu.roll(idxs, c - jj, 1))

    # ascending block == lower half of the pair -> keep the min (bits of
    # the lane index compared as ints: Mosaic has no i1 == i1)
    take_min = ((lane // kk) & 1) == ((lane // jj) & 1)
    a_less = (vals < pv) | ((vals == pv) & (idxs < pi))
    a_more = (vals > pv) | ((vals == pv) & (idxs > pi))
    # identical (val, idx) pairs (padding) keep neither: the partner copy
    # is the same entry.  No select over booleans: Mosaic refuses it.
    keep = (take_min & a_less) | (~take_min & a_more)
    return (
        jnp.where(keep, vals, pv),
        jnp.where(keep, idxs, pi),
    )


def _sort_net(vals, idxs, c: int):
    kk = 2
    while kk <= c:
        jj = kk // 2
        while jj >= 1:
            vals, idxs = _bitonic_stage(vals, idxs, kk, jj)
            jj //= 2
        kk *= 2
    return vals, idxs


def _topk_kernel(vals_ref, idxs_ref, ov_ref, oi_ref, *, c: int, k: int):
    vals, idxs = _sort_net(vals_ref[...], idxs_ref[...], c)
    ov_ref[...] = vals[:, :k]
    oi_ref[...] = idxs[:, :k]


def bitonic_topk_pallas(
    vals: jnp.ndarray,    # (B, C) float32, C power of two
    idxs: jnp.ndarray,    # (B, C) int32
    k: int,
    tb: int = DEFAULT_TB,
    interpret: bool = False,
):
    b, c = vals.shape
    assert c & (c - 1) == 0, f"C={c} must be a power of two"
    assert b % tb == 0

    return pl.pallas_call(
        functools.partial(_topk_kernel, c=c, k=k),
        grid=(b // tb,),
        in_specs=[
            pl.BlockSpec((tb, c), lambda i: (i, 0)),
            pl.BlockSpec((tb, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, k), lambda i: (i, 0)),
            pl.BlockSpec((tb, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        interpret=interpret,
    )(vals, idxs)
