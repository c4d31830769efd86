"""PQ ADC as an MXU kernel (TPU adaptation of the paper's SIMD LUT-sum).

GPU/CPU ADC is a gather: dists[q,n] = Σ_m lut[q,m,codes[n,m]].  TPUs have no
fast per-lane gather, but they have a 128x128 systolic MXU — so we re-express
the per-subspace lookup as a one-hot matmul and *batch over the resident
query states* (the same states the baton engine keeps per device, §5):

    dists[q, n] = Σ_m onehot(codes[:, m]) @ lut[q, m, :]^T

Per subspace this is a (TN, K) @ (K, TQ) matmul with K=256 contraction —
MXU-aligned.  The one-hot expansion costs K× more FLOPs than the gather, but
they run on the otherwise-idle MXU at ~197 TFLOP/s while the VPU handles the
beam bookkeeping; the code tile is amortized across all TQ queries.

Grid: (N tiles, Q tiles).  Each block carries all M subspaces (codes laid
out (M, N), so each subspace's codes run along lanes) and the kernel
accumulates the M one-hot matmuls in order.  Every dot runs at
``Precision.HIGHEST``: the one-hot factor is exact, so the products are the
LUT entries themselves, not their bf16 roundings.

The slot-batched engine path (``step_disk_batched``) wants something
narrower: slot s's LUT scored against slot s's OWN candidate block only.
Routing that through the dense kernel (``ops.pq_adc_slots``) scores every
(slot, candidate) pair and keeps the block diagonal — an S× FLOP
overcommit.  ``pq_adc_slots_pallas`` instead puts the slot axis on the
grid: each grid step is one (slot, candidate-tile) block, and per subspace
a (1, K) @ (K, TC) one-hot product writes that subspace's partials, which
the caller reduces with the same ``pq.ordered_sum`` the gather uses.  One-hot
products are exact (a single 1.0 per row selects one LUT entry; adding
hard zeros never rounds), so the partials are bit-equal to gathered
values and the whole path is bit-identical to ``pq.adc_slots`` — unlike
the dense route, whose in-kernel accumulation order differs from the
gather's axis reduce by ulps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TN = 256   # code rows per tile
DEFAULT_TQ = 128   # queries per tile
DEFAULT_TC = 256   # candidates per slot tile (slot-tiled variant)


_HIGHEST = jax.lax.Precision.HIGHEST


def _onehot_t(codes_row, k: int):
    """(1, T) codes -> (K, T) one-hot, codes along lanes."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, codes_row.shape[-1]), 0)
    return (rows == codes_row).astype(jnp.float32)


def _adc_kernel(codes_ref, lut_ref, out_ref, *, m: int, k: int):
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(m):                                         # subspaces
        onehot = _onehot_t(codes_ref[pl.ds(j, 1), :], k)       # (K, TN)
        acc += jnp.dot(lut_ref[j], onehot, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)     # (TQ, TN)
    out_ref[...] = acc


def pq_adc_pallas(
    lut: jnp.ndarray,        # (Q, M, K) float32
    codes: jnp.ndarray,      # (N, M) int32 (uint8 at rest; widened by ops.py)
    tn: int = DEFAULT_TN,
    tq: int = DEFAULT_TQ,
    interpret: bool = False,
) -> jnp.ndarray:            # (Q, N) float32
    """Grid (N tiles, Q tiles); every block holds all M subspaces, which the
    kernel walks in order, so no block is narrower than the (8, 128) tile
    (a one-subspace block would be)."""
    q, m, k = lut.shape
    n = codes.shape[0]
    assert codes.shape[1] == m
    assert n % tn == 0 and q % tq == 0, (n, q, tn, tq)

    return pl.pallas_call(
        functools.partial(_adc_kernel, m=m, k=k),
        grid=(n // tn, q // tq),
        in_specs=[
            pl.BlockSpec((m, tn), lambda i, j: (0, i)),
            pl.BlockSpec((m, tq, k), lambda i, j: (0, j, 0)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        interpret=interpret,
    )(codes.T, jnp.transpose(lut, (1, 0, 2)))


def _adc_slots_kernel(codes_ref, lut_ref, out_ref, *, m: int, k: int):
    for j in range(m):                                         # subspaces
        onehot = _onehot_t(codes_ref[0, pl.ds(j, 1), :], k)    # (K, TC)
        out_ref[0, pl.ds(j, 1), :] = jnp.dot(
            lut_ref[0, pl.ds(j, 1), :], onehot, precision=_HIGHEST,
            preferred_element_type=jnp.float32)                # (1, TC)


def pq_adc_slots_pallas(
    luts: jnp.ndarray,       # (S, M, K) float32 — one LUT per slot
    codes: jnp.ndarray,      # (S, C, M) int32 — each slot's own candidates
    tc: int = DEFAULT_TC,
    interpret: bool = False,
) -> jnp.ndarray:            # (S, M, C) float32 per-subspace partials
    """Slot-tiled ADC: grid over (slot, candidate tile).

    Each grid step scores one slot's candidate tile against that slot's own
    LUT, one subspace after another — (S, C) work total, no cross-slot
    blocks.  Returns the per-subspace partials; the caller owns the
    M-reduction (``pq.ordered_sum(parts, axis=1)``) so the reduce order —
    and hence the bits — match ``pq.adc_slots``.
    """
    s, c, m = codes.shape
    k = luts.shape[-1]
    assert luts.shape == (s, m, k), (luts.shape, codes.shape)
    assert c % tc == 0, (c, tc)
    return pl.pallas_call(
        functools.partial(_adc_slots_kernel, m=m, k=k),
        grid=(s, c // tc),
        in_specs=[
            pl.BlockSpec((1, m, tc), lambda si, ci: (si, 0, ci)),
            pl.BlockSpec((1, m, k), lambda si, ci: (si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, m, tc), lambda si, ci: (si, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((s, m, c), jnp.float32),
        interpret=interpret,
    )(jnp.transpose(codes, (0, 2, 1)), luts)
