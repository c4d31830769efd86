"""PQ lookup-table (codebook) build kernel.

lut[q, m, c] = ||query_sub[q, m] - centroid[m, c]||², expanded to
q2 - 2·q·c + c2.  Grid: (Q tiles, M subspaces).  Each step takes the whole
(TQ, d) query block, so no block is narrower than the (8, 128) tile: the
cross term is (TQ, d) @ (d, K) against subspace m's centroids placed in
rows m·dsub..(m+1)·dsub of a zero (d, K) matrix, and q2 sums the squares
of those lanes only.  The output is laid out (Q, M·K), one lane-aligned
(TQ, K) block per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TQ = 128


def _lut_kernel(q_ref, e_ref, c2_ref, out_ref, *, dsub: int):
    m = pl.program_id(1)
    q = q_ref[...]                                   # (TQ, d)
    lane = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
    mine = (lane >= m * dsub) & (lane < (m + 1) * dsub)
    q2 = jnp.sum(jnp.where(mine, q * q, 0.0), axis=-1, keepdims=True)
    cross = jnp.dot(q, e_ref[0], precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)            # (TQ, K)
    out_ref[...] = q2 - 2.0 * cross + c2_ref[0]


def pq_lut_pallas(
    queries: jnp.ndarray,     # (Q, d) float32
    centroids: jnp.ndarray,   # (M, K, dsub) float32
    tq: int = DEFAULT_TQ,
    interpret: bool = False,
) -> jnp.ndarray:             # (Q, M, K)
    q, d = queries.shape
    m, k, dsub = centroids.shape
    assert d == m * dsub and q % tq == 0
    # (M, d, K): subspace m's centroids transposed into its own rows
    emb = jnp.zeros((m, m, dsub, k), jnp.float32)
    emb = emb.at[jnp.arange(m), jnp.arange(m)].set(
        jnp.transpose(centroids, (0, 2, 1))).reshape(m, d, k)
    c2 = jnp.sum(centroids * centroids, -1)[:, None, :]           # (M, 1, K)

    out = pl.pallas_call(
        functools.partial(_lut_kernel, dsub=dsub),
        grid=(q // tq, m),
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, mm: (i, 0)),
            pl.BlockSpec((1, d, k), lambda i, mm: (mm, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda i, mm: (mm, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tq, k), lambda i, mm: (i, mm)),
        out_shape=jax.ShapeDtypeStruct((q, m * k), jnp.float32),
        interpret=interpret,
    )(queries, emb, c2)
    return out.reshape(q, m, k)
