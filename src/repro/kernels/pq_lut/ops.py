"""Jitted public wrapper for the PQ LUT kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.pq_lut.kernel import DEFAULT_TQ, pq_lut_pallas
from repro.kernels.pq_lut.ref import pq_lut_ref


@partial(jax.jit, static_argnames=("tq", "interpret"))
def pq_lut(
    queries: jnp.ndarray,
    centroids: jnp.ndarray,
    tq: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(Q, d) x (M, K, dsub) -> (Q, M, K) plain subspace tables
    ||q_m - c_mk||².  ``pq.build_lut``'s residual rows are these tables of
    the residual centroids less ||q_m||²."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    q = queries.shape[0]
    tq = tq or min(DEFAULT_TQ, max(8, q))
    pad = (-q) % tq
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad), (0, 0)))
    out = pq_lut_pallas(qp, centroids.astype(jnp.float32), tq=tq,
                        interpret=interpret)
    return out[:q]


__all__ = ["pq_lut", "pq_lut_ref"]
