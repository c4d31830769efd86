"""Jitted public wrapper for the PQ ADC kernel (padding + CPU interpret)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.pq import ordered_sum
from repro.kernels.pq_adc.kernel import (
    DEFAULT_TC, DEFAULT_TN, DEFAULT_TQ, pq_adc_pallas, pq_adc_slots_pallas,
)
from repro.kernels.pq_adc.ref import pq_adc_ref


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("tn", "tq", "interpret"))
def pq_adc(
    lut: jnp.ndarray,
    codes: jnp.ndarray,
    tn: int | None = None,
    tq: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(Q, M, K) x (N, M) -> (Q, N).  Drop-in for repro.core.pq.adc."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    q, m, k = lut.shape
    n = codes.shape[0]
    tn = tn or min(DEFAULT_TN, max(8, n))
    tq = tq or min(DEFAULT_TQ, max(8, q))
    lut_p = _pad_to(lut, 0, tq)
    codes_p = _pad_to(codes.astype(jnp.int32), 0, tn)
    out = pq_adc_pallas(lut_p, codes_p, tn=tn, tq=tq, interpret=interpret)
    return out[:q, :n]


@partial(jax.jit, static_argnames=("tn", "tq", "interpret"))
def pq_adc_slots(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    tn: int | None = None,
    tq: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(S, M, K) x (S, C, M) -> (S, C): per-slot candidates on the MXU.

    The one-hot kernel scores every (query, code-row) pair, so we flatten all
    slots' candidates into one (S·C, M) code matrix, run the full (S, S·C)
    tile-padded matmul, and keep the block diagonal.  The S× extra FLOPs run
    on the otherwise-idle MXU (see kernel.py); the gather formulation for
    CPU/debug is ``repro.core.pq.adc_slots``.
    """
    s, c, m = codes.shape
    full = pq_adc(luts, codes.reshape(s * c, m), tn=tn, tq=tq,
                  interpret=interpret)                       # (S, S*C)
    idx = jnp.arange(c)[None, :] + jnp.arange(s)[:, None] * c
    return jnp.take_along_axis(full, idx, axis=1)


@partial(jax.jit, static_argnames=("tc", "interpret"))
def pq_adc_slots_tiled(
    luts: jnp.ndarray,
    codes: jnp.ndarray,
    tc: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(S, M, K) x (S, C, M) -> (S, C): slot-tiled, no cross-slot FLOPs.

    ``adc_impl="mxu_tiled"``: the grid walks (slot, candidate tile) and
    the kernel the subspaces, so the MXU scores only each slot's own
    candidate block — 2·S·C·K·M FLOPs against the dense route's
    2·S·(S·C)·K·M.  The kernel
    emits per-subspace partials (exact, see kernel.py) and this wrapper
    reduces them with the gather's own ``pq.ordered_sum`` — bit-identical to
    ``repro.core.pq.adc_slots`` (tested), which is what lets the exec tier
    run it under the engine's bit-parity guarantee.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    s, c, m = codes.shape
    tc = tc or min(DEFAULT_TC, max(8, c))
    codes_p = _pad_to(codes.astype(jnp.int32), 1, tc)
    parts = pq_adc_slots_pallas(luts, codes_p, tc=tc, interpret=interpret)
    return ordered_sum(parts[:, :, :c], axis=1)


__all__ = ["pq_adc", "pq_adc_ref", "pq_adc_slots", "pq_adc_slots_tiled"]
