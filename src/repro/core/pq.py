"""Product quantization (Jégou et al.) — codebook training, encoding, ADC.

The paper keeps a 32-byte PQ representation of every vector in memory on each
node and performs almost all distance comparisons with it (§2, §5 "Memory
footprint").  This module is the pure-JAX substrate; the MXU-optimized ADC
lives in ``repro.kernels.pq_adc`` and is validated against this code.

Codes are residual (IVFADC, Jégou et al. 2011 §4): a coarse k-means centroid
c is subtracted from each vector x and the residual r = x - c is product
quantized, so the M subspace quantizers spend their bits on the spread
around c rather than on where c lies.  A vector's code has M + 2 bytes: the
M residual codes, the coarse centroid's id, and the id of the level nearest
to the vector's cross term 2<c, r̂>.  With them

    ||q - c - r̂||² = ||q - c||² + Σ_m (||r̂_m||² - 2<q_m, r̂_m>) + 2<c, r̂>,

one table entry per code byte, so ADC stays a sum of M + 2 lookups and every
consumer of a (M + 2, K) table works unchanged.

Conventions: squared-L2 everywhere (paper §2).  codes are uint8 with K<=256.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def ordered_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Sum over ``axis`` in one fixed pairwise order, as elementwise adds.

    A ``jnp.sum`` leaves the reduction order to the compiler, and on a TPU
    the order follows the program's layout: the same distances summed in a
    vmapped program and in a ``shard_map`` or per-worker program differ in
    their last bits.  Elementwise adds round the same in every program, so
    every search distance (exact and ADC) is reduced here.
    """
    x = jnp.moveaxis(x, axis, -1)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        s = x[..., :h] + x[..., h:2 * h]
        x = jnp.concatenate([s, x[..., 2 * h:]], -1) if x.shape[-1] % 2 else s
    return x[..., 0]


@dataclasses.dataclass
class PQCodebook:
    """One (M + 2, K, d) float32 array, so that it travels through jit
    arguments, shardings and index files as a single leaf:

    * rows ``[0, M)``: subspace m's residual centroids, in columns
      ``[0, dsub)`` (the rest is zero);
    * row ``M``: the coarse centroids (K, d);
    * row ``M + 1``: the cross-term levels, in column 0.

    ``centroids.shape[:2]`` is the shape of a query's table (see
    :func:`build_lut`) and ``centroids.shape[0]`` the bytes of a code."""

    centroids: jnp.ndarray  # (M + 2, K, d) float32

    @property
    def m(self) -> int:
        return self.centroids.shape[0] - 2

    @property
    def k(self) -> int:
        return self.centroids.shape[1]

    @property
    def dim(self) -> int:
        return self.centroids.shape[2]

    @property
    def dsub(self) -> int:
        return self.dim // self.m

    def tree_flatten(self):
        return (self.centroids,), None


def code_width(m: int) -> int:
    """Bytes of one code (and rows of one table) for M residual subspaces."""
    return m + 2


def codebook_shape(m: int, k: int, dim: int) -> tuple[int, int, int]:
    """Shape of the codebook array for M subspaces of K centroids in dim."""
    return (code_width(m), k, dim)


def _parts(cent: jnp.ndarray):
    """(residual (M, K, dsub), coarse (K, d), levels (K,)) of a codebook."""
    m = cent.shape[0] - 2
    return cent[:m, :, :cent.shape[2] // m], cent[m], cent[m + 1, :, 0]


def _split(x: jnp.ndarray, m: int) -> jnp.ndarray:
    """(N, d) -> (N, M, dsub)."""
    n, d = x.shape
    assert d % m == 0, f"dim {d} not divisible by M={m}"
    return x.reshape(n, m, d // m)


def _sub_dists(xs: jnp.ndarray, cent: jnp.ndarray) -> jnp.ndarray:
    """(N, M, dsub) x (M, K, dsub) -> (N, M, K) squared L2 per subspace.

    Summed differences over the dsub coordinates, not the x² - 2x·c + c²
    expansion: no matmul, so no backend runs it at reduced precision, and no
    cancellation.  On a TPU v5e the matmul form of a 131072-row encode
    agreed with a float64 encode on 98.7% of codes at DEFAULT precision and
    on only 25.3% at HIGHEST; this form agreed on all of them
    (``chip_smoke.py`` prints the three shares).
    """
    out = 0.0
    for j in range(xs.shape[-1]):
        out = out + (xs[:, :, None, j] - cent[None, :, :, j]) ** 2
    return out


@partial(jax.jit, static_argnums=(1, 2, 3))
def _kmeans_all_subspaces(x, m, k, iters):
    """Vectorized k-means over all M subspaces at once.

    x: (N, d).  Returns centroids (M, K, dsub).
    """
    xs = _split(x, m)                       # (N, M, dsub)
    n = xs.shape[0]
    # k-means++-lite init: deterministic strided sample (data is pre-shuffled
    # by the synthetic generator; real pipelines shuffle on ingest).
    idx = (jnp.arange(k) * max(n // k, 1)) % n
    cent = xs[idx]                          # (K, M, dsub)
    cent = jnp.transpose(cent, (1, 0, 2))   # (M, K, dsub)

    def per_subspace_sum(vals, assign):     # (N, M, c), (N, M) -> (M, K, c)
        return jax.vmap(
            lambda v, a: jax.ops.segment_sum(v, a, num_segments=k),
            in_axes=(1, 1))(vals, assign)

    def step(cent, _):
        assign = jnp.argmin(_sub_dists(xs, cent), axis=-1)      # (N, M)
        sums = per_subspace_sum(xs, assign)                     # (M, K, dsub)
        cnts = per_subspace_sum(jnp.ones(xs.shape[:2] + (1,), x.dtype),
                                assign)                         # (M, K, 1)
        new = jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1), cent)
        return new, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    return cent


def _nearest(x: jnp.ndarray, cent: jnp.ndarray) -> jnp.ndarray:
    """(N, d) x (K, d) -> (N,) id of the nearest row of ``cent``."""
    return jnp.argmin(_sub_dists(x[:, None, :], cent[None])[:, 0], axis=-1)


def _cross_term(coarse_rows: jnp.ndarray, res: jnp.ndarray,
                rcodes: jnp.ndarray) -> jnp.ndarray:
    """2<c, r̂> per row: (N, d) coarse centroids, (M, K, dsub) residual
    centroids, (N, M) residual codes -> (N,)."""
    m = res.shape[0]
    r_hat = res[jnp.arange(m)[None], rcodes]                  # (N, M, dsub)
    return 2.0 * ordered_sum(coarse_rows * r_hat.reshape(coarse_rows.shape))


def train(
    x: np.ndarray, m: int = 32, k: int = 256, iters: int = 8, sample: int = 65536,
    seed: int = 0,
) -> PQCodebook:
    """Coarse centroids, then the residual subspace centroids, then the
    cross-term levels, each by k-means with K centroids on one sample."""
    x = np.asarray(x, dtype=np.float32)
    if x.shape[0] > sample:
        rng = np.random.default_rng(seed)
        x = x[rng.choice(x.shape[0], sample, replace=False)]
    return PQCodebook(centroids=_train(jnp.asarray(x), m, k, iters))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _train(x, m, k, iters):
    d = x.shape[1]
    coarse = _kmeans_all_subspaces(x, 1, k, iters)[0]         # (K, d)
    cid = _nearest(x, coarse)
    res = _kmeans_all_subspaces(x - coarse[cid], m, k, iters)  # (M, K, dsub)
    rcodes = jnp.argmin(_sub_dists(_split(x - coarse[cid], m), res), -1)
    cross = _cross_term(coarse[cid], res, rcodes)
    levels = _kmeans_all_subspaces(cross[:, None], 1, k, iters)[0, :, 0]
    cent = jnp.zeros(codebook_shape(m, k, d), jnp.float32)
    cent = cent.at[:m, :, :d // m].set(res)
    cent = cent.at[m].set(coarse)
    return cent.at[m + 1, :, 0].set(levels)


@jax.jit
def _encode(x, cent):
    res, coarse, levels = _parts(cent)
    m = res.shape[0]
    cid = _nearest(x, coarse)
    rcodes = jnp.argmin(_sub_dists(_split(x - coarse[cid], m), res), -1)
    cross = _cross_term(coarse[cid], res, rcodes)
    lid = jnp.argmin(jnp.abs(cross[:, None] - levels[None]), axis=-1)
    return jnp.concatenate(
        [rcodes, cid[:, None], lid[:, None]], axis=1).astype(jnp.uint8)


def encode(cb: PQCodebook, x: np.ndarray, chunk: int = 131072) -> np.ndarray:
    """(N, d) -> (N, M + 2) uint8 codes, chunked to bound memory."""
    x = np.asarray(x, dtype=np.float32)
    cent = jnp.asarray(cb.centroids)
    out = np.empty((x.shape[0], cent.shape[0]), dtype=np.uint8)
    for s in range(0, x.shape[0], chunk):
        out[s : s + chunk] = np.asarray(_encode(jnp.asarray(x[s : s + chunk]),
                                                cent))
    return out


def build_lut(cb_centroids: jnp.ndarray, queries: jnp.ndarray) -> jnp.ndarray:
    """Query lookup tables (the 'codebook' of §2).

    cb_centroids: (M + 2, K, d) (see :class:`PQCodebook`); queries: (Q, d)
    -> (Q, M + 2, K) float32 where, for code bytes (k_0..k_{M-1}, j, l),
    ``sum_m lut[q, m, k_m] + lut[q, M, j] + lut[q, M + 1, l]`` is the
    squared distance from the query to the coded vector:
    ``lut[q, m, k] = ||r̂_mk||² - 2<q_m, r̂_mk>``, ``lut[q, M, j] =
    ||q - c_j||²`` and ``lut[q, M + 1, l]`` the l-th cross-term level.
    Every entry is a sum of elementwise products (no matmul).
    """
    res, coarse, levels = _parts(cb_centroids)
    qs = _split(queries, res.shape[0])                        # (Q, M, dsub)
    rows = 0.0
    for j in range(res.shape[-1]):
        r = res[None, :, :, j]
        rows = rows + r * (r - 2.0 * qs[:, :, None, j])       # (Q, M, K)
    near = _sub_dists(queries[:, None, :], coarse[None])      # (Q, 1, K)
    lvl = jnp.broadcast_to(levels, (queries.shape[0], 1, levels.shape[0]))
    return jnp.concatenate([rows, near, lvl], axis=1)


def quantize_lut_i8(lut: jnp.ndarray):
    """Per-subspace symmetric int8 quantization of a (..., M, K) LUT.

    The §8 "Reducing Message Size" wire variant: each subspace row is scaled
    by its own ``max(|row|)/127`` so the quantization error is bounded by
    ``scale/2`` per entry — ~4× fewer wire bytes than f32 at a distance
    error of at most ``sum_m max_m(lut)/254`` (tested).  Returns
    ``(codes (..., M, K) int8, scales (..., M) float32)``.
    """
    scale = jnp.max(jnp.abs(lut), axis=-1) / 127.0
    scale = jnp.maximum(scale, jnp.float32(1e-12))
    q = jnp.clip(jnp.round(lut / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_lut_i8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_lut_i8` (receiver side of the i8 wire)."""
    return q.astype(jnp.float32) * scale[..., None]


def adc(lut: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Asymmetric distance computation.

    lut: (Q, M, K); codes: (N, M) uint8 -> (Q, N) approximate sq-L2.
    Reference (gather) formulation; the MXU one-hot formulation is in
    kernels/pq_adc and must match this to ~1e-4.
    """
    c = codes.astype(jnp.int32)  # (N, M)
    # take_along_axis over K: (Q, M, N)
    g = jnp.take_along_axis(
        lut, c.T[None, :, :], axis=2
    )  # (Q, M, N)
    return ordered_sum(g, axis=1)


def adc_slots(luts: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Slot-batched ADC: every resident state scores *its own* candidates.

    luts: (S, M, K); codes: (S, C, M) uint8 -> (S, C) approximate sq-L2.
    One fused gather+reduce for all S slots — bit-identical to vmapping
    ``adc`` per slot (verified by tests), but a single XLA op instead of S.
    The MXU one-hot route for the same contract is
    ``repro.kernels.pq_adc.ops.pq_adc_slots``.
    """
    c = codes.astype(jnp.int32)                       # (S, C, M)
    g = jnp.take_along_axis(luts, c.transpose(0, 2, 1), axis=2)  # (S, M, C)
    return ordered_sum(g, axis=1)


def reconstruct(cb: PQCodebook, codes: jnp.ndarray) -> jnp.ndarray:
    """Decode codes back to vectors c + r̂ (for diagnostics)."""
    res, coarse, _ = _parts(jnp.asarray(cb.centroids))
    c = codes.astype(jnp.int32)
    m = res.shape[0]
    r_hat = res[jnp.arange(m)[None], c[:, :m]]               # (N, M, dsub)
    return coarse[c[:, m]] + r_hat.reshape(codes.shape[0], -1)


def level_error(cb: PQCodebook, codes: jnp.ndarray) -> jnp.ndarray:
    """(N,) level minus exact cross term of each code: ADC of a query and a
    code is ``||q - reconstruct(code)||² + level_error(code)``."""
    res, coarse, levels = _parts(jnp.asarray(cb.centroids))
    c = codes.astype(jnp.int32)
    m = res.shape[0]
    return levels[c[:, m + 1]] - _cross_term(coarse[c[:, m]], res, c[:, :m])
