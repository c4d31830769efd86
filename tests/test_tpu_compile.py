"""Compile the main path's kernels and the search program for a described
TPU v5e chip, at real widths.  Nothing runs: these catch what only the
TPU compiler refuses (tile shapes, unsupported lowerings, programs that do
not fit the device), which interpret-mode tests on the CPU cannot see.

The topology is described inside a module fixture — never while a module
is imported — so every test worker collects the same tests and only the
one running this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import baton, partition, pq
from repro.core.beam_search import Shard
from repro.kernels.pq_adc.ops import pq_adc, pq_adc_slots_tiled
from repro.kernels.pq_lut.ops import pq_lut
from repro.kernels.topk.ops import bitonic_topk

# the chip_smoke / batann-serve deployment: DEEP shape at 1M points
N, D, P, M, K, R = 1_000_000, 96, 8, 24, 256, 32
W = pq.code_width(M)            # bytes of a code == rows of a query's table
CODEBOOK = pq.codebook_shape(M, K, D)
N_QUERIES = 256
HBM_BYTES = 16 * 2**30
PARAMS = baton.BatonParams(L=64, W=8, k=10, pool=256, slots=32)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled


def test_pq_adc_slots_compiles(one_chip):
    s, c = 32, PARAMS.W * R
    out = _compile(one_chip,
                   lambda lut, codes: pq_adc_slots_tiled(lut, codes,
                                                         interpret=False),
                   ((s, W, K), jnp.float32), ((s, c, W), jnp.int32))
    assert "tpu_custom_call" in out.as_text()


@pytest.mark.parametrize("width,k", [
    (PARAMS.L + PARAMS.W * R, PARAMS.L),       # beam merge: L + W·R -> L
    (PARAMS.pool + PARAMS.W, PARAMS.pool),     # pool merge: pool + W -> pool
])
def test_bitonic_topk_compiles(one_chip, width, k):
    out = _compile(one_chip,
                   lambda v, i: bitonic_topk(v, i, k, interpret=False),
                   ((PARAMS.slots, width), jnp.float32),
                   ((PARAMS.slots, width), jnp.int32))
    assert "tpu_custom_call" in out.as_text()


def test_pq_adc_compiles(one_chip):
    out = _compile(one_chip, lambda lut, codes: pq_adc(lut, codes,
                                                       interpret=False),
                   ((PARAMS.slots, W, K), jnp.float32), ((4096, W), jnp.int32))
    assert "tpu_custom_call" in out.as_text()


def test_pq_lut_compiles(one_chip):
    out = _compile(one_chip, lambda q, c: pq_lut(q, c, interpret=False),
                   ((N_QUERIES, D), jnp.float32), ((M, K, D // M), jnp.float32))
    assert "tpu_custom_call" in out.as_text()


@pytest.mark.parametrize("adc_impl,merge_impl", [
    ("gather", "lexsort"), ("mxu_tiled", "bitonic"),
])
def test_run_simulated_fits_one_chip(one_chip, adc_impl, merge_impl):
    """The jitted super-step program over the 1M-point, 8-partition index,
    from shapes only: it compiles and fits in the chip's 16 GB."""
    cfg = baton.BatonParams(**{**vars(PARAMS), "adc_impl": adc_impl,
                               "merge_impl": merge_impl})
    npmax = partition.partition_capacity(N, P)
    per = N_QUERIES // P

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    codebook = sds(CODEBOOK, jnp.float32)
    devs = jax.eval_shape(
        jax.vmap(lambda q, i, s, sd: baton.init_device_state(
            q, i, s, sd, cfg, jnp.zeros(CODEBOOK))),
        sds((P, per, D), jnp.float32), sds((P, per), jnp.int32),
        sds((P, per, cfg.n_starts), jnp.int32),
        sds((P, per, cfg.n_starts), jnp.float32))
    devs = jax.tree.map(lambda x: sds(x.shape, x.dtype), devs)
    shard = Shard(vectors=sds((P, npmax, D), jnp.float32),
                  neighbors=sds((P, npmax, R), jnp.int32),
                  codes=sds((N, W), jnp.uint8),
                  node2part=sds((N,), jnp.int32),
                  node2local=sds((N,), jnp.int32))
    compiled = baton.run_supersteps.lower(
        devs, shard, codebook, cfg=cfg, P=P).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > P * npmax * D * 4   # the index is an argument
    assert total < HBM_BYTES, mem


def test_spmd_program_fits_four_chips(topo, one_chip):
    """``baton.spmd_program`` over a described 2x2 mesh, one partition per
    chip, for the 1M-point index in four partitions: it compiles, and each
    chip's share fits in 16 GB."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(topo.devices), ("part",))
    p = mesh.size
    npmax = partition.partition_capacity(N, p)
    per = N_QUERIES // p
    sds = jax.ShapeDtypeStruct
    devs = jax.eval_shape(
        jax.vmap(lambda q, i, s, sd: baton.init_device_state(
            q, i, s, sd, PARAMS, jnp.zeros(CODEBOOK))),
        sds((p, per, D), jnp.float32), sds((p, per), jnp.int32),
        sds((p, per, PARAMS.n_starts), jnp.int32),
        sds((p, per, PARAMS.n_starts), jnp.float32))
    shard = Shard(vectors=sds((p, npmax, D), jnp.float32),
                  neighbors=sds((p, npmax, R), jnp.int32),
                  codes=sds((N, W), jnp.uint8),
                  node2part=sds((N,), jnp.int32),
                  node2local=sds((N,), jnp.int32))
    compiled = baton.spmd_program(PARAMS, mesh).lower(
        devs, shard, sds(CODEBOOK, jnp.float32)).compile()
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert "all-to-all" in compiled.as_text()
    assert mem.argument_size_in_bytes > npmax * D * 4   # a partition per chip
    assert per_chip < HBM_BYTES, mem
