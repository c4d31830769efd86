import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell
on the production mesh; record memory/cost/collective analysis for §Roofline.

The XLA_FLAGS override above MUST run before any other import (jax locks the
device count at first backend init) and lives ONLY here — smoke tests and
benchmarks see the real single CPU device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_IDS, get_config
from repro.launch import hlo_stats, shardings as sh
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.config import SHAPES, applicable_shapes
from repro.training import optimizer as opt_mod
from repro.training.train_loop import TrainConfig, make_train_step

ARTIFACTS = os.path.join(os.path.dirname(__file__), "../../../artifacts/dryrun")

# params >= this use bf16 params + bf16 adam moments for train cells
_BF16_TRAIN_THRESHOLD = 100e9
# per-arch grad-accumulation microbatches for train_4k (activation fit)
_MICROBATCHES = {
    "qwen3-14b": 4, "gemma3-27b": 8, "kimi-k2-1t-a32b": 8,
    "grok-1-314b": 8, "musicgen-large": 2, "internvl2-2b": 2,
}


def _train_cell(cfg, shape, mesh, multi_pod, unroll=True, variant="baseline"):
    big = cfg.param_count() >= _BF16_TRAIN_THRESHOLD
    zero2 = variant == "zero2"
    param_dtype = jnp.bfloat16 if (big or zero2) else jnp.float32
    moment_dtype = "bfloat16" if big else "float32"
    cell = sh.make_cell_sharding(cfg, shape, mesh, multi_pod)
    if zero2:
        cell.param_specs = sh.make_param_specs(cfg, mesh, multi_pod,
                                               zero2=True)
    ctx = T.RunCtx(
        ax=cell.rules, mesh=mesh, batch_axes=cell.batch_axes,
        param_dtype=param_dtype, compute_dtype=jnp.bfloat16, remat=True,
        attn_chunk=4096, scan_unroll=unroll,
    )
    tcfg = TrainConfig(
        batch=shape.global_batch, seq_len=shape.seq_len,
        microbatches=_MICROBATCHES.get(cfg.name, 1),
        opt=opt_mod.AdamWConfig(moment_dtype=moment_dtype),
    )
    params = T.abstract_params(cfg, param_dtype)
    opt_state = jax.eval_shape(lambda p: opt_mod.init(tcfg.opt, p), params)
    batch, batch_shardings = sh.input_specs(cfg, shape, mesh, multi_pod)
    labels_like = batch

    pspecs = sh.named(mesh, cell.param_specs)
    mspecs = pspecs
    if zero2:
        # moments keep the data-sharded (ZeRO) layout
        mspecs = sh.named(
            mesh, sh.make_param_specs(cfg, mesh, multi_pod, zero2=False)
        )
    ospecs = opt_mod.OptState(
        step=sh.named(mesh, jax.sharding.PartitionSpec()),
        m=mspecs, v=mspecs,
    )
    step_fn = make_train_step(cfg, tcfg, ctx)
    jitted = jax.jit(
        step_fn,
        in_shardings=(pspecs, ospecs, batch_shardings),
        out_shardings=(pspecs, ospecs, None),
        donate_argnums=(0, 1),
    )
    return jitted, (params, opt_state, labels_like)


def _prefill_cell(cfg, shape, mesh, multi_pod, unroll=True):
    cell = sh.make_cell_sharding(cfg, shape, mesh, multi_pod)
    ctx = T.RunCtx(
        ax=cell.rules, mesh=mesh, batch_axes=cell.batch_axes,
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
        attn_chunk=2048, scan_unroll=unroll,
    )
    params = T.abstract_params(cfg, jnp.bfloat16)
    batch, batch_shardings = sh.input_specs(cfg, shape, mesh, multi_pod)
    pspecs = sh.named(mesh, cell.param_specs)

    def fn(params, batch):
        return T.prefill(cfg, params, batch, s_max=shape.seq_len, ctx=ctx)

    jitted = jax.jit(fn, in_shardings=(pspecs, batch_shardings))
    return jitted, (params, batch)


def _decode_cell(cfg, shape, mesh, multi_pod, unroll=True,
                 variant="baseline"):
    cell = sh.make_cell_sharding(cfg, shape, mesh, multi_pod)
    ctx = T.RunCtx(
        ax=cell.rules, mesh=mesh, batch_axes=cell.batch_axes,
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
        scan_unroll=unroll, grouped_gqa=(variant == "grouped"),
    )
    params = T.abstract_params(cfg, jnp.bfloat16)
    batch, batch_shardings = sh.input_specs(cfg, shape, mesh, multi_pod)
    caches, cache_shardings = sh.cache_specs(cfg, shape, mesh, multi_pod)
    pspecs = sh.named(mesh, cell.param_specs)
    t_spec = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(params, tokens, t, caches):
        tok = tokens if cfg.frontend else tokens["tokens"]
        return T.decode_step(cfg, params, tok, t, caches, ctx)

    jitted = jax.jit(
        fn,
        in_shardings=(pspecs, batch_shardings, None, cache_shardings),
        out_shardings=(None, cache_shardings),
        donate_argnums=(3,),
    )
    return jitted, (params, batch, t_spec, caches)


def _batann_cell(mesh, multi_pod, sector: bool = False):
    """The paper's own serve workload: the baton SPMD search over the full
    flattened device set (each device = one partition/server)."""
    from repro.configs.batann_serve import CONFIG as BC
    from repro.core import baton, pq
    from repro.core.beam_search import Shard
    from repro.launch.mesh import all_axes

    axes = all_axes(multi_pod)
    n_dev = mesh.size
    n_local = BC.n_total // n_dev
    cfg = baton.BatonParams(
        L=BC.L, W=BC.W, k=BC.k, pool=BC.pool, slots=BC.slots,
        pair_cap=BC.pair_cap, result_cap=BC.result_cap, n_starts=BC.n_starts,
        max_supersteps=64,
    )
    q_per_dev = cfg.slots  # one refill's worth of queued queries per device
    d = BC.dim
    w = pq.code_width(BC.pq_m)     # code bytes == table rows

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    dev = baton.DeviceState(
        states=jax.eval_shape(
            lambda: baton._batched_empty_states(
                d, cfg, (n_dev, cfg.slots), m=w, k_pq=BC.pq_k
            )
        ),
        queue_emb=sds((n_dev, q_per_dev, d), jnp.float32),
        queue_qid=sds((n_dev, q_per_dev), jnp.int32),
        queue_starts=sds((n_dev, q_per_dev, cfg.n_starts), jnp.int32),
        queue_start_d=sds((n_dev, q_per_dev, cfg.n_starts), jnp.float32),
        queue_lut=sds((n_dev, q_per_dev, w, BC.pq_k), jnp.float32),
        queue_head=sds((n_dev,), jnp.int32),
        out_ids=sds((n_dev, q_per_dev, cfg.k), jnp.int32),
        out_dists=sds((n_dev, q_per_dev, cfg.k), jnp.float32),
        out_stats=sds((n_dev, q_per_dev, baton.N_STATS), jnp.int32),
        out_trace=sds((n_dev, q_per_dev, cfg.trace_cap, baton.N_TRACE),
                      jnp.int32),
        delivered=sds((n_dev, q_per_dev), bool),
        local_steps=sds((n_dev,), jnp.int32),
    )
    if sector:
        # AiSAQ sector layout (§Perf iteration): neighbor codes in-sector,
        # uint8 native vectors, uint8 routing map, placeholder code array
        shard = Shard(
            vectors=sds((n_dev, n_local, d), jnp.uint8),
            neighbors=sds((n_dev, n_local, BC.graph_r), jnp.int32),
            codes=sds((1, w), jnp.uint8),
            node2part=sds((BC.n_total,), jnp.uint8),
            node2local=sds((BC.n_total,), jnp.int32),
            nbr_codes=sds((n_dev, n_local, BC.graph_r, w), jnp.uint8),
        )
    else:
        shard = Shard(
            vectors=sds((n_dev, n_local, d), jnp.float32),
            neighbors=sds((n_dev, n_local, BC.graph_r), jnp.int32),
            codes=sds((BC.n_total, w), jnp.uint8),
            node2part=sds((BC.n_total,), jnp.int32),
            node2local=sds((BC.n_total,), jnp.int32),
        )
    codebook = sds(pq.codebook_shape(BC.pq_m, BC.pq_k, BC.dim), jnp.float32)

    jitted = baton.spmd_program(cfg, mesh, axes, sector_codes=sector)
    return jitted, (dev, shard, codebook)


def _build(arch, shape_name, mesh, multi_pod, unroll, variant="baseline"):
    if arch == "batann-serve":
        return _batann_cell(mesh, multi_pod,
                            sector=(shape_name == "serve-sector"))
    cfg = get_config(arch)
    if variant == "headpad48":
        # §Perf iteration: pad attention heads to the next TP multiple so
        # heads shard over "model" (kills the q_seq<->TP activation
        # resharding); +20% attention params/FLOPs, honest A/B label
        import dataclasses as _dc

        cfg = _dc.replace(cfg, n_heads=48)
    shape = SHAPES[shape_name]
    with mesh:
        if shape.kind == "train":
            return _train_cell(cfg, shape, mesh, multi_pod, unroll, variant)
        if shape.kind == "prefill":
            return _prefill_cell(cfg, shape, mesh, multi_pod, unroll)
        return _decode_cell(cfg, shape, mesh, multi_pod, unroll, variant)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             verbose: bool = True, skip_unroll: bool = False,
             variant: str = "baseline") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = None if arch == "batann-serve" else get_config(arch)
    if cfg is not None and shape_name not in applicable_shapes(cfg):
        return {"arch": arch, "shape": shape_name, "skipped": True}

    # pass 1: scan-over-layers — realistic loop buffer reuse => MEMORY truth
    t0 = time.time()
    jitted, args = _build(arch, shape_name, mesh, multi_pod, unroll=False,
                          variant=variant)
    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()

    # pass 2: unrolled layers — XLA cost analysis counts loop bodies once,
    # so FLOPs/collective truth needs the unrolled module (EXPERIMENTS.md)
    unrolled_ok = True
    if arch != "batann-serve" and not skip_unroll:
        try:
            t1 = time.time()
            jitted_u, args_u = _build(arch, shape_name, mesh, multi_pod,
                                      unroll=True, variant=variant)
            with mesh:
                compiled_u = jitted_u.lower(*args_u).compile()
            t_compile += time.time() - t1
            cost = compiled_u.cost_analysis()
            hlo = compiled_u.as_text()
        except Exception:  # noqa: BLE001
            unrolled_ok = False
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
    else:
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll = hlo_stats.collective_stats(hlo)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", -1)) if cost else -1,
        "bytes_accessed": float(cost.get("bytes accessed", -1)) if cost else -1,
        "collectives": coll,
        "hlo_instructions": hlo.count("\n"),
        "microbatches": _MICROBATCHES.get(arch, 1)
        if shape_name == "train_4k" else 1,
        "flops_from_unrolled": (unrolled_ok and not skip_unroll)
        if arch != "batann-serve" else False,
    }
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                rec[k] = int(v)
    if cfg is not None:
        rec["params"] = cfg.param_count()
        rec["active_params"] = cfg.active_param_count()

    rec["variant"] = variant
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}_{shape_name}_{rec['mesh'].replace('x', '-')}"
    if variant != "baseline":
        tag += f"_{variant}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        print(f"[dryrun] {tag}: OK flops={rec['flops']:.3e} "
              f"coll={coll['total']['bytes']/1e6:.1f}MB/dev "
              f"compile={rec['compile_s']:.0f}s")
        print("  memory_analysis:", {k: rec[k] for k in rec
                                     if k.endswith("_in_bytes")})
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(ARTIFACTS))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--skip-unroll", action="store_true",
                    help="compile-proof only (multi-pod sweep)")
    ap.add_argument("--variant", default="baseline",
                    help="train-cell variant: baseline | zero2")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh
    ]
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            if arch == "batann-serve":
                cells.append((arch, "serve"))
                cells.append((arch, "serve-sector"))
                continue
            for s in applicable_shapes(get_config(arch)):
                cells.append((arch, s))
    else:
        assert args.arch, "--arch or --all required"
        shapes = [args.shape] if args.shape else (
            ["serve"] if args.arch == "batann-serve"
            else applicable_shapes(get_config(args.arch))
        )
        cells = [(args.arch, s) for s in shapes]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'2-16-16' if mp else '16-16'}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] {tag}: cached")
                continue
            try:
                run_cell(arch, shape, mp, args.out,
                         skip_unroll=args.skip_unroll, variant=args.variant)
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failures.append((tag, str(e)[:200]))
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for t, e in failures:
            print("  ", t, e)
        raise SystemExit(1)
    print("[dryrun] all cells OK")


if __name__ == "__main__":
    main()
