"""Bring-up smoke: build and serve a baton index on one TPU chip.

    python chip_smoke.py               # one chip: the served path
    python chip_smoke.py --four-chips  # four chips: SPMD vs one-device search

The default run drives the main path the serve launcher drives —
``get_serve_config("batann-serve")`` -> ``Deployment.from_config`` ->
``Deployment.run`` / ``Deployment.run_exec`` — over a DEEP-shaped dataset
(96-d float32, 256 queries) in eight partitions on one device, built from
the seed on the device: 1M points, the scale of the standard ANN
benchmarks.
It prints the seconds of each build phase, recall@10 against the exact
reference at L = 64, 128 and 256, the mean hops and inter-partition hops and
the device's peak memory, and checks:

* the device's exact kNN (ground truth and graph build) against float64
  numpy, on 64 queries and on 64 database rows;
* the index's PQ codes (residual: coarse id, residual subspace codes,
  cross-term level) against a float64 host encode (it also prints how
  often the matmul form of the residual encode, x² - 2x·c + c², agrees at
  HIGHEST and at DEFAULT precision: the reason ``pq`` encodes without
  matmuls);
* recall@10 >= 0.90 at L = 256, every query delivered, inter_hops > 0;
* the Pallas kernels (``adc_impl="mxu_tiled"``, ``merge_impl="bitonic"``)
  return the same ids as the default path;
* the executable tier (thread mode) answers 32 queries exactly as
  ``Deployment.search`` does: the same ids and bit-identical distances.

``--four-chips`` builds the same kind of data at 100k points in four
partitions (the build runs on one chip while all four wait, ~6 minutes at
1M; the bit-for-bit comparison does not need that scale), runs
``baton.run_spmd`` with one partition per chip, and checks that ids,
distances and counters equal ``run_simulated`` on one chip.

There is no CPU path: without a TPU it exits nonzero.  The last line of a
passing run is ``{"ok": true, "device": {...}}``; any failed check exits
nonzero without it.  All times are cold bring-up times (compilation
included), not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

N_POINTS = 1_000_000
N_QUERIES = 256
N_PARTS = 8
L_SWEEP = (64, 128, 256)
RECALL_FLOOR = 0.90          # at the largest L: catches a broken search
N_EXEC = 32                  # queries answered by the executable tier
N_REF_CHECK = 64             # queries and rows whose kNN is re-done in numpy
KNN_BUILD_ROWS = 2048        # rows per kNN call, as the graph build tiles
PQ_CHECK_ROWS = 16384        # rows whose PQ codes are re-done in numpy
PQ_ENCODE_ROWS = 131072      # rows per device encode call (pq.encode chunk)
CODE_AGREEMENT = 0.99        # near-ties aside, float32 codes match float64
EXEC_WORKERS = 4
FOUR_CHIP_POINTS = 100_000
FOUR_CHIP_PARTS = 4


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _config(n: int, n_queries: int, p: int):
    from repro.configs.registry import get_serve_config

    return get_serve_config("batann-serve").with_updates(
        data={"name": "deep", "n": n, "n_queries": n_queries},
        index={"p": p}, sim={"send_rate": 0.0})


def _peak_bytes(device) -> "int | None":
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _knn_vs_float64(vecs, queries, got) -> "tuple[int, float]":
    """Rows whose kNN ids equal a float64 numpy kNN's, and the largest gap
    between the true distances of the returned and of the true neighbours,
    relative to the k-th true distance (0 where only near-ties differ)."""
    import numpy as np

    v64 = vecs.astype(np.float64)
    n2 = (v64 * v64).sum(1)
    k = got.shape[1]
    same, gap = 0, 0.0
    for q, ids in zip(queries.astype(np.float64), got):
        d = n2 - 2.0 * (v64 @ q) + q @ q
        true = np.argpartition(d, k - 1)[:k]
        same += set(ids.tolist()) == set(true.tolist())
        worst = np.abs(np.sort(d[ids]) - np.sort(d[true])).max()
        gap = max(gap, worst / max(d[true].max(), 1e-30))
    return same, gap


def _pq_agreement(vecs, codes, codebook) -> dict:
    """Share of sampled code bytes equal to a float64 host encode: the
    index's own codes (coarse id, residual codes, cross-term level), and
    the residual codes by the matmul form of the subspace distance at
    HIGHEST and DEFAULT precision, run on the device at the index's encode
    chunk."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    w, k, d = codebook.shape
    m = w - 2
    dsub = d // m
    res = codebook[:m, :, :dsub].astype(np.float64)
    coarse = codebook[m].astype(np.float64)
    levels = codebook[m + 1, :, 0].astype(np.float64)
    rows = min(PQ_CHECK_ROWS, len(vecs))
    x = vecs[:rows].astype(np.float64)
    cid = np.concatenate([
        ((x[i:i + 1024, None] - coarse[None]) ** 2).sum(-1).argmin(-1)
        for i in range(0, rows, 1024)])
    r = (x - coarse[cid]).reshape(rows, m, 1, dsub)
    rcodes = ((r - res[None]) ** 2).sum(-1).argmin(-1)           # (rows, M)
    cross = 2.0 * (coarse[cid].reshape(rows, m, dsub)
                   * res[np.arange(m)[None], rcodes]).sum((1, 2))
    lid = np.abs(cross[:, None] - levels[None]).argmin(-1)
    host = np.concatenate([rcodes, cid[:, None], lid[:, None]], 1)
    out = {"index": float((codes[:rows] == host).mean())}

    def matmul_form(x, cent, precision):
        xs = x.reshape(x.shape[0], m, dsub)
        d = (jnp.sum(xs * xs, -1)[:, :, None]
             - 2.0 * jnp.einsum("nmd,mkd->nmk", xs, cent, precision=precision)
             + jnp.sum(cent * cent, -1)[None])
        return jnp.argmin(d, -1)

    n_enc = min(PQ_ENCODE_ROWS, len(vecs))
    # residuals from the host's coarse ids on the compared rows, from the
    # index's own beyond them (those rows only fill the encode chunk)
    cid_enc = np.concatenate([cid, codes[rows:n_enc, m]])
    resid = jnp.asarray(vecs[:n_enc] - codebook[m][cid_enc])
    for prec in ("HIGHEST", "DEFAULT"):
        got = jax.jit(matmul_form, static_argnums=2)(
            resid, jnp.asarray(codebook[:m, :, :dsub]),
            getattr(jax.lax.Precision, prec))
        out[prec] = float((np.asarray(got)[:rows] == rcodes).mean())
    return out


def run_one_chip(n: int = N_POINTS, n_queries: int = N_QUERIES,
                 l_sweep=L_SWEEP, log=print) -> dict:
    """Build the deployment from the seed, sweep L, check the kernel paths
    and the executable tier.  Every phase runs; then a :class:`SmokeFailure`
    names every failed check.  Returns what it measured."""
    import jax
    import numpy as np

    from repro.api import Deployment
    from repro.core import ref

    failed = []

    def check(ok: bool, what: str) -> None:
        if not ok:
            failed.append(what)

    cfg = _config(n, n_queries, N_PARTS)
    t0 = time.perf_counter()
    dep = Deployment.from_config(cfg)
    build = {k: round(v, 3) for k, v in dep.build_s.items()}
    log(f"build_s {json.dumps(build)} total={time.perf_counter() - t0:.3f}")

    vecs = dep.dataset.vectors
    k = cfg.search.k
    rows = np.linspace(0, n - 1, N_REF_CHECK).astype(np.int64)
    build_rows = np.resize(rows, min(KNN_BUILD_ROWS, n))
    graph_knn = ref.exact_knn(vecs, vecs[build_rows], cfg.index.knn_k)[0]
    for what, qs, got in (
            ("queries", dep.dataset.queries[:N_REF_CHECK],
             dep.dataset.gt[:N_REF_CHECK, :k]),
            ("database rows", vecs[rows], graph_knn[:N_REF_CHECK])):
        same, gap = _knn_vs_float64(vecs, qs, got)
        log(f"exact kNN vs float64 numpy on {len(qs)} {what}: "
            f"same ids {same}/{len(qs)}, max relative distance gap={gap:.3g}")
        check(gap <= 1e-4, f"device exact kNN ({what}) disagrees with numpy "
                           "beyond near-ties")

    agree = _pq_agreement(vecs, dep.index.codes, dep.index.codebook)
    log("PQ codes agreeing with a float64 host encode on "
        f"{min(PQ_CHECK_ROWS, n)} rows: index={agree['index']:.4f}, "
        f"matmul form at HIGHEST={agree['HIGHEST']:.4f}, "
        f"at DEFAULT={agree['DEFAULT']:.4f}")
    check(agree["index"] >= CODE_AGREEMENT,
          f"PQ codes agree with a float64 encode on only {agree['index']:.4f}")

    def at(**search):
        return Deployment.from_parts(cfg.with_updates(search=search),
                                     dep.engine, dep.dataset)

    out = {"build_s": build, "recall": {}}
    base = None
    for L in l_sweep:
        runs = 2 if L == l_sweep[0] else 1      # compile, then warm
        for i in range(runs):
            t0 = time.perf_counter()
            rep = at(L=L).run()
            sec = time.perf_counter() - t0
            tag = "first search (compile)" if i == 0 else "second search"
            log(f"search L={L} {tag}: {sec:.3f}s")
            out[f"search_L{L}_{'first' if i == 0 else 'second'}_s"] = sec
        c = rep.counters
        delivered = float(rep.stats["delivered"])
        log(f"L={L} recall@{rep.k}={rep.recall:.4f} hops={c['hops']:.2f} "
            f"inter_hops={c['inter_hops']:.2f} delivered={delivered:.4f}")
        out["recall"][L] = rep.recall
        check(delivered == 1.0, f"L={L}: only {delivered:.4f} delivered")
        check(c["inter_hops"] > 0, f"L={L}: no inter-partition hop")
        if base is None:
            base = rep
    top = out["recall"][l_sweep[-1]]
    check(top >= RECALL_FLOOR,
           f"recall@10={top:.4f} < {RECALL_FLOOR} at L={l_sweep[-1]}")

    t0 = time.perf_counter()
    kern = at(L=l_sweep[0], adc_impl="mxu_tiled", merge_impl="bitonic").run()
    log(f"kernels (mxu_tiled ADC, bitonic merge) L={l_sweep[0]}: "
        f"{time.perf_counter() - t0:.3f}s, "
        f"same ids={np.array_equal(kern.ids, base.ids)}")
    check(np.array_equal(kern.ids, base.ids),
           "Pallas kernel path returned other ids than the default path")

    queries = dep.dataset.queries[:N_EXEC]
    t0 = time.perf_counter()
    ex = Deployment.from_parts(
        cfg.with_updates(search={"L": l_sweep[0]},
                         exec={"workers": EXEC_WORKERS, "mode": "thread"}),
        dep.engine, dep.dataset).run_exec(queries)
    log(f"exec tier ({EXEC_WORKERS} thread workers): "
        f"{ex['completed']}/{N_EXEC} completed, {ex['handoffs']} hand-offs, "
        f"same ids and dists={ex['parity']}, "
        f"{time.perf_counter() - t0:.3f}s")
    check(ex["completed"] == N_EXEC, "executable tier lost queries")
    check(ex["parity"], "executable tier's ids or distances differ from "
                        "Deployment.search's")

    out["peak_bytes_in_use"] = _peak_bytes(jax.devices()[0])
    log(f"peak_bytes_in_use={out['peak_bytes_in_use']}")
    if failed:
        raise SmokeFailure("; ".join(failed))
    return out


def run_four_chips(n: int = FOUR_CHIP_POINTS, n_queries: int = N_QUERIES,
                   log=print) -> dict:
    """SPMD search over a 4-chip mesh, one partition per chip, against
    ``run_simulated`` on one chip: ids, distances and counters bit-equal."""
    import jax
    import numpy as np

    from repro.api import Deployment
    from repro.core import baton, ref

    _check(len(jax.devices()) >= FOUR_CHIP_PARTS,
           f"--four-chips needs {FOUR_CHIP_PARTS} devices, "
           f"found {len(jax.devices())}")
    cfg = _config(n, n_queries, FOUR_CHIP_PARTS)
    t0 = time.perf_counter()
    dep = Deployment.from_config(cfg)
    log(f"build_s {json.dumps({k: round(v, 3) for k, v in dep.build_s.items()})}"
        f" total={time.perf_counter() - t0:.3f}")
    index, queries = dep.index, dep.dataset.queries
    params = dep.engine.baton_params(cfg.search)

    t0 = time.perf_counter()
    ids_1, dists_1, stats_1 = baton.run_simulated(index, queries, params)
    log(f"run_simulated on one chip: {time.perf_counter() - t0:.3f}s")

    mesh = jax.make_mesh((FOUR_CHIP_PARTS,), ("part",),
                         devices=jax.devices()[:FOUR_CHIP_PARTS])
    t0 = time.perf_counter()
    ids_4, dists_4, stats_4 = baton.run_spmd(index, queries, params, mesh)
    log(f"SPMD search on {FOUR_CHIP_PARTS} chips: "
        f"{time.perf_counter() - t0:.3f}s")
    # one partition per chip, read from the placed arrays, not the mesh
    placed = stats_4["part_device"]
    log(f"partition -> chip: {dict(enumerate(placed))}")
    _check(None not in placed and len(set(placed)) == FOUR_CHIP_PARTS,
           f"partitions placed as {placed}")
    same = {
        "ids": np.array_equal(ids_4, ids_1),
        "dists": np.array_equal(dists_4, dists_1),
        "counters": all(np.array_equal(stats_4[k], stats_1[k])
                        for k in ("hops", "inter_hops", "dist_comps", "reads",
                                  "lut_builds")),
        "delivered": stats_4["delivered"] == stats_1["delivered"] == 1.0,
    }
    recall = ref.recall_at_k(ids_4, dep.dataset.gt, params.k)
    peaks = [_peak_bytes(d) for d in jax.devices()[:FOUR_CHIP_PARTS]]
    log(f"SPMD vs one chip: {same}; recall@{params.k}={recall:.4f} "
        f"inter_hops={float(np.mean(stats_4['inter_hops'])):.2f}")
    log(f"peak_bytes_in_use per chip={peaks}")
    for what, ok in same.items():
        _check(ok, f"SPMD and one-chip {what} differ")
    return {"same": same, "recall": recall, "peak_bytes_in_use": peaks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip SPMD path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax

    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(jax.devices())}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    from repro import compile_cache

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    try:
        if args.four_chips:
            run_four_chips(log=log)
        else:
            run_one_chip(log=log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
