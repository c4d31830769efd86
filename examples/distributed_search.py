"""Distributed execution demo: the same baton search on 8 real devices
(shard_map + all_to_all) vs the single-host simulation — results must match
bit-exactly — plus a failover demonstration.

Setup routes through the documented ``repro.api`` service layer
(``Deployment.from_config`` builds the dataset + index; failover re-wraps
the rescaled index with ``Deployment.from_parts``).  The SPMD execution
itself calls the engine's multi-device driver ``baton.run_spmd`` *below*
the API — the one remaining entry point the ``Engine`` protocol does not
cover; see docs/ARCHITECTURE.md "Known gap".
Start from ``examples/quickstart.py`` for the pure Deployment-level API.

    PYTHONPATH=src python examples/distributed_search.py
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax

from repro.api import (
    DataSpec, Deployment, IndexSpec, SearchParams, ServeConfig,
)
from repro.api.engine import BatonEngine
from repro.core import baton
from repro.ft.elastic import rescale_assignment


CONFIG = ServeConfig(
    name="distributed-search-demo",
    data=DataSpec(n=3000, n_queries=48, seed=0),
    index=IndexSpec(p=8, graph_mode="vamana", r=20, l_build=40, pq_m=24,
                    pq_k=128, head_fraction=0.02),
    search=SearchParams(L=40, W=8, k=10, pool=256, slots=24),
)


def main():
    dep = Deployment.from_config(CONFIG)
    ds, index = dep.dataset, dep.index
    cfg = dep.engine.baton_params(CONFIG.search)

    print("== single-host simulation (8 partitions, vmapped) ==")
    rep = dep.run()
    ids_sim = rep.ids
    print(f"recall@10={rep.recall:.3f} hops={rep.counters['hops']:.1f} "
          f"inter={rep.counters['inter_hops']:.2f}")

    print("\n== SPMD: shard_map over 8 devices, all_to_all state routing ==")
    mesh = jax.make_mesh((8,), ("part",))
    ids_spmd, _, st2 = baton.run_spmd(index, ds.queries, cfg, mesh)
    match = np.array_equal(ids_sim, ids_spmd)
    from repro.core import ref
    print(f"recall@10={ref.recall_at_k(ids_spmd, ds.gt, 10):.3f} "
          f"delivered={st2['delivered']:.0%}  bit-identical to sim: {match}")
    assert match

    print("\n== failover: device dies, re-shard 8 -> 6 partitions ==")
    new_assign = rescale_assignment(index.graph.neighbors, index.assign, 6)
    idx6 = baton.build_index(ds.vectors, p=6, pq_m=24, pq_k=128,
                             head_fraction=0.02, graph=index.graph,
                             assign=new_assign)
    dep6 = Deployment.from_parts(CONFIG.with_updates(index={"p": 6}),
                                 BatonEngine(index=idx6), dataset=ds)
    rep6 = dep6.run()
    delivered = rep6.stats["delivered"]
    print(f"recall@10={rep6.recall:.3f} "
          f"delivered={delivered:.0%} (search survives rescale)")


if __name__ == "__main__":
    main()
