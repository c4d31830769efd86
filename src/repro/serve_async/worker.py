"""Partition-owning workers: the service loop behind the async tier.

A worker owns one or more partition shards (partitions fold onto workers as
``part % n_workers``, the same fold ``cluster.Placement.fold`` uses to map
partitions onto fewer servers).  Its loop is the executable version of the
engine's super-step, a micro-batch of batons at a time:

    drain up to ``batch`` batons (hand-offs first)  — queues.get_many
      admit: seed the state                         — baton.refill
      frame: decode + LUT restore per baton         — baton.merge_recv
      local: in-memory leaves, no codec             — co-location short cut
    group by resident partition and advance each
    group in ONE jit dispatch                       — runtime.advance_batch
      (a single baton takes the scalar fast path,   — baton.local_advance
       so batch=1 reproduces the one-at-a-time
       loop dispatch-for-dispatch)
    done  -> result message to client               — baton.deliver_local
    else  -> coalesce all batons bound for the same — baton.pack_sends
             destination worker into one frame

Because the per-query math is untouched (``runtime`` drives the engine's
own primitives and the batch advance is row-masked, never cross-query),
*where*, *when* and *with whom* a baton runs never changes *what* it
computes — concurrency and batching may reorder completions, never
answers.  A hand-off whose destination partition lives on the same worker
still counts an ``inter_hops`` and re-enters the priority lane (partitions
are the paper's servers; worker count is a deployment choice), but as the
in-memory leaf dict — the sender-side ``pack_for_wire`` and receiver-side
``unpack_from_wire`` transforms (the §8 LUT drop/quantize semantics) still
run, only the byte codec is skipped, so answers cannot depend on whether a
hop crossed a process boundary.

The same loop body serves both modes: thread workers share jitted shards
and one compile cache; process workers rebuild their shards from numpy in
the child (spawn-safe) and pay their own jit, talking over ``mp.Queue``s.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from repro.serve_async import runtime, wire

# message kinds on the result queue
RESULT = "result"
# hand-off payload tags (first element of a hand-off queue item)
FRAME = "frame"      # coalesced cross-worker frame: (FRAME, bytes)
LOCAL = "local"      # same-worker short-circuit: (LOCAL, arrival, part, leaves)


def _expand(got, codebook, cfg):
    """Drained queue items -> work list of ``(arrival_id, state, part)``."""
    import jax.numpy as jnp

    work = []
    for kind, msg in got:
        if kind == "admit":
            arrival_id, qid, home, query, starts, start_d, lut = msg
            st = runtime.seed_state(
                jnp.asarray(query), jnp.asarray(starts),
                jnp.asarray(start_d), jnp.asarray(lut),
                home, qid, cfg.L, cfg.pool,
            )
            work.append((arrival_id, st, int(home)))
        elif msg[0] == LOCAL:
            _, arrival_id, part, leaves = msg
            st = runtime.unpack_from_wire(leaves, codebook, cfg)
            work.append((arrival_id, st, int(part)))
        else:
            for arrival_id, part, payload in wire.decode_frame(msg[1]):
                st = runtime.unpack_from_wire(
                    wire.decode_baton(payload), codebook, cfg)
                work.append((arrival_id, st, int(part)))
    return work


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def service_loop(wid: int, shards: dict, codebook, cfg, inbox, inboxes,
                 part2worker, results, batch: int = 1) -> None:
    """Drain the inbox until stopped; see the module docstring for the map
    from each step to its engine counterpart."""
    k = cfg.k
    while True:
        got = inbox.get_many(batch)
        if got is None:
            return
        work = _expand(got, codebook, cfg)
        outgoing = []                       # (arrival_id, dest_part, state)
        while work:
            part = work[0][2]
            group = [it for it in work if it[2] == part]
            work = [it for it in work if it[2] != part]
            # advance in power-of-two chunks: jax compiles one
            # ``advance_batch`` variant per distinct batch shape, so
            # rounding group sizes down to {1, 2, 4, ..., batch} bounds
            # the compile set (and lets a warm-up run cover it); deferred
            # items re-enter the work list and ride the next chunk
            take = _pow2_floor(len(group))
            group, work = group[:take], work + group[take:]
            if len(group) == 1:
                a, st, _ = group[0]
                st, done, dest = runtime.advance_state(
                    st, shards[part], part, cfg.W, cfg.max_local_steps)
                resolved = [(a, st, bool(done), int(dest))]
            else:
                sts = runtime.stack_states([g[1] for g in group])
                sts, done, dest = runtime.advance_batch(
                    sts, shards[part], part, cfg.W, cfg.max_local_steps,
                    adc_impl=cfg.adc_impl, merge_impl=cfg.merge_impl)
                states = runtime.unstack_states(sts, len(group))
                done, dest = np.asarray(done), np.asarray(dest)
                resolved = [
                    (group[i][0], states[i], bool(done[i]), int(dest[i]))
                    for i in range(len(group))
                ]
            inbox.add_advance()
            for a, st, done, dest in resolved:
                if done:
                    results.put((
                        RESULT, a, int(st.qid),
                        np.asarray(st.pool_ids)[:k].copy(),
                        np.asarray(st.pool_dists)[:k].copy(),
                        np.asarray(st.counters.stacked()).copy(),
                        time.perf_counter(),
                    ))
                    inbox.release()
                elif dest == part:
                    # max_local_steps fired with local work left: the state
                    # stays in this drain's work list — the next super-step
                    work.append((a, st, part))
                else:
                    outgoing.append((a, dest, st))
        # --- coalesced hand-offs: one message per destination worker -------
        by_worker: dict = {}
        for a, dest, st in outgoing:
            by_worker.setdefault(part2worker[dest], []).append((a, dest, st))
        for dw, items in sorted(by_worker.items()):
            if dw == wid:
                # co-location short-circuit: wire transforms, no codec
                for a, dest, st in items:
                    inboxes[wid].push_handoff(
                        (LOCAL, a, dest, runtime.pack_for_wire(st, cfg)),
                        n=1, local=True)
            else:
                records = [
                    (a, dest, wire.encode_baton(runtime.pack_for_wire(st,
                                                                      cfg)))
                    for a, dest, st in items
                ]
                frame = wire.encode_frame(records)
                inboxes[dw].push_handoff(
                    (FRAME, frame), n=len(records), nbytes=len(frame))
            for _ in items:
                inbox.release()


def start_thread_worker(wid, shards, codebook, cfg, inbox, inboxes,
                        part2worker, results, batch=1) -> threading.Thread:
    t = threading.Thread(
        target=service_loop, name=f"serve-async-w{wid}", daemon=True,
        args=(wid, shards, codebook, cfg, inbox, inboxes, part2worker,
              results, batch),
    )
    t.start()
    return t


def process_worker_main(wid, owned, shard_arrays, codebook_np, cfg_dict,
                        inbox, inboxes, part2worker, results,
                        batch=1) -> None:
    """Child-process entry: rebuild jax shards from numpy, then serve.

    ``shard_arrays`` maps owned partition -> the numpy leaves of its
    ``runtime.partition_shard``; ``cfg_dict`` is the ``BatonParams``
    field dict (plain scalars, pickles fine).
    """
    import jax.numpy as jnp

    from repro.core.baton import BatonParams
    from repro.core.beam_search import Shard

    cfg = BatonParams(**cfg_dict)
    shards = {}
    for part in owned:
        leaves = shard_arrays[part]
        shards[part] = Shard(**{
            name: jnp.asarray(a) if a is not None else None
            for name, a in leaves.items()
        })
    codebook = jnp.asarray(codebook_np)
    service_loop(wid, shards, codebook, cfg, inbox, inboxes, part2worker,
                 results, batch)
