"""Core vector-search behaviour: PQ, Vamana, beam search vs oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core import beam_search, pq, ref
from repro.core.state import INF, NO_ID, init_state


def test_brute_force_is_exact(dataset):
    d = ref.pairwise_sq_l2(dataset.queries[:4], dataset.vectors)
    naive = np.argsort(d, axis=1)[:, :10]
    assert np.array_equal(np.sort(naive), np.sort(dataset.gt[:4]))


@pytest.mark.parametrize("n,q,k,d", [
    (1, 3, 1, 8), (37, 5, 10, 96), (1500, 64, 17, 96), (20000, 33, 10, 32),
    (300, 2100, 5, 8),
])
def test_exact_knn_matches_numpy(n, q, k, d):
    """The jitted, tiled kNN == a plain numpy sort of the full distance
    matrix; a swap is accepted only between (near-)equal distances."""
    rng = np.random.default_rng(n + q)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    qs = rng.normal(size=(q, d)).astype(np.float32)
    ids, dists = ref.exact_knn(vecs, qs, k)
    full = ((qs[:, None, :].astype(np.float64) - vecs[None]) ** 2).sum(-1)
    naive = np.argsort(full, axis=1, kind="stable")[:, :k]
    want = np.take_along_axis(full, naive, 1)
    assert ids.shape == dists.shape == (q, k)
    np.testing.assert_allclose(dists, want, rtol=1e-4, atol=1e-4)
    swapped = ids != naive
    got = np.take_along_axis(full, ids, 1)
    np.testing.assert_allclose(got[swapped], want[swapped], rtol=1e-5,
                               atol=1e-5)
    assert np.array_equal(ref.brute_force_knn(vecs, qs, k), ids)


def test_chunked_reverse_prune_unchanged(dataset, monkeypatch):
    """Pruning in width-grouped calls of at most PRUNE_ELEMS candidates
    gives the same graph as one call over every row (rows are independent;
    padding columns are never picked)."""
    from repro.core import vamana

    def one_call(vectors, jvec, neighbors, rows, cids, widths, r, alpha):
        cd = vamana._exact_dists(vectors, vectors[rows], cids)
        neighbors[rows] = np.asarray(vamana._robust_prune_batch(
            jnp.asarray(vectors[rows]), jnp.asarray(cids), jnp.asarray(cd),
            jvec, r=r, alpha=alpha))

    knn = ref.brute_force_knn(dataset.vectors, dataset.vectors, 17)[:, 1:]
    with monkeypatch.context() as m:
        m.setattr(vamana, "_prune_rows", one_call)
        whole = vamana.build_from_knn(dataset.vectors, knn, r=20)
    monkeypatch.setattr(vamana, "PRUNE_ELEMS", 2048)
    chunked = vamana.build_from_knn(dataset.vectors, knn, r=20)
    assert np.array_equal(chunked.neighbors, whole.neighbors)
    assert chunked.medoid == whole.medoid


def test_pq_distance_correlation(dataset, codebook, codes):
    """ADC distances must track exact distances (the index's guidance signal)."""
    lut = pq.build_lut(codebook.centroids, jnp.asarray(dataset.queries[:8]))
    approx = np.asarray(pq.adc(lut, jnp.asarray(codes)))
    exact = ref.pairwise_sq_l2(dataset.queries[:8], dataset.vectors)
    corr = np.corrcoef(approx.ravel(), exact.ravel())[0, 1]
    assert corr > 0.9, corr


def test_pq_reconstruction_consistency(dataset, codebook, codes):
    """adc(q, code(x)) == ||q - reconstruct(code(x))||^2 + level_error
    (the quantized cross term's error) by construction."""
    q = jnp.asarray(dataset.queries[:4])
    lut = pq.build_lut(codebook.centroids, q)
    approx = np.asarray(pq.adc(lut, jnp.asarray(codes[:50])))
    recon = np.asarray(pq.reconstruct(codebook, jnp.asarray(codes[:50])))
    off = np.asarray(pq.level_error(codebook, jnp.asarray(codes[:50])))
    exact = ref.pairwise_sq_l2(dataset.queries[:4], recon) + off[None]
    np.testing.assert_allclose(approx, exact, rtol=1e-3, atol=1e-3)


def test_vamana_graph_wellformed(graph, dataset):
    n = dataset.n
    nbrs = graph.neighbors
    assert nbrs.shape[0] == n
    valid = nbrs[nbrs >= 0]
    assert valid.max() < n
    # no self-loops
    rows = np.repeat(np.arange(n), nbrs.shape[1])
    assert not np.any(rows[nbrs.reshape(-1) >= 0] == valid)
    # reasonable connectivity
    deg = (nbrs >= 0).sum(1)
    assert deg.mean() > graph.R * 0.4


def test_inmem_search_matches_reference(dataset, graph):
    """Fixed-shape lax beam search == plain-python Algorithm 1."""
    for qi in range(4):
        got, _ = None, None
        res = beam_search.search_inmem(
            jnp.asarray(dataset.vectors), jnp.asarray(graph.neighbors),
            jnp.asarray(dataset.queries[qi]),
            jnp.asarray([graph.medoid], dtype=jnp.int32), L=32, max_hops=256,
        )
        expect, stats = ref.greedy_beam_search_ref(
            dataset.vectors, graph.neighbors, dataset.queries[qi],
            graph.medoid, L=32, k=10,
        )
        got = np.asarray(res.beam_ids[:10])
        # identical top-10 (both exact-distance beam searches, same graph)
        assert set(got.tolist()) == set(expect.tolist()), qi


def test_inmem_search_recall(dataset, graph):
    res = jax.vmap(
        lambda q: beam_search.search_inmem(
            jnp.asarray(dataset.vectors), jnp.asarray(graph.neighbors), q,
            jnp.asarray([graph.medoid], dtype=jnp.int32), L=40, max_hops=256,
        )
    )(jnp.asarray(dataset.queries))
    rec = ref.recall_at_k(np.asarray(res.beam_ids), dataset.gt, 10)
    assert rec > 0.9, rec


def _single_shard(dataset, graph, codes):
    return beam_search.Shard(
        vectors=jnp.asarray(dataset.vectors),
        neighbors=jnp.asarray(graph.neighbors),
        codes=jnp.asarray(codes),
        node2part=jnp.zeros(dataset.n, jnp.int32),
        node2local=jnp.arange(dataset.n, dtype=jnp.int32),
    )


@pytest.mark.parametrize("w", [1, 2, 8])
def test_disk_search_recall_and_counters(dataset, graph, codebook, codes, w):
    shard = _single_shard(dataset, graph, codes)

    def run(q):
        lut = pq.build_lut(codebook.centroids, q[None])[0]
        starts = jnp.asarray([graph.medoid], dtype=jnp.int32)
        sd = pq.adc(lut[None], shard.codes[starts])[0]
        st = init_state(q, starts, sd, L=40, P=256)
        return beam_search.search_disk(st, shard, codebook.centroids, w=w,
                                       max_hops=512)

    out = jax.vmap(run)(jnp.asarray(dataset.queries))
    rec = ref.recall_at_k(np.asarray(out.pool_ids[:, :10]), dataset.gt, 10)
    assert rec > 0.85, (w, rec)
    hops = np.asarray(out.counters.hops, dtype=np.float64)
    reads = np.asarray(out.counters.reads, dtype=np.float64)
    assert (hops < 512).all(), "non-convergence (stuck explored flag)"
    # reads should stay near L regardless of W (paper Fig. 5)
    assert reads.mean() < 40 * 3.0, reads.mean()
    if w > 1:
        assert hops.mean() < reads.mean(), "W>1 must batch reads per hop"


def test_w8_reduces_hops(dataset, graph, codebook, codes):
    """Paper Fig. 4: higher W -> fewer hops, similar reads/dist comps."""
    shard = _single_shard(dataset, graph, codes)

    def run(q, w):
        lut = pq.build_lut(codebook.centroids, q[None])[0]
        starts = jnp.asarray([graph.medoid], dtype=jnp.int32)
        sd = pq.adc(lut[None], shard.codes[starts])[0]
        st = init_state(q, starts, sd, L=40, P=256)
        return beam_search.search_disk(st, shard, codebook.centroids, w=w,
                                       max_hops=512)

    o1 = jax.vmap(lambda q: run(q, 1))(jnp.asarray(dataset.queries))
    o8 = jax.vmap(lambda q: run(q, 8))(jnp.asarray(dataset.queries))
    h1 = np.asarray(o1.counters.hops).mean()
    h8 = np.asarray(o8.counters.hops).mean()
    d1 = np.asarray(o1.counters.dist_comps).mean()
    d8 = np.asarray(o8.counters.dist_comps).mean()
    assert h8 < h1 / 2.0, (h1, h8)
    assert d8 < d1 * 1.5, (d1, d8)


# ---------------------------------------------------------------------------
# fixed-shape primitive properties
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(
    l=st.integers(2, 24), c=st.integers(1, 24), seed=st.integers(0, 2**16),
)
def test_merge_into_beam_properties(l, c, seed):
    rng = np.random.default_rng(seed)
    bids = rng.choice(100, size=l, replace=False).astype(np.int32)
    bdist = rng.random(l).astype(np.float32)
    bexp = rng.random(l) < 0.5
    npad = rng.integers(0, l)
    bids[:npad] = NO_ID
    bdist[:npad] = np.inf
    bexp[:npad] = False
    cids = rng.choice(120, size=c, replace=False).astype(np.int32)
    cdist = rng.random(c).astype(np.float32)
    cpad = rng.integers(0, c + 1)
    cids[:cpad] = NO_ID
    cdist[:cpad] = np.inf

    ids, dists, expl = beam_search.merge_into_beam(
        jnp.asarray(bids), jnp.asarray(bdist), jnp.asarray(bexp),
        jnp.asarray(cids), jnp.asarray(cdist),
    )
    ids, dists, expl = map(np.asarray, (ids, dists, expl))
    # sorted ascending over the finite (real) prefix
    fin = np.isfinite(dists)
    nfin = int(fin.sum())
    assert fin[:nfin].all(), "finite entries must precede padding"
    if nfin > 1:
        assert (np.diff(dists[:nfin]) >= 0).all()
    # no duplicate real ids
    real = ids[ids >= 0]
    assert len(real) == len(set(real.tolist()))
    # semantics: for ids present in the beam, the beam copy is authoritative
    # when explored; otherwise min(beam, candidate) distance wins.
    best = {}
    for i, d, e in zip(bids, bdist, bexp):
        if i >= 0:
            best[int(i)] = (float(d), bool(e))
    for i, d in zip(cids, cdist):
        if i < 0:
            continue
        i = int(i)
        if i in best:
            bd, be = best[i]
            if not be:
                best[i] = (min(bd, float(d)), False)
        else:
            best[i] = (float(d), False)
    want = sorted(best.items(), key=lambda kv: (kv[1][0], kv[0]))[:l]
    got = [(int(i), float(d), bool(e)) for i, d, e in zip(ids, dists, expl)
           if i >= 0 and np.isfinite(d)]
    expect = [(i, float(np.float32(d)), e) for i, (d, e) in want
              if np.isfinite(d)]
    assert got == expect[: len(got)] and len(got) == len(expect)


@settings(max_examples=30, deadline=None)
@given(w=st.integers(1, 8), l=st.integers(2, 20), seed=st.integers(0, 2**16))
def test_select_frontier_properties(w, l, seed):
    rng = np.random.default_rng(seed)
    ids = rng.choice(50, size=l, replace=False).astype(np.int32)
    ids[rng.random(l) < 0.3] = NO_ID
    expl = rng.random(l) < 0.5
    pos, fids, valid = beam_search.select_frontier(
        jnp.asarray(ids), jnp.asarray(expl), w
    )
    pos, fids, valid = map(np.asarray, (pos, fids, valid))
    unexp = [(i, v) for i, v in enumerate(ids) if v >= 0 and not expl[i]]
    assert valid.sum() == min(w, len(unexp))
    # frontier = first min(w, .) unexplored positions (beam is dist-sorted)
    expect = [i for i, _ in unexp[:w]]
    assert pos[valid].tolist() == expect
