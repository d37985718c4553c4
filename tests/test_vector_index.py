"""IVF-Flat index (Algorithm 2) + batched kNN + distributed kNN tests."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.pandadb import VectorIndexConfig
from repro.core.vector_index import (
    IVFIndex,
    merge_topk,
    pairwise_scores,
    recall_at_k,
    scan_topk,
    scatter_gather_knn,
)
from repro.data.synthetic_graph import sift_like_vectors


@pytest.fixture(scope="module")
def index():
    vecs = sift_like_vectors(4000, dim=32, n_clusters=16, seed=1)
    cfg = VectorIndexConfig(dim=32, metric="l2", vectors_per_bucket=250,
                            min_buckets=8, nprobe=4, kmeans_iters=4)
    return IVFIndex.build(vecs, cfg=cfg, seed=0)


def loop_search(index, queries, k, nprobe):
    """The seed's per-query host loop: the parity oracle for search_many."""
    q = jnp.asarray(queries, jnp.float32)
    cscores = pairwise_scores(q, jnp.asarray(index.centroids),
                              index.cfg.metric)
    _, probe = jax.lax.top_k(cscores, min(nprobe, index.centroids.shape[0]))
    probe = np.asarray(probe)
    out_v = np.full((queries.shape[0], k), -np.inf, np.float32)
    out_i = np.full((queries.shape[0], k), -1, np.int64)
    for qi in range(queries.shape[0]):
        segs = [index.bucket_slice(int(b)) for b in probe[qi]]
        rows = np.concatenate([np.arange(lo, hi) for lo, hi in segs]) \
            if segs else np.array([], np.int64)
        if rows.size == 0:
            continue
        vals, ids = scan_topk(q[qi:qi + 1], jnp.asarray(index.vectors[rows]),
                              jnp.asarray(index.ids[rows]), k,
                              index.cfg.metric)
        kk = vals.shape[1]
        out_v[qi, :kk] = np.asarray(vals)[0]
        out_i[qi, :kk] = np.asarray(ids)[0]
    return out_v, out_i


def test_build_bucket_count(index):
    m = index.centroids.shape[0]
    assert m >= 8          # n // vectors_per_bucket = 16, min 8
    assert index.vectors.shape[0] == 4000
    assert np.all(np.diff(index.bucket_of) >= 0)   # sorted by bucket


def test_every_vector_in_nearest_centroid(index):
    """Algorithm-2 invariant: assignment = nearest core vector."""
    s = np.asarray(pairwise_scores(jnp.asarray(index.vectors),
                                   jnp.asarray(index.centroids), "l2"))
    nearest = s.argmax(axis=1)
    assert (nearest == index.bucket_of).mean() > 0.999


def test_knn_recall(index):
    """Paper Fig 11: average recall stable above 0.95."""
    rng = np.random.default_rng(2)
    queries = index.vectors[rng.choice(4000, 32)] + \
        rng.standard_normal((32, 32)).astype(np.float32) * 0.01
    for k in (1, 10, 100):
        r = recall_at_k(index, queries, k, nprobe=6)
        assert r >= 0.95, (k, r)


def test_recall_increases_with_nprobe(index):
    rng = np.random.default_rng(3)
    queries = rng.standard_normal((16, 32)).astype(np.float32)
    r_lo = recall_at_k(index, queries, 10, nprobe=1)
    r_hi = recall_at_k(index, queries, 10, nprobe=index.centroids.shape[0])
    assert r_hi >= r_lo
    assert r_hi == pytest.approx(1.0)   # probing all buckets == exact


def test_dynamic_insert(index):
    # well-separated from the corpus (matmul-form L2 has ~1e-5 fp32 noise,
    # so near-duplicates can tie; distance 0.5 is unambiguous)
    v = index.vectors[7] + 0.5
    n0 = index.vectors.shape[0]
    b = index.insert(v, ext_id=999_999)
    # buffered append: the compacted arrays are untouched until compaction
    assert index.vectors.shape[0] == n0
    assert index.pending_count == 1
    assert index.n_total == n0 + 1
    vals, ids = index.search(v[None], k=1, nprobe=4)
    assert ids[0, 0] == 999_999          # searches see uncompacted rows
    index.compact()
    assert index.pending_count == 0
    assert index.vectors.shape[0] == n0 + 1
    assert np.all(np.diff(index.bucket_of) >= 0)   # layout still sorted
    vals, ids = index.search(v[None], k=1, nprobe=4)
    assert ids[0, 0] == 999_999
    # restore module-scoped index (remove inserted row)
    keep = index.ids != 999_999
    index.vectors = index.vectors[keep]
    index.ids = index.ids[keep]
    index.bucket_of = index.bucket_of[keep]


def test_batched_matches_loop_clustered(index):
    """Probe-signature grouping: clustered queries, identical ids to the
    per-query loop (vals to fp32 reduction-order noise)."""
    rng = np.random.default_rng(7)
    queries = index.vectors[rng.choice(4000, 48)] + \
        rng.standard_normal((48, 32)).astype(np.float32) * 0.01
    for k, nprobe in [(1, 4), (10, 4), (100, 6)]:
        v1, i1 = index.search_many(queries, k, nprobe)
        v2, i2 = loop_search(index, queries, k, nprobe)
        assert np.array_equal(i1, i2), (k, nprobe)
        np.testing.assert_allclose(v1, v2, rtol=1e-3, atol=1e-4)


def test_batched_matches_loop_scattered(index):
    """Scattered signatures, one gathered scan per signature: same
    candidates."""
    rng = np.random.default_rng(8)
    queries = rng.standard_normal((64, 32)).astype(np.float32)
    for k, nprobe in [(10, 4), (10, 8)]:
        v1, i1 = index.search_many(queries, k, nprobe)
        v2, i2 = loop_search(index, queries, k, nprobe)
        assert np.array_equal(i1, i2), (k, nprobe)
        np.testing.assert_allclose(v1, v2, rtol=1e-3, atol=1e-4)


def test_single_query_fast_path_matches_batched(index):
    """Q=1 skips probe-signature grouping / block padding / device
    dispatch; candidates must match the batched path (the same query
    duplicated engages grouping)."""
    rng = np.random.default_rng(12)
    queries = index.vectors[rng.choice(4000, 8)] + \
        rng.standard_normal((8, 32)).astype(np.float32) * 0.01
    for k, nprobe in [(1, 4), (10, 4), (10, index.centroids.shape[0])]:
        for qi in range(queries.shape[0]):
            q1 = queries[qi:qi + 1]
            v_fast, i_fast = index.search_many(q1, k, nprobe)
            v_batch, i_batch = index.search_many(
                np.concatenate([q1, q1]), k, nprobe)
            assert np.array_equal(i_fast[0], i_batch[0]), (k, nprobe, qi)
            # host BLAS vs device reduction order: the matmul-identity L2
            # cancels near-duplicate distances to ~1e-4 absolute noise
            np.testing.assert_allclose(v_fast[0], v_batch[0],
                                       rtol=1e-3, atol=1e-3)


def test_single_query_fast_path_tie_order():
    """Duplicate corpus vectors tie exactly: the fast path must break ties
    by lower row index, like the batched path's lax.top_k."""
    vecs = sift_like_vectors(400, dim=16, n_clusters=8, seed=13)
    dup = np.concatenate([vecs, vecs])          # every vector twice
    cfg = VectorIndexConfig(dim=16, metric="l2", vectors_per_bucket=100,
                            min_buckets=4, nprobe=3, kmeans_iters=2)
    idx = IVFIndex.build(dup, cfg=cfg, seed=0)
    rng = np.random.default_rng(14)
    queries = vecs[rng.choice(400, 16)]
    for qi in range(16):
        q1 = queries[qi:qi + 1]
        _, i_fast = idx.search_many(q1, 4, 3)
        _, i_batch = idx.search_many(np.concatenate([q1, q1]), 4, 3)
        assert np.array_equal(i_fast[0], i_batch[0]), qi


def test_single_query_fast_path_stats_feedback(index):
    from repro.core.cost_model import StatisticsService
    stats = StatisticsService()
    q = index.vectors[:1] + 0.01
    index.search_many(q, 5, nprobe=4, stats=stats)
    assert stats.counts.get("knn_scan", 0) > 0


def test_exact_mode_byte_identical(index):
    """nprobe=m is exact mode: one probe signature, one fused scan,
    byte-identical ids to the loop."""
    rng = np.random.default_rng(9)
    queries = rng.standard_normal((32, 32)).astype(np.float32)
    m = index.centroids.shape[0]
    _, i1 = index.search_many(queries, 10, m)
    _, i2 = loop_search(index, queries, 10, m)
    assert np.array_equal(i1, i2)


def test_insert_then_search_uncompacted():
    """Uncompacted buffer rows participate in probe, exact and batched
    searches; compaction changes nothing observable."""
    vecs = sift_like_vectors(600, dim=16, n_clusters=8, seed=5)
    cfg = VectorIndexConfig(dim=16, metric="l2", vectors_per_bucket=100,
                            min_buckets=4, nprobe=3, kmeans_iters=2)
    idx = IVFIndex.build(vecs, cfg=cfg, seed=0)
    rng = np.random.default_rng(6)
    new = rng.standard_normal((20, 16)).astype(np.float32) * 0.1 + vecs[:20]
    for j, v in enumerate(new):
        idx.insert(v, 10_000 + j)
    assert idx.pending_count == 20
    assert idx.n_total == 620
    for j, v in enumerate(new):
        _, ids = idx.search(v[None], k=1, nprobe=idx.centroids.shape[0])
        assert ids[0, 0] == 10_000 + j          # exact mode must find it
    _, ids_exact = idx.search_exact(new, 1)
    assert set(ids_exact[:, 0].tolist()) == set(range(10_000, 10_020))
    # the batched signature-group path sees pending rows too
    queries = rng.standard_normal((32, 16)).astype(np.float32)
    v_pend, i_pend = idx.search_many(queries, 5, 3)
    idx.compact()
    v_comp, i_comp = idx.search_many(queries, 5, 3)
    assert np.array_equal(i_pend, i_comp)
    np.testing.assert_allclose(v_pend, v_comp, rtol=1e-3, atol=1e-4)


def test_insert_many_matches_single_inserts():
    vecs = sift_like_vectors(300, dim=8, n_clusters=4, seed=2)
    cfg = VectorIndexConfig(dim=8, vectors_per_bucket=100, min_buckets=2,
                            kmeans_iters=2)
    a = IVFIndex.build(vecs, cfg=cfg, seed=0)
    b = IVFIndex.build(vecs, cfg=cfg, seed=0)
    rng = np.random.default_rng(3)
    new = rng.standard_normal((10, 8)).astype(np.float32)
    for j, v in enumerate(new):
        a.insert(v, 500 + j)
    b.insert_many(new, np.arange(500, 510))
    a.compact()
    b.compact()
    assert np.array_equal(a.ids, b.ids)
    assert np.array_equal(a.bucket_of, b.bucket_of)
    np.testing.assert_array_equal(a.vectors, b.vectors)


def test_pending_compaction_threshold():
    vecs = sift_like_vectors(200, dim=8, n_clusters=4, seed=4)
    cfg = VectorIndexConfig(dim=8, vectors_per_bucket=50, min_buckets=2,
                            kmeans_iters=1, pending_compact_min=16,
                            pending_compact_frac=0.01)
    idx = IVFIndex.build(vecs, cfg=cfg, seed=0)
    rng = np.random.default_rng(5)
    for j in range(16):
        idx.insert(rng.standard_normal(8).astype(np.float32), 1000 + j)
    # the 16th insert crosses pending_compact_min and auto-compacts
    assert idx.pending_count == 0
    assert idx.vectors.shape[0] == 216
    assert np.all(np.diff(idx.bucket_of) >= 0)


def _flat_pieces(corpus, ids, n_shards):
    """``n_shards`` pieces of a one-bucket IVF-Flat index over ``corpus``,
    row r on piece r % n_shards: the layout ``ShardedPandaDB`` serves."""
    corpus = np.asarray(corpus, np.float32)
    cfg = VectorIndexConfig(dim=corpus.shape[1], metric="l2", min_buckets=1,
                            vectors_per_bucket=len(corpus), nprobe=1)
    index = IVFIndex.build(corpus, ids=np.asarray(ids), cfg=cfg)
    return index.shard(n_shards, strategy="roundrobin")


def test_distributed_knn_equals_global():
    """Sharded kNN (per-piece scan, k-way merge) equals the global scan."""
    rng = np.random.default_rng(4)
    corpus = jnp.asarray(rng.standard_normal((1024, 16)), jnp.float32)
    ids = jnp.arange(1024)
    q = jnp.asarray(rng.standard_normal((5, 16)), jnp.float32)
    v_g, i_g = scan_topk(q, corpus, ids, 8, "l2")
    pieces = _flat_pieces(corpus, ids, 4)
    v_d, i_d = scatter_gather_knn(pieces, np.asarray(q), 8)
    np.testing.assert_allclose(np.asarray(v_g), v_d, rtol=1e-5)
    assert np.array_equal(np.asarray(i_g), i_d)


def test_distributed_knn_no_sentinel_leak():
    """Pieces smaller than k pad with (-inf, -1); the merge must never show
    those to callers when enough real candidates exist, and past the real
    rows every slot is (-inf, -1), never a -1 with a finite score."""
    rng = np.random.default_rng(11)
    corpus = rng.standard_normal((10, 4)).astype(np.float32)
    q = rng.standard_normal((3, 4)).astype(np.float32)
    # 4 pieces of 2-3 rows, k=8 > any piece: total rows (10) >= k -> no -1
    pieces = _flat_pieces(corpus, np.arange(10), 4)
    assert max(p.n_total for p in pieces) < 8
    v, i = scatter_gather_knn(pieces, q, 8)
    assert np.all(i >= 0)
    assert np.all(np.isfinite(v))
    # total rows (10) < k=20 -> 10 real columns, then padding only
    v, i = scatter_gather_knn(pieces, q, 20)
    assert v.shape == (3, 20) and i.shape == (3, 20)
    assert np.all(i[:, :10] >= 0) and np.all(np.isfinite(v[:, :10]))
    assert np.all(i[:, 10:] == -1) and np.all(np.isinf(v[:, 10:]))


def test_merge_topk_associative():
    rng = np.random.default_rng(5)
    v = jnp.asarray(rng.standard_normal((6, 3, 8)), jnp.float32)
    i = jnp.asarray(rng.integers(0, 1000, (6, 3, 8)))
    v_all, i_all = merge_topk(v, i, 8)
    # split merge: (first 3) + (last 3) then merge again
    v1, i1 = merge_topk(v[:3], i[:3], 8)
    v2, i2 = merge_topk(v[3:], i[3:], 8)
    v12, i12 = merge_topk(jnp.stack([v1, v2]), jnp.stack([i1, i2]), 8)
    np.testing.assert_allclose(np.asarray(v_all), np.asarray(v12), rtol=1e-6)


def test_index_shard_partition(index):
    shards = index.shard(4)
    assert sum(s.vectors.shape[0] for s in shards) == index.vectors.shape[0]
    for s in shards:
        assert s.centroids is index.centroids     # replicated


def test_metrics():
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((20, 8)), jnp.float32)
    s_l2 = np.asarray(pairwise_scores(q, c, "l2"))
    manual = -((np.asarray(q)[:, None] - np.asarray(c)[None]) ** 2).sum(-1)
    np.testing.assert_allclose(s_l2, manual, rtol=1e-4, atol=1e-4)
    s_cos = np.asarray(pairwise_scores(q, c, "cosine"))
    assert np.all(s_cos <= 1.0 + 1e-5)
