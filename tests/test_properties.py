"""Hypothesis property tests on system invariants."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed; property tests are optional extras")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax
import jax.numpy as jnp

SETTINGS = dict(max_examples=25, deadline=None)


# -- distributed top-k == global top-k -----------------------------------------

@settings(**SETTINGS)
@given(
    n=st.integers(32, 256),
    k=st.integers(1, 8),
    shards=st.integers(1, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_distributed_topk_equals_global(n, k, shards, seed):
    from repro.configs.pandadb import VectorIndexConfig
    from repro.core.vector_index import (IVFIndex, scan_topk,
                                         scatter_gather_knn)
    rng = np.random.default_rng(seed)
    corpus = rng.standard_normal((n, 8)).astype(np.float32)
    q = rng.standard_normal((2, 8)).astype(np.float32)
    v_g, _ = scan_topk(jnp.asarray(q), jnp.asarray(corpus), jnp.arange(n),
                       k, "l2")
    cfg = VectorIndexConfig(dim=8, metric="l2", min_buckets=1,
                            vectors_per_bucket=n, nprobe=1)
    pieces = IVFIndex.build(corpus, cfg=cfg, seed=seed).shard(
        shards, strategy="roundrobin")
    v_d, _ = scatter_gather_knn(pieces, q, k)
    np.testing.assert_allclose(np.asarray(v_g), v_d, rtol=1e-4, atol=1e-4)


# -- IVF invariants --------------------------------------------------------------

@settings(**SETTINGS)
@given(n=st.integers(64, 400), seed=st.integers(0, 1000))
def test_ivf_partition_is_total(n, seed):
    from repro.configs.pandadb import VectorIndexConfig
    from repro.core.vector_index import IVFIndex
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, 8)).astype(np.float32)
    idx = IVFIndex.build(vecs, cfg=VectorIndexConfig(
        dim=8, vectors_per_bucket=50, min_buckets=2, kmeans_iters=2),
        seed=seed)
    # every vector exactly once; ids form a permutation
    assert idx.vectors.shape[0] == n
    assert sorted(idx.ids.tolist()) == list(range(n))
    # bucket slices tile the array
    m = idx.centroids.shape[0]
    total = sum(idx.bucket_slice(b)[1] - idx.bucket_slice(b)[0]
                for b in range(m))
    assert total == n


# -- probe-group batching: search_many == per-query searches -----------------------

@settings(**SETTINGS)
@given(
    n=st.integers(64, 300),
    qn=st.integers(1, 12),
    k=st.integers(1, 8),
    nprobe=st.integers(1, 6),
    seed=st.integers(0, 1000),
)
def test_batched_search_equals_per_query(n, qn, k, nprobe, seed):
    """Grouping queries by probe signature must return exactly what
    one-query-at-a-time searches return."""
    from repro.configs.pandadb import VectorIndexConfig
    from repro.core.vector_index import IVFIndex
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, 8)).astype(np.float32)
    idx = IVFIndex.build(vecs, cfg=VectorIndexConfig(
        dim=8, vectors_per_bucket=40, min_buckets=2, kmeans_iters=2),
        seed=seed)
    queries = rng.standard_normal((qn, 8)).astype(np.float32)
    v_b, i_b = idx.search_many(queries, k, nprobe)
    for qi in range(qn):
        v_1, i_1 = idx.search_many(queries[qi:qi + 1], k, nprobe)
        assert np.array_equal(i_b[qi], i_1[0])
        np.testing.assert_allclose(v_b[qi], v_1[0], rtol=1e-3, atol=1e-4)


# -- EmbeddingBag ragged == dense --------------------------------------------------

@settings(**SETTINGS)
@given(b=st.integers(1, 6), h=st.integers(1, 4), seed=st.integers(0, 999))
def test_embedding_bag_layout_equivalence(b, h, seed):
    from repro.models.recsys.embedding_bag import (
        embedding_bag_dense, embedding_bag_ragged)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((30, 4)), jnp.float32)
    ids2d = rng.integers(0, 30, (b, h))
    ragged = embedding_bag_ragged(
        table, jnp.asarray(ids2d.reshape(-1), jnp.int32),
        jnp.asarray(np.arange(b) * h, jnp.int32), b, mode="sum")
    dense = embedding_bag_dense(table[None],
                                jnp.asarray(ids2d[:, None, :]),
                                mode="sum")[:, 0]
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


# -- plan optimizer: any well-formed query graph converges + covers -----------------

_LABELS = ["A", "B", "C"]


@settings(**SETTINGS)
@given(
    n_nodes=st.integers(1, 4),
    n_edges=st.integers(0, 4),
    n_preds=st.integers(0, 3),
    seed=st.integers(0, 10_000),
)
def test_optimizer_always_covers(n_nodes, n_edges, n_preds, seed):
    from repro.core import logical_plan as lp
    from repro.core.cost_model import StatisticsService
    from repro.core.cypherplus import Compare, Literal, NodePattern, Prop, SubProp
    from repro.core.plan_optimizer import QueryEdge, QueryGraph, optimize
    rng = np.random.default_rng(seed)
    names = [f"v{i}" for i in range(n_nodes)]
    nodes = {v: NodePattern(v, _LABELS[i % 3]) for i, v in enumerate(names)}
    edges = []
    for _ in range(n_edges):
        a, b = rng.choice(names, 2)
        edges.append(QueryEdge(str(a), str(b), "knows", "out"))
    preds = []
    for i in range(n_preds):
        v = str(rng.choice(names))
        if i % 2:
            preds.append(Compare("=", Prop(v, "name"), Literal("x")))
        else:
            preds.append(Compare("=", SubProp(Prop(v, "photo"), "face"),
                                 Literal("y")))
    qg = QueryGraph(nodes, edges, preds)
    stats = StatisticsService()
    stats.n_nodes = 100
    stats.label_counts = {l: 30 for l in _LABELS}
    plan = optimize(qg, stats)
    assert set(names) <= set(plan.vars)
    applied = plan.applied
    assert applied == set(range(len(preds)))


# -- WAL: catch-up is idempotent + complete -------------------------------------------

@settings(**SETTINGS)
@given(n=st.integers(0, 20), start=st.integers(0, 20))
def test_wal_catchup_reaches_head(n, start):
    from repro.graphstore.wal import WriteAheadLog
    wal = WriteAheadLog()
    for i in range(n):
        wal.append(f"s{i}")
    start = min(start, n)
    executed = []
    v = wal.catch_up(start, executed.append)
    assert v == max(n, start) if start <= n else True
    assert len(executed) == n - start
    # second catch-up is a no-op
    executed2 = []
    v2 = wal.catch_up(v, executed2.append)
    assert v2 == v and executed2 == []


# -- gradient compression: error feedback is bounded ---------------------------------

@settings(**SETTINGS)
@given(seed=st.integers(0, 500))
def test_compression_error_feedback_unbiased(seed):
    from repro.training.compression import compress, decompress, init_error_feedback
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)}
    e = init_error_feedback(g)
    total_true = jnp.zeros((16, 16))
    total_deq = jnp.zeros((16, 16))
    for _ in range(8):
        q, s, e = compress(g, e)
        deq = decompress(q, s)
        total_true += g["w"]
        total_deq += deq["w"]
    # accumulated dequantized sum tracks the true sum within one quant step
    resid = np.abs(np.asarray(total_true - total_deq - e["w"])).max()
    assert resid < 1e-4


# -- $param binding + plan-cache skeleton keys (PR 2) -------------------------------

_PARAM_DB = None


def _param_db():
    """Lazily built tiny db shared across hypothesis examples."""
    global _PARAM_DB
    if _PARAM_DB is None:
        from repro.core import PandaDB
        db = PandaDB()
        for i in range(20):
            db.graph.create_node("Item", name=f"item_{i}", x=float(i))
        _PARAM_DB = db
    return _PARAM_DB


_SAFE_STR = st.text(
    alphabet=st.characters(whitelist_categories=("Ll", "Lu", "Nd"),
                           max_codepoint=127),
    min_size=0, max_size=8)


@settings(**SETTINGS)
@given(v=st.one_of(st.integers(-10**6, 10**6),
                   st.floats(min_value=0, max_value=1e6, allow_nan=False),
                   _SAFE_STR, st.booleans()))
def test_param_render_roundtrip(v):
    """Any scalar render_scalar claims to represent faithfully must re-parse
    to an equal literal (WAL replay = bind-time execution)."""
    from repro.core.cypherplus import Literal, parse_query
    from repro.core.session import render_scalar
    r = render_scalar(v)
    if r is None:
        return          # unrepresentable values keep their placeholder
    q = parse_query(f"MATCH (n:Item) WHERE n.x = {r} RETURN n.x")
    lit = q.where.right
    assert isinstance(lit, Literal)
    if isinstance(v, bool):
        assert lit.value is v
    elif isinstance(v, (int, float)):
        assert float(lit.value) == pytest.approx(float(v))
    else:
        assert lit.value == v


@settings(**SETTINGS)
@given(vals=st.lists(st.integers(0, 50), min_size=1, max_size=4),
       pad=st.integers(1, 4))
def test_same_skeleton_different_bindings_share_one_plan(vals, pad):
    """Whitespace variants of a $param query collapse to one skeleton, one
    plan-cache entry serves every binding, and each binding still filters
    correctly (late binding, not plan-time substitution)."""
    from repro.core.session import skeleton_of
    db = _param_db()
    base = "MATCH (n:Item) WHERE n.x < $lim RETURN n.name"
    spaced = base.replace(" ", " " * pad)
    assert skeleton_of(spaced) == skeleton_of(base)
    s = db.session()
    size0 = db.plan_cache.stats()["size"]
    for v in vals:
        rows = s.run(spaced, lim=v).fetchall()
        assert len(rows) == min(v, 20)      # binding applied per execution
    assert db.plan_cache.stats()["size"] - size0 <= 1


# -- merge_topk: permutation invariance -------------------------------------------------

@settings(**SETTINGS)
@given(seed=st.integers(0, 500), parts=st.integers(2, 5))
def test_merge_topk_permutation_invariant(seed, parts):
    from repro.core.vector_index import merge_topk
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.standard_normal((parts, 3, 4)), jnp.float32)
    i = jnp.asarray(rng.integers(0, 10_000, (parts, 3, 4)))
    v1, _ = merge_topk(v, i, 4)
    perm = rng.permutation(parts)
    v2, _ = merge_topk(v[perm], i[perm], 4)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)


# -- cascade routing: monotone in the threshold pair ---------------------------

@settings(**SETTINGS)
@given(
    seed=st.integers(0, 2**31 - 1),
    lo=st.floats(-1, 1),
    dhi=st.floats(0, 2),
    widen_lo=st.floats(0, 1),
    widen_hi=st.floats(0, 1),
)
def test_cascade_routing_monotone_in_band(seed, lo, dhi, widen_lo, widen_hi):
    """Widening [lo, hi] only moves items INTO escalation: no item ever
    flips accept <-> reject, and no new accepts/rejects appear."""
    from repro.core.cascade import route_scores
    rng = np.random.default_rng(seed)
    s = rng.uniform(-2, 2, 200)
    hi = lo + dhi
    acc1, rej1, esc1 = route_scores(s, lo, hi)
    acc2, rej2, esc2 = route_scores(s, lo - widen_lo, hi + widen_hi)
    assert not (acc2 & ~acc1).any()
    assert not (rej2 & ~rej1).any()
    assert not (acc2 & rej1).any() and not (rej2 & acc1).any()
    assert (esc1 & ~esc2).sum() == 0          # escalation set only grows
    # totality on both bands
    for a, r, e in ((acc1, rej1, esc1), (acc2, rej2, esc2)):
        assert (a.astype(int) + r.astype(int) + e.astype(int) == 1).all()


# -- ACCURACY 1.0 is a byte-identical bypass -----------------------------------

@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(8, 24),
    dup_every=st.integers(2, 6),
    seed=st.integers(0, 1000),
)
def test_accuracy_one_byte_identical_on_random_graphs(n, dup_every, seed):
    from repro.core import PandaDB
    from repro.core.aipm import feature_hash_extractor
    rng = np.random.default_rng(seed)
    base = rng.bytes(256)
    db = PandaDB()
    db.register_extractor("face", feature_hash_extractor(dim=16))
    db.register_proxy("face", feature_hash_extractor(dim=4, seed=99))
    for i in range(n):
        db.graph.create_node(
            "Person", name=f"n{i}",
            photo=base if i % dup_every == 0 else rng.bytes(256))
    q = ("MATCH (p:Person) WHERE p.photo->face ~: "
         "createFromSource($src)->face RETURN p.name")
    plain = db.query(q, {"src": base})
    assert db.query(q + " WITH ACCURACY 1.0", {"src": base}) == plain
    assert db.plan(q + " WITH ACCURACY 1.0") == db.plan(q)
