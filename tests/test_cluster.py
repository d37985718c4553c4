"""Sharded cluster subsystem: single-node parity + routing + edge cases.

The contract under test: a ``ShardedPandaDB`` fed the same creation order
as a single-node ``PandaDB`` returns BYTE-IDENTICAL ids (and exact
re-ranked scores) for kNN, semantic-filter, point-lookup and ``LIMIT``
queries at any shard count -- sharding is a serving-layer concern, never a
semantics change.
"""
import numpy as np
import pytest

from repro.configs.pandadb import VectorIndexConfig
from repro.core import PandaDB
from repro.core.aipm import feature_hash_extractor
from repro.core.cost_model import StatisticsService
from repro.core.vector_index import (
    IVFIndex,
    owner_shard,
    scan_topk,
    stable_id_hash,
)
from repro.cluster import ClusterUnsupportedQuery, ShardedPandaDB
from repro.data.synthetic_graph import sift_like_vectors

N_NODES = 72
DIM = 32


def _payloads(n=N_NODES, seed=3, dup_every=6):
    rng = np.random.default_rng(seed)
    base = rng.bytes(256)
    return base, [base if dup_every and i % dup_every == 0 else rng.bytes(256)
                  for i in range(n)]


#: duplicate photos every 6 nodes: semantic-filter queries get real matches
BASE, PAYLOADS = _payloads()
#: all-distinct photos: kNN parity asserts byte-identical top-k, which only
#: makes sense without exact score ties (tie order among equal scores is
#: arbitrary on BOTH topologies: global row order vs shard-merge order)
_, PAYLOADS_UNIQ = _payloads(seed=4, dup_every=0)


def _populate(db, payloads=PAYLOADS):
    """Same creation order on every topology (ids must align)."""
    db.register_extractor("face", feature_hash_extractor(dim=DIM))
    cn = db.create_node if isinstance(db, ShardedPandaDB) \
        else db.graph.create_node
    cr = db.create_relationship if isinstance(db, ShardedPandaDB) \
        else db.graph.create_relationship
    nodes = [cn("Person", name=f"n{i}", rank=float(i % 7),
                photo=payloads[i]) for i in range(N_NODES)]
    for i in range(N_NODES - 1):
        cr(nodes[i], nodes[i + 1], "KNOWS")
    return db


@pytest.fixture(scope="module")
def single():
    return _populate(PandaDB())


@pytest.fixture(scope="module")
def single_indexed():
    db = _populate(PandaDB())
    db.build_index("face", "photo")
    return db


@pytest.fixture(scope="module")
def single_knn():
    db = _populate(PandaDB(), PAYLOADS_UNIQ)
    db.build_index("face", "photo")
    return db


def make_cluster(n_shards, owner_fn=None, indexed=False, payloads=PAYLOADS):
    c = _populate(ShardedPandaDB(n_shards, owner_fn=owner_fn), payloads)
    if indexed:
        c.build_index("face", "photo")
    return c


SEM_Q = ("MATCH (p:Person) WHERE p.photo->face ~: "
         "createFromSource($src)->face RETURN p.name")


# -- sharded-vs-single-node parity -------------------------------------------


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_knn_parity(single_knn, n_shards):
    """Scatter-gather kNN: byte-identical ids + exact scores to the
    single-node index, probe and exact widths."""
    index = single_knn.indexes["face"]
    rng = np.random.default_rng(9)
    q = rng.standard_normal((6, DIM)).astype(np.float32)
    c = make_cluster(n_shards, indexed=True, payloads=PAYLOADS_UNIQ)
    for nprobe in (2, index.centroids.shape[0]):
        v_s, i_s = index.search_many(q, 5, nprobe=nprobe)
        v_c, i_c = c.knn("face", q, 5, nprobe=nprobe)
        assert np.array_equal(i_s, i_c), nprobe
        assert np.array_equal(v_s, v_c), nprobe
    c.close()


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_semantic_filter_parity(single, n_shards):
    """Fan-out semantic filter (no index): same rows, same global order."""
    rows_s = single.query(SEM_Q, {"src": BASE})
    assert rows_s                                  # duplicates exist
    c = make_cluster(n_shards)
    assert c.query(SEM_Q, {"src": BASE}) == rows_s
    c.close()


@pytest.mark.parametrize("n_shards", [2, 4])
def test_semantic_filter_pushdown_parity(single_indexed, n_shards):
    """Per-shard index pushdown: each shard's piece covers exactly its
    owned blobs, so the fan-out union equals the single-node pushdown."""
    rows_s = single_indexed.query(SEM_Q, {"src": BASE})
    c = make_cluster(n_shards, indexed=True)
    assert c.query(SEM_Q, {"src": BASE}) == rows_s
    c.close()


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_point_lookup_routed_parity(single, n_shards):
    c = make_cluster(n_shards)
    for nid in (0, 11, N_NODES - 1):
        rows_s = single.query("MATCH (p:Person) WHERE p = $id RETURN p.name",
                              {"id": nid})
        assert rows_s == [{"p.name": f"n{nid}"}]
        assert c.query("MATCH (p:Person) WHERE p = $id RETURN p.name",
                       {"id": nid}) == rows_s
    assert c.route_counts["routed"] == 3
    c.close()


def test_point_lookup_touches_owner_shard_only():
    c = make_cluster(4)
    nid = 11
    owner = c.owner_of(nid)
    before = [dict(sh.stats.counts) for sh in c.shards]
    c.query("MATCH (p:Person) WHERE p = $id RETURN p.name", {"id": nid})
    for s, sh in enumerate(c.shards):
        scanned = sh.stats.counts.get("nodebylabelscan", 0) \
            - before[s].get("nodebylabelscan", 0)
        assert (scanned > 0) == (s == owner), (s, owner, scanned)
    c.close()


@pytest.mark.parametrize("n_shards", [2, 4])
def test_limit_parity_and_order(single, n_shards):
    """Fan-out label scan with LIMIT: the ordered merge restores global
    row order, so prefixes are byte-identical."""
    c = make_cluster(n_shards)
    for n in (1, 7, N_NODES):
        rows_s = single.query(f"MATCH (p:Person) RETURN p.name LIMIT {n}")
        assert c.query(f"MATCH (p:Person) RETURN p.name LIMIT {n}") == rows_s
    c.close()


def test_limit_early_exit_cancels_phi():
    """LIMIT early exit flows through every shard's streaming pipeline:
    φ extraction stops far short of the corpus."""
    extracted = {"n": 0}
    base_fn = feature_hash_extractor(dim=DIM)

    def counting(raws):
        extracted["n"] += len(raws)
        return base_fn(raws)

    c = _populate(ShardedPandaDB(2))
    c.register_extractor("face", counting)
    with c.session(batch_rows=4) as s:
        rows = s.run(SEM_Q + " LIMIT 1", {"src": BASE}).fetchall()
    assert len(rows) == 1
    # 2 shards x a few 4-row chunks in flight, nowhere near all 72 blobs
    assert 0 < extracted["n"] < N_NODES // 2, extracted["n"]
    c.close()


@pytest.mark.parametrize("n_shards", [2, 4])
def test_parity_after_dynamic_insert(n_shards):
    """Insert-after-shard routing: new blobs land on their owner's index
    piece; search results stay byte-identical to single-node."""
    rng = np.random.default_rng(21)
    new_payloads = [rng.bytes(256) for _ in range(5)]

    sdb = _populate(PandaDB(), PAYLOADS_UNIQ)
    sdb.build_index("face", "photo")
    c = make_cluster(n_shards, indexed=True, payloads=PAYLOADS_UNIQ)
    for i, payload in enumerate(new_payloads):
        nid_s = sdb.graph.create_node("Person", name=f"x{i}", photo=payload)
        nid_c = c.create_node("Person", name=f"x{i}", photo=payload)
        assert nid_s == nid_c
        bid = sdb.graph.store.node_props.get(nid_s, "photo")
        sdb.index_insert("face", bid)
        c.index_insert("face", bid)
        # routed to the blob owner's piece, and only there
        owner = c._blob_owner[bid]
        assert bid in np.concatenate(
            [c.shards[owner].indexes["face"].ids,
             np.asarray(sum(c.shards[owner].indexes["face"]
                            ._pend_ids.values(), []), np.int64)])
    q = rng.standard_normal((4, DIM)).astype(np.float32)
    nprobe = sdb.indexes["face"].centroids.shape[0]
    v_s, i_s = sdb.indexes["face"].search_many(q, 8, nprobe=nprobe)
    v_c, i_c = c.knn("face", q, 8, nprobe=nprobe)
    assert np.array_equal(i_s, i_c)
    assert np.array_equal(v_s, v_c)
    # parity survives for the query path too (the fresh blob matches itself)
    rows_s = sdb.query(SEM_Q, {"src": new_payloads[0]})
    assert rows_s
    assert c.query(SEM_Q, {"src": new_payloads[0]}) == rows_s
    c.close()


# -- edge cases ---------------------------------------------------------------


def test_empty_shard():
    """Shards that own nothing scan nothing and contribute only padding."""
    everything_to_zero = lambda ids: np.zeros(len(np.asarray(ids)), np.int64)
    c = make_cluster(3, owner_fn=everything_to_zero, indexed=True)
    assert len(c.shards[1].graph.store.all_nodes()) == 0
    assert c.shards[1].indexes["face"].n_total == 0
    rows = c.query("MATCH (p:Person) RETURN p.name LIMIT 5")
    assert rows == [{"p.name": f"n{i}"} for i in range(5)]
    rng = np.random.default_rng(2)
    q = rng.standard_normal((3, DIM)).astype(np.float32)
    v, i = c.knn("face", q, 4, nprobe=c.shards[0].indexes["face"]
                 .centroids.shape[0])
    assert np.all(i >= 0) and np.all(np.isfinite(v))
    c.close()


def test_skewed_partition_matches_single(single):
    """All rows hashed to one shard: degenerate but still exact."""
    skew = lambda ids: np.full(len(np.asarray(ids)), 1, np.int64)
    c = make_cluster(2, owner_fn=skew)
    rows_s = single.query("MATCH (p:Person) WHERE p.rank > 4 RETURN p.name")
    assert c.query("MATCH (p:Person) WHERE p.rank > 4 RETURN p.name") \
        == rows_s
    c.close()


def test_unsupported_queries_raise():
    c = make_cluster(2)
    with pytest.raises(ClusterUnsupportedQuery):
        c.query("MATCH (a:Person)-[:KNOWS]->(b) RETURN b.name")   # remote prop
    with pytest.raises(ClusterUnsupportedQuery):
        c.query("MATCH (a:Person)<-[:KNOWS]-(b) WHERE a.name='n3' "
                "RETURN a.name")                                  # in-edges
    # out-expand returning only the neighbor's id is shard-local: allowed
    rows = c.query("MATCH (a:Person)-[:KNOWS]->(b) WHERE a.name='n3' "
                   "RETURN a.name, b")
    assert rows == [{"a.name": "n3", "b.__self__": 4}]
    c.close()


def test_create_node_rejects_blob_handles():
    """Blob handles point into one store; cluster blob ids must come from
    the coordinator's global sequence."""
    c = ShardedPandaDB(2)
    blob = c.shards[0].graph.blobs.create_from_source(b"x")
    with pytest.raises(TypeError):
        c.create_node("Person", photo=blob)
    c.close()


def test_create_from_source_keeps_mime():
    """Statement blobs carry the resolved mime to the owner shard, matching
    single-node metadata."""
    sdb = PandaDB()
    c = ShardedPandaDB(2)
    text = "CREATE (a:Doc {payload: createFromSource('http://example/x')})"
    sdb.query(text)
    with c.session() as s:
        s.run(text)
    bid = sdb.graph.store.node_props.get(0, "payload")
    owner = c.owner_of(0)
    assert c.shards[owner].graph.blobs.meta[bid].mime \
        == sdb.graph.blobs.meta[bid].mime == "application/x-url"
    c.close()


def test_create_statement_routed(single):
    """CREATE through the cluster session: replicated slots, owner payload,
    one leader-WAL statement, id parity with single-node."""
    c = make_cluster(2)
    sdb = _populate(PandaDB())
    for db in (sdb, c):
        with db.session() as s:
            s.run("CREATE (a:Person {name: 'zz', rank: 3})")
    rows_s = sdb.query("MATCH (p:Person) WHERE p.name='zz' RETURN p")
    rows_c = c.query("MATCH (p:Person) WHERE p.name='zz' RETURN p")
    assert rows_c == rows_s and rows_s[0]["p.__self__"] == N_NODES
    nid = rows_c[0]["p.__self__"]
    owner = c.owner_of(nid)
    for s, sh in enumerate(c.shards):
        assert sh.graph.store.n_nodes == N_NODES + 1      # slot replicated
        assert sh.graph.store.is_owned(nid) == (s == owner)
        assert (sh.graph.prop(nid, "name") == "zz") == (s == owner)
    assert any("zz" in stmt for _, stmt in c.wal.entries)
    c.close()


# -- IVFIndex.shard strategies ------------------------------------------------


def test_shard_hash_stable_under_reorder():
    """Hash membership keys on the external id: reordering rows (what a
    compaction does) must not move any id between shards -- the positional
    round-robin split does, which is exactly why it lost the default."""
    vecs = sift_like_vectors(600, dim=16, n_clusters=8, seed=0)
    ids = np.arange(600) * 7 + 3
    cfg = VectorIndexConfig(dim=16, vectors_per_bucket=100, min_buckets=4,
                            kmeans_iters=2)
    a = IVFIndex.build(vecs, ids=ids, cfg=cfg, seed=0)
    perm = np.random.default_rng(1).permutation(600)
    b = IVFIndex.build(vecs[perm], ids=ids[perm], cfg=cfg, seed=0)

    def membership(index, strategy):
        out = {}
        for s, piece in enumerate(index.shard(4, strategy=strategy)):
            for i in piece.ids:
                out[int(i)] = s
        return out

    assert membership(a, "hash") == membership(b, "hash")
    assert membership(a, "roundrobin") != membership(b, "roundrobin")
    # hash strategy == the documented owner function
    expect = owner_shard(ids, 4)
    got = membership(a, "hash")
    assert all(got[int(i)] == int(e) for i, e in zip(ids, expect))


def test_shard_explicit_assign_and_validation():
    vecs = sift_like_vectors(100, dim=16, n_clusters=4, seed=2)
    idx = IVFIndex.build(vecs, cfg=VectorIndexConfig(
        dim=16, vectors_per_bucket=50, min_buckets=2, kmeans_iters=1))
    assign = np.zeros(100, np.int64)
    assign[:10] = 1
    pieces = idx.shard(2, assign=assign)
    assert pieces[1].ids.shape[0] == 10
    assert sum(p.ids.shape[0] for p in pieces) == 100
    with pytest.raises(ValueError):
        idx.shard(2, assign=np.zeros(7, np.int64))
    with pytest.raises(ValueError):
        idx.shard(2, strategy="modulo")


def test_stable_id_hash_is_deterministic_and_spread():
    ids = np.arange(10_000)
    h1, h2 = stable_id_hash(ids), stable_id_hash(ids)
    assert np.array_equal(h1, h2)
    counts = np.bincount((h1 % 8).astype(np.int64), minlength=8)
    assert counts.min() > 10_000 / 8 * 0.8          # roughly balanced


# -- sharded ADC kNN through the shared merge path ---------------------------


def test_distributed_knn_adc_mode():
    """The cluster merge schedule serves PQ pieces: ADC top-k' + exact
    re-rank per piece, merged -- identical to the global float truth on a
    clustered corpus (re-rank recovers quantization)."""
    vecs = sift_like_vectors(1200, dim=DIM, n_clusters=12, seed=5)
    cfg = VectorIndexConfig(dim=DIM, vectors_per_bucket=1200, min_buckets=1,
                            kmeans_iters=1, pq_m=8, pq_bits=8,
                            pq_kmeans_iters=3, rerank_mult=16)
    index = IVFIndex.build(vecs, cfg=cfg, seed=0)
    import jax.numpy as jnp
    from repro.core.vector_index import scatter_gather_knn
    rng = np.random.default_rng(6)
    q = vecs[rng.choice(1200, 5)] + \
        rng.standard_normal((5, DIM)).astype(np.float32) * 0.01
    pieces = index.shard(4, strategy="roundrobin")
    v_g, i_g = scan_topk(jnp.asarray(q), jnp.asarray(index.vectors),
                         jnp.asarray(index.ids), 8, "l2")
    v_d, i_d = scatter_gather_knn(pieces, q, 8, mode="adc")
    assert np.array_equal(np.asarray(i_g), i_d)
    # scores to fp32 noise: the global truth uses the matmul-identity L2 on
    # device, the re-rank computes the difference form on host -- near-zero
    # distances keep ~1e-3 of cancellation noise on ~1e1 magnitudes
    np.testing.assert_allclose(np.asarray(v_g), v_d, rtol=1e-4, atol=5e-3)


# -- cost model: shard terms --------------------------------------------------


def test_shard_scan_ewma_and_fanout_cost():
    stats = StatisticsService()
    base = stats.shard_knn_fanout_cost([1000, 1000], m=8, nprobe=8, q=4)
    # fan-out wall time follows the SLOWEST shard: a 100x slower shard 1
    stats.record_shard_scan(1, 0.1, 1000)          # 1e-4 s/row
    slow = stats.shard_knn_fanout_cost([1000, 1000], m=8, nprobe=8, q=4)
    assert slow > base * 10
    assert stats.shard_scan_speed(1) == pytest.approx(1e-4)
    assert stats.shard_scan_speed(0) == stats.knn_scan_speed()  # fallback


def test_choose_shard_route_prefers_routed():
    stats = StatisticsService()
    cost = 1.0
    assert stats.choose_shard_route(cost, 4, routable=True) == "routed"
    assert stats.choose_shard_route(cost, 4, routable=False) == "fanout"
    # routed saves the P-1 extra dispatches fan-out pays
    assert stats.shard_routed_cost(cost, 4) < stats.shard_fanout_cost(cost, 4)


def test_coordinator_records_per_shard_ewmas(single_indexed):
    c = make_cluster(2, indexed=True)
    q = np.random.default_rng(0).standard_normal((4, DIM)).astype(np.float32)
    c.knn("face", q, 5)
    assert any(k.startswith("shard") for k in c.stats.speeds)
    assert c.knn_fanout_cost("face", q=4, k=5) > 0
    c.close()


# -- serving ------------------------------------------------------------------


def test_query_server_over_cluster():
    from repro.serving.engine import QueryServer
    c = make_cluster(2, indexed=True)
    server = QueryServer(c, n_workers=2)
    queries = [
        ("MATCH (p:Person) WHERE p = $id RETURN p.name", {"id": 5}),
        "MATCH (p:Person) RETURN p.name LIMIT 3",
    ]
    stats = server.run_closed_loop(queries, n_clients=2, duration_s=0.4)
    assert stats.summary()["requests"] > 0
    counts = server.route_counts()
    assert counts.get("routed", 0) > 0 and counts.get("fanout", 0) > 0
    # the shared plan cache served every worker: hits dominate misses
    pc = c.plan_cache.stats()
    assert pc["hits"] > pc["misses"]
    c.close()


def test_shared_plan_cache_across_shards():
    c = make_cluster(4)
    text = "MATCH (p:Person) WHERE p.rank > $r RETURN p.name"
    with c.session() as s:
        stmt = s.prepare(text)
        stmt.run(r=2).fetchall()
        m0 = c.plan_cache.stats()["misses"]
        stmt.run(r=5).fetchall()            # same skeleton, new binding
        stmt.run(r=1).fetchall()
    pc = c.plan_cache.stats()
    assert pc["misses"] == m0                # one optimize for the cluster
    assert pc["hits"] >= 2
    c.close()


# -- ordered_merge / close_streams edge cases ---------------------------------


def _stream(ids_rows):
    for ids, rows in ids_rows:
        yield np.asarray(ids, np.int64), rows


def _merge_all(streams, **kw):
    from repro.cluster import ordered_merge
    return [r for batch in ordered_merge(streams, **kw) for r in batch]


def test_ordered_merge_empty_shards():
    """Shards contributing nothing (no streams, empty streams, streams of
    empty batches) never stall or corrupt the merge."""
    assert _merge_all([]) == []
    assert _merge_all([_stream([])]) == []
    assert _merge_all([_stream([([], [])])]) == []
    got = _merge_all([_stream([]),
                      _stream([([2, 5], [{"i": 2}, {"i": 5}])]),
                      _stream([([], []), ([3], [{"i": 3}])])])
    assert got == [{"i": 2}, {"i": 3}, {"i": 5}]


def test_ordered_merge_all_equal_keys_tie_order():
    """Equal anchor ids (impossible under disjoint ownership, but the
    merge must still be deterministic): lower stream index drains first."""
    got = _merge_all([_stream([([7, 7], [{"s": 0, "j": 0}, {"s": 0, "j": 1}])]),
                      _stream([([7], [{"s": 1, "j": 0}])])])
    assert got == [{"s": 0, "j": 0}, {"s": 0, "j": 1}, {"s": 1, "j": 0}]


def test_ordered_merge_property_sorted_concat():
    """Property: for disjoint non-decreasing per-shard streams, the merge
    equals the sorted concatenation, under any per-shard LIMIT cap."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.integers(0, 200), unique=True, max_size=60),
           st.integers(1, 4), st.integers(1, 5), st.integers(0, 1),
           st.data())
    def check(ids, n_shards, chunk, use_limit, data):
        parts = [[] for _ in range(n_shards)]
        for i in sorted(ids):
            parts[data.draw(st.integers(0, n_shards - 1))].append(i)
        limit = (data.draw(st.integers(0, len(ids) + 2))
                 if use_limit else None)
        streams = []
        for p in parts:
            capped = p if limit is None else p[:limit]   # per-shard cap
            batches = [(capped[o:o + chunk],
                        [{"i": v} for v in capped[o:o + chunk]])
                       for o in range(0, len(capped), chunk)]
            streams.append(_stream(batches))
        got = [r["i"] for r in _merge_all(streams, batch_rows=3,
                                          limit=limit)]
        want = sorted(ids)
        if limit is not None:
            want = want[:limit]
        assert got == want

    check()


def test_close_streams_visits_all_and_reraises():
    """A stream whose close() raises must not stop the teardown of the
    others; the first error resurfaces."""
    from repro.cluster import close_streams
    closed = []

    class S:
        def __init__(self, i, err=False):
            self.i, self.err = i, err

        def close(self):
            closed.append(self.i)
            if self.err:
                raise RuntimeError(f"close {self.i}")

    with pytest.raises(RuntimeError, match="close 1"):
        close_streams([S(0), S(1, err=True), S(2, err=True)])
    assert closed == [0, 1, 2]


def test_session_close_closes_open_cursors():
    """An abandoned mid-iteration cursor is torn down by session close."""
    c = make_cluster(2)
    with c.session(batch_rows=4) as s:
        cur1 = s.run("MATCH (p:Person) RETURN p.name")
        cur2 = s.run("MATCH (p:Person) WHERE p.rank > 2 RETURN p.name")
        assert cur1.fetchone() is not None
        assert cur2.fetchone() is not None
    assert cur1._closed and cur2._closed
    # re-closing is a no-op, not an error
    cur1.close()
    c.close()


def test_ordered_merge_property_seeded_fallback():
    """Same property as above on 80 seeded random cases -- runs even where
    hypothesis is not installed."""
    rng = np.random.default_rng(42)
    for _ in range(80):
        ids = sorted(rng.choice(200, size=rng.integers(0, 50),
                                replace=False).tolist())
        n_shards = int(rng.integers(1, 5))
        chunk = int(rng.integers(1, 6))
        limit = int(rng.integers(0, len(ids) + 2)) \
            if rng.random() < 0.5 else None
        parts = [[] for _ in range(n_shards)]
        for i in ids:
            parts[int(rng.integers(0, n_shards))].append(i)
        streams = []
        for p in parts:
            capped = p if limit is None else p[:limit]
            streams.append(_stream(
                [(capped[o:o + chunk],
                  [{"i": v} for v in capped[o:o + chunk]])
                 for o in range(0, len(capped), chunk)]))
        got = [r["i"] for r in _merge_all(streams, batch_rows=3,
                                          limit=limit)]
        assert got == (ids if limit is None else ids[:limit])


# -- merge padding contract (device-side k-way merge) -------------------------


def test_scatter_gather_padding_contract_starved_shards():
    """The retire/recovery shape: shards holding FEWER than k rows each
    (one completely empty).  The merged output must satisfy the padding
    invariant -- id=-1 exactly where val=-inf, never a -1 with a finite
    score and never a real id past the real candidate count."""
    from repro.core.vector_index import scatter_gather_knn

    rng = np.random.default_rng(21)
    qs = rng.standard_normal((5, 8)).astype(np.float32)
    rows = rng.standard_normal((5, 8)).astype(np.float32)
    cfg = VectorIndexConfig(dim=8, metric="l2", min_buckets=1,
                            vectors_per_bucket=5, nprobe=1)
    index = IVFIndex.build(rows, ids=np.arange(10, 15), cfg=cfg)
    # ids 10, 11 on piece 0, none on piece 1, ids 12-14 on piece 2
    shards = index.shard(3, assign=np.where(index.ids < 12, 0, 2))
    assert [s.n_total for s in shards] == [2, 0, 3]
    k = 10                                   # > 5 total real rows
    v, i = scatter_gather_knn(shards, qs, k)
    assert v.shape == (5, k) and i.shape == (5, k)
    assert np.array_equal(i == -1, ~np.isfinite(v))
    assert np.isfinite(v[:, :5]).all() and (i[:, :5] >= 10).all()
    assert (i[:, 5:] == -1).all() and np.isinf(v[:, 5:]).all()
    # the merged head is the true exact top-5 of the union
    allv, alli = np.concatenate([rows[:2], rows[2:]]), np.arange(10, 15)
    s = -((qs[:, None, :] - allv[None]) ** 2).sum(-1)
    want = np.argsort(-s, axis=1, kind="stable")
    assert np.array_equal(i[:, :5], alli[want])


def test_cluster_knn_fused_mode_passthrough():
    """mode="fused" rides the coordinator path end-to-end (knn ->
    scatter_gather_knn -> each shard's search_many) and stays
    byte-identical to the staged ADC scan."""
    cfg = VectorIndexConfig(dim=DIM, metric="l2", vectors_per_bucket=16,
                            min_buckets=4, nprobe=4, pq_m=8,
                            pq_residual=True)
    c = make_cluster(2, payloads=PAYLOADS_UNIQ)
    c.build_index("face", "photo", cfg=cfg)
    for piece in c.index_pieces("face"):
        assert piece.cfg.pq_residual and piece.code_bias is not None
    rng = np.random.default_rng(17)
    q = rng.standard_normal((6, DIM)).astype(np.float32)
    v_a, i_a = c.knn("face", q, 5, mode="adc")
    v_f, i_f = c.knn("face", q, 5, mode="fused")
    assert np.array_equal(i_a, i_f)
    assert np.array_equal(v_a, v_f)   # exact re-ranked scores merge exactly
    c.close()
