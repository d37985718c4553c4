"""Filtered kNN: the row-masked ADC scan against its numpy oracle, and
``search_many`` / ``ShardedPandaDB.knn`` with per-query filters against a
float64 brute force over each query's allowed rows, on every path a filter
can take."""
import numpy as np
import pytest

from repro.configs.pandadb import PandaDBConfig, VectorIndexConfig
from repro.core.scalar_index import InvertedIndex
from repro.core.vector_index import IVFIndex
from repro.kernels.dispatch import METRICS, dispatch_counts
from repro.kernels.pq_scan import (pack_row_mask, pq_adc_topk,
                                   pq_adc_topk_ref, row_mask_from_bitmaps)
from repro.kernels.pq_scan.pq_scan import unpack_row_mask


# -- the row-masked kernel and its twin against ref.py ------------------------


def _rowmask_inputs(case, qn, m, n=1400, ksub=64, mb=24):
    rng = np.random.default_rng([qn, m, len(case)])
    if case == "ties":
        luts = rng.integers(-4, 4, (qn, m, ksub)).astype(np.float32)
        bias = rng.integers(-2, 2, n).astype(np.float32)
        cs = rng.integers(-3, 3, (qn, mb)).astype(np.float32)
    else:
        luts = rng.standard_normal((qn, m, ksub)).astype(np.float32)
        bias = rng.standard_normal(n).astype(np.float32)
        cs = rng.standard_normal((qn, mb)).astype(np.float32)
    codes = rng.integers(0, ksub, (n, m)).astype(np.int32)
    rb = np.sort(rng.integers(0, mb, n)).astype(np.int32)
    allow = rng.random((qn, n)) < 0.3
    allow[:, 512:1024] = False                 # a whole tile masked
    allow[0] = False
    allow[0, [3, 200, 1100, 1399]] = True      # fewer rows than k'
    return luts, codes, bias, rb, cs, allow


@pytest.mark.parametrize("case", ["float", "ties"])
@pytest.mark.parametrize("qn", [5, 13])
@pytest.mark.parametrize("m", [16, 24])
@pytest.mark.parametrize("force_pallas", [False, True])
def test_rowmask_scan_matches_ref(case, qn, m, force_pallas):
    """Every masked row stays out, a tile with every row masked yields
    nothing, a query allowed four rows returns them then (-inf, -1), and
    exact ties come back lower row first -- Pallas (interpret) and the XLA
    twin alike, at Q not a multiple of 8."""
    luts, codes, bias, rb, cs, allow = _rowmask_inputs(case, qn, m)
    k = 80
    mask = pack_row_mask([np.nonzero(a)[0] for a in allow], len(codes))
    before = dispatch_counts()
    v, i = pq_adc_topk(luts, codes, k, bias=bias, row_bucket=rb, cscores=cs,
                       row_mask=mask, force_pallas=force_pallas)
    impl = "pallas" if force_pallas else "xla"
    assert (dispatch_counts()[f"pq_scan_rowmask:{impl}"]
            == before.get(f"pq_scan_rowmask:{impl}", 0) + 1)
    v2, i2 = pq_adc_topk_ref(luts, codes, k, bias=bias, row_bucket=rb,
                             cscores=cs, row_mask=allow)
    if case == "ties":
        assert np.array_equal(v, v2)
        fin = v2[np.isfinite(v2)]
        assert len(np.unique(fin)) < len(fin)
    else:
        np.testing.assert_allclose(v, v2, rtol=1e-5, atol=1e-5)
    assert np.array_equal(i, i2)
    assert sorted(i[0, :4]) == [3, 200, 1100, 1399]
    assert (i[0, 4:] == -1).all() and np.isinf(v[0, 4:]).all()
    ok = i >= 0
    assert allow[np.nonzero(ok)[0], i[ok]].all()
    assert not ((i >= 512) & (i < 1024)).any()


def test_pack_row_mask_round_trips():
    rng = np.random.default_rng(3)
    for qn in (1, 7, 32, 33, 70):
        allow = rng.random((qn, 700)) < 0.4
        words = pack_row_mask([np.nonzero(a)[0] for a in allow], 700)
        assert words.dtype == np.int32 and words.shape[1] == 1024
        back = np.asarray(unpack_row_mask(words, qn))
        assert np.array_equal(back[:, :700], allow)
        assert not back[:, 700:].any()


def test_pack_row_mask_repeated_positions_set_one_bit():
    """A position given twice sets its query's bit once: it neither moves
    the row to the next query nor, at bit 31, overflows the word."""
    rows = [np.array([q, q, 600, q]) for q in range(33)]
    words = pack_row_mask(rows, 700)
    back = np.asarray(unpack_row_mask(words, 33))
    want = np.zeros_like(back)
    for q, r in enumerate(rows):
        want[q, r] = True
    assert np.array_equal(back, want)


def test_rowmask_excludes_probe_mask():
    luts, codes, bias, rb, cs, allow = _rowmask_inputs("float", 2, 8)
    mask = pack_row_mask([np.nonzero(a)[0] for a in allow], len(codes))
    with pytest.raises(ValueError, match="exclude"):
        pq_adc_topk(luts, codes, 5, row_bucket=rb, row_mask=mask,
                    probe_mask=np.ones((2, 24), bool))


# -- search_many: every path a filter can take ---------------------------------

N, D, K = 2400, 32, 10


def _ids_to_rows(ids):
    return (np.asarray(ids) - 7) // 3


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(0)
    vecs = rng.integers(0, 64, (N, D)).astype(np.float32)
    return vecs, np.arange(N) * 3 + 7            # sparse external ids


def _index(corpus, pq=True):
    vecs, ids = corpus
    cfg = VectorIndexConfig(dim=D, metric="l2", vectors_per_bucket=60,
                            nprobe=4, pq_m=8 if pq else 0, pq_bits=8,
                            pq_residual=True, rerank_mult=8)
    return IVFIndex.build(vecs, ids=ids, cfg=cfg)


def _filters(ids, qn, seed=1):
    rng = np.random.default_rng(seed)
    sizes = [3, 9, 40, 400, 1200] * qn
    return [np.sort(rng.choice(ids, size=sizes[j], replace=False))
            for j in range(qn)]


def _brute(vecs, ids, queries, allow, k=K):
    """float64 top-k over each query's allowed rows, ties to the lower
    external id (rows follow id order here)."""
    out_d, out_i = [], []
    for q, a in zip(queries, allow):
        r = _ids_to_rows(a)
        d = ((vecs[r].astype(np.float64) - q) ** 2).sum(1)
        top = np.lexsort((r, d))[:k]
        out_d.append(d[top])
        out_i.append(np.asarray(a)[top])
    return out_d, out_i


def _check(vecs, ids, queries, allow, v, i, exact):
    ref_d, ref_i = _brute(vecs, ids, queries, allow)
    hits = total = 0
    for q, a, vv, ii, rd, ri in zip(queries, allow, v, i, ref_d, ref_i):
        got = ii[ii >= 0]
        assert np.isin(got, a).all()                  # never outside
        assert len(got) == len(ri)                    # no slot missing
        assert (ii[len(ri):] == -1).all()
        d = ((vecs[_ids_to_rows(got)].astype(np.float64) - q) ** 2).sum(1)
        np.testing.assert_allclose(-vv[:len(got)], d, rtol=1e-6)
        if exact:
            assert np.array_equal(got, ri)
        hits += len(set(got.tolist()) & set(ri.tolist()))
        total += len(ri)
    assert hits / total >= 0.9


@pytest.mark.parametrize("mode", ["fused", "auto", "adc"])
def test_filtered_batch_takes_the_rowmask_scan(corpus, mode):
    vecs, ids = corpus
    ix = _index(corpus)
    q = np.random.default_rng(2).integers(0, 64, (10, D)).astype(np.float32)
    allow = _filters(ids, 10)
    before = dispatch_counts()
    v, i = ix.search_many(q, K, allow=allow, mode=mode)
    after = dispatch_counts()
    assert after["pq_scan_rowmask:xla"] == \
        before.get("pq_scan_rowmask:xla", 0) + 1
    assert after.get("pq_scan_ext:xla", 0) == before.get("pq_scan_ext:xla", 0)
    _check(vecs, ids, q, allow, v, i, exact=False)
    # queries allowed no more rows than k' are answered exactly
    small = [j for j, a in enumerate(allow) if len(a) <= 80]
    _check(vecs, ids, q[small], [allow[j] for j in small], v[small],
           i[small], exact=True)


def _host_queries():
    return METRICS.counter("knn_filter_host_queries").value


def test_single_filtered_query_is_exact_on_the_host(corpus):
    vecs, ids = corpus
    ix = _index(corpus)
    q = np.random.default_rng(4).integers(0, 64, (1, D)).astype(np.float32)
    allow = _filters(ids, 4, seed=4)[3:4]
    h0, d0 = _host_queries(), dispatch_counts()
    v, i = ix.search_many(q, K, allow=allow)
    assert _host_queries() == h0 + 1
    assert dispatch_counts() == d0                    # no device scan
    _check(vecs, ids, q, allow, v, i, exact=True)


def test_pending_appends_filter_exactly_on_the_host(corpus):
    vecs, ids = corpus
    ix = _index(corpus)
    rng = np.random.default_rng(5)
    extra = rng.integers(0, 64, (30, D)).astype(np.float32)
    extra_ids = 3 * (N + np.arange(30)) + 7
    ix.insert_many(extra, extra_ids)
    assert ix.pending_count == 30
    allv = np.concatenate([vecs, extra])
    allid = np.concatenate([ids, extra_ids])
    q = rng.integers(0, 64, (6, D)).astype(np.float32)
    allow = _filters(allid, 6, seed=6)
    allow[0] = np.sort(np.concatenate([allow[0], extra_ids[:5]]))
    h0 = _host_queries()
    v, i = ix.search_many(q, K, allow=allow, mode="fused")
    assert _host_queries() == h0 + 6
    _check(allv, allid, q, allow, v, i, exact=True)
    assert np.isin(extra_ids[:5], i[0]).any() or len(allow[0]) > K


def test_flat_index_filters_on_the_host(corpus):
    vecs, ids = corpus
    ix = _index(corpus, pq=False)
    q = np.random.default_rng(7).integers(0, 64, (4, D)).astype(np.float32)
    allow = _filters(ids, 4, seed=7)
    h0 = _host_queries()
    v, i = ix.search_many(q, K, allow=allow)
    assert _host_queries() == h0 + 4
    _check(vecs, ids, q, allow, v, i, exact=True)


def test_unfiltered_call_unchanged(corpus):
    """A filtered search leaves nothing behind that moves an unfiltered
    one: the same answers before and after, through the fused ADC scan
    (``pq_scan_ext``), equal to the staged ADC path."""
    ix = _index(corpus)
    q = np.random.default_rng(8).integers(0, 64, (12, D)).astype(np.float32)
    d0 = dispatch_counts()
    v1, i1 = ix.search_many(q, K, mode="fused")
    d1 = dispatch_counts()
    assert d1["pq_scan_ext:xla"] == d0.get("pq_scan_ext:xla", 0) + 1
    assert d1.get("pq_scan_rowmask:xla", 0) == \
        d0.get("pq_scan_rowmask:xla", 0)
    ix.search_many(q, K, allow=_filters(corpus[1], 12))
    v2, i2 = ix.search_many(q, K, mode="fused")
    v3, i3 = ix.search_many(q, K, mode="adc")
    assert np.array_equal(i1, i2) and np.array_equal(v1, v2)
    assert np.array_equal(i1, i3)
    np.testing.assert_allclose(v1, v3, rtol=1e-6)


def test_ids_without_rows_are_dropped(corpus):
    vecs, ids = corpus
    ix = _index(corpus)
    q = np.random.default_rng(9).integers(0, 64, (3, D)).astype(np.float32)
    allow = [np.array([ids[5], 1, 2]), np.array([], np.int64),
             np.array([ids[9], 10 ** 9])]
    v, i = ix.search_many(q, K, allow=allow)
    assert i[0, 0] == ids[5] and (i[0, 1:] == -1).all()
    assert (i[1] == -1).all() and np.isinf(v[1]).all()
    assert i[2, 0] == ids[9] and (i[2, 1:] == -1).all()


@pytest.mark.parametrize("path", ["fused", "host"])
def test_repeated_allowed_ids_count_once(corpus, path):
    """Each allowed id given twice, out of order, gives the answers of
    the plain filter on the row-masked scan and on the host path."""
    vecs, ids = corpus
    ix = _index(corpus)
    qn = 10 if path == "fused" else 1
    q = np.random.default_rng(11).integers(0, 64, (qn, D)).astype(np.float32)
    allow = _filters(ids, qn, seed=11)
    twice = [np.concatenate([a[::-1], a]) for a in allow]
    h0 = _host_queries()
    v1, i1 = ix.search_many(q, K, allow=allow)
    v2, i2 = ix.search_many(q, K, allow=twice)
    assert _host_queries() == h0 + (0 if path == "fused" else 2 * qn)
    assert np.array_equal(i1, i2) and np.array_equal(v1, v2)
    _check(vecs, ids, q, allow, v2, i2, exact=path == "host")


@pytest.mark.parametrize("mode", ["fused", "float"])
def test_sparse_ids_take_the_sorted_lookup(corpus, mode):
    """Ids too sparse for a dense table are found by binary search over
    the sorted ids, with the answers of the same index under dense ids."""
    vecs, ids = corpus
    wide = (ids - 7) // 3 * 1_000_003 + 7
    dense_ix, sparse_ix = _index(corpus), _index((vecs, wide))
    q = np.random.default_rng(12).integers(0, 64, (6, D)).astype(np.float32)
    allow = _filters(ids, 6, seed=12)
    allow_wide = [(a - 7) // 3 * 1_000_003 + 7 for a in allow]
    allow_wide[0] = np.concatenate([allow_wide[0], [5, 10 ** 15]])  # no row
    v1, i1 = dense_ix.search_many(q, K, allow=allow, mode=mode)
    v2, i2 = sparse_ix.search_many(q, K, allow=allow_wide, mode=mode)
    assert len(dense_ix._row_lookup) == 1 and len(sparse_ix._row_lookup) == 2
    assert np.array_equal(np.where(i2 >= 0, (i2 - 7) // 1_000_003 * 3 + 7,
                                   -1), i1)
    assert np.array_equal(v1, v2)


def test_filter_count_must_match_queries(corpus):
    ix = _index(corpus)
    with pytest.raises(ValueError, match="filters"):
        ix.search_many(np.zeros((2, D), np.float32), K,
                       allow=[np.array([7])])


# -- the coordinator: tags resolved by the inverted index ----------------------


def _tag_db(n_shards, vecs, tags):
    from repro.cluster import ShardedPandaDB
    cfg = PandaDBConfig(index=VectorIndexConfig(
        dim=D, metric="l2", vectors_per_bucket=60, nprobe=4, pq_m=8,
        pq_bits=8, pq_residual=True, rerank_mult=8))
    db = ShardedPandaDB(n_shards, cfg=cfg)
    db.register_extractor(
        "emb", lambda raws: np.stack([np.asarray(r, np.uint8)[:D].astype(
            np.float32) for r in raws]))
    db.register_extractor(
        "tags", lambda raws: [np.asarray(r, np.uint8)[D:].tobytes().decode()
                              for r in raws])
    for v, t in zip(vecs, tags):
        db.create_node("Image", photo=np.asarray(v, np.uint8).tobytes()
                       + " ".join(t).encode())
    db.build_index("emb", "photo")
    db.build_scalar_index("tags", "photo")
    return db


@pytest.fixture(scope="module")
def tagged():
    rng = np.random.default_rng(11)
    n = 1500
    vecs = rng.integers(0, 64, (n, D)).astype(np.float32)
    words = [f"t{j}" for j in range(12)]
    tags = [list(rng.choice(words, size=rng.integers(1, 5), replace=False))
            for _ in range(n)]
    return vecs, tags


@pytest.mark.parametrize("n_shards", [1, 2])
def test_coordinator_knn_with_tag_filters(tagged, n_shards):
    """Each query ANDs one or two tags; the answers hold only images that
    carry them, and match a float64 brute force and a single node."""
    vecs, tags = tagged
    db = _tag_db(n_shards, vecs, tags)
    one = _tag_db(1, vecs, tags) if n_shards > 1 else db
    rng = np.random.default_rng(12)
    q = rng.integers(0, 64, (8, D)).astype(np.float32)
    filters = [("t0",), ("t1", "t2"), ("t3",), ("t4", "t5"), ("t6",),
               ("T7", "t8"), ("t9",), ("t10", "t11")]
    v, i = db.knn("emb", q, K, mode="fused", filters=filters,
                  filter_key="tags")
    allow = [np.array([b for b, t in enumerate(tags)
                       if {w.lower() for w in f} <= set(t)])
             for f in filters]
    ref_i = []
    for qq, a in zip(q, allow):
        d = ((vecs[a].astype(np.float64) - qq) ** 2).sum(1)
        ref_i.append(a[np.lexsort((a, d))[:K]])
    hits = total = 0
    for qq, vv, ii, a, ri in zip(q, v, i, allow, ref_i):
        got = ii[ii >= 0]
        assert np.isin(got, a).all() and len(got) == len(ri)
        d = ((vecs[got].astype(np.float64) - qq) ** 2).sum(1)
        np.testing.assert_allclose(-vv[:len(got)], d, rtol=1e-6)
        hits += len(set(got.tolist()) & set(ri.tolist()))
        total += len(ri)
    assert hits / total >= 0.95
    # where every shard re-ranks all its allowed rows, the answer is exact
    # and the same as one node's
    v1, i1 = one.knn("emb", q, K, mode="fused", filters=filters,
                     filter_key="tags")
    for j, a in enumerate(allow):
        if len(a) <= 32:
            assert np.array_equal(i[j], i1[j])
            assert np.array_equal(i[j][i[j] >= 0], ref_i[j])
    with pytest.raises(ValueError, match="token"):
        db.knn("emb", q[:1], K, filters=[()], filter_key="tags")
    with pytest.raises(KeyError):
        db.knn("emb", q[:1], K, filters=[("t0",)], filter_key="nope")


# -- row bitmaps: the device-built mask, the cache, the coordinator's ANDs -----


def _bare_index(n, seed=0):
    """A compacted index of ``n`` rows, sparse ids in shuffled row order;
    only the id -> row layout matters to the filter."""
    ids = np.random.default_rng(seed).permutation(n) * 3 + 7
    return IVFIndex(VectorIndexConfig(dim=1), np.zeros((4, 1), np.float32),
                    np.zeros(n, np.int32), np.zeros((n, 1), np.float32), ids)


@pytest.mark.parametrize("qn", [1, 31, 32, 33, 256])
def test_device_row_mask_matches_pack_row_mask(qn):
    """Each query's row bitmap, transposed on the device, is bit for bit
    ``pack_row_mask`` of the same row sets: empty filters, ids with no
    row, the last real row, one-term and two-term ANDs of id arrays and of
    postings long enough to be cached; no bit past the last real row."""
    from repro.core.scalar_index import Posting
    from repro.kernels.dispatch import padded_queries, padded_rows
    n = 1100
    ix = _bare_index(n)
    rows = padded_rows(n, ix.cfg.block_n)
    row_of = {int(i): r for r, i in enumerate(ix.ids)}
    last = int(ix.ids[-1])
    rng = np.random.default_rng(qn)
    big = [Posting(np.sort(rng.choice(ix.ids, 600, replace=False)),
                   (-1, 0), f"big{j}") for j in range(3)]
    allow, want = [], []
    for q in range(qn):
        kind = q % 6
        a = np.append(rng.choice(ix.ids, rng.integers(0, 300)), [last, 10**9])
        b = rng.choice(ix.ids, 500)
        if kind == 0:
            f = np.zeros(0, np.int64)
        elif kind == 1:
            f = a
        elif kind == 2:
            f = (a, b)
        elif kind == 3:
            f = big[q % 3]
        elif kind == 4:
            f = (big[q % 3], a)
        else:
            f = (big[0], big[1], b)
        terms = f if isinstance(f, tuple) else (f,)
        sets = [{row_of[int(i)] for i in (t.ids if isinstance(t, Posting)
                                           else t) if int(i) in row_of}
                for t in terms]
        allow.append(f)
        want.append(np.array(sorted(set.intersection(*sets)), np.int64))
    words, _, _ = ix._filter_words(allow, padded_queries(qn))
    assert words.shape == (padded_queries(qn), rows // 32)
    assert words.dtype == np.uint32
    assert np.array_equal(np.asarray(row_mask_from_bitmaps(words)),
                          pack_row_mask(want, n))
    bits = np.unpackbits(words.view(np.uint8), axis=1, bitorder="little")
    assert not bits[:, n:].any() and not bits[qn:].any()
    assert int(np.bitwise_count(words).sum()) == sum(map(len, want))


def _tag_index(ids, sizes, seed=0):
    """An inverted index whose tag ``s<k>`` is on ``k`` ids drawn from
    ``ids``."""
    rng = np.random.default_rng(seed)
    return InvertedIndex({f"s{k}": np.sort(rng.choice(ids, k, replace=False))
                          for k in sizes})


def _bitmap_counts():
    return (METRICS.counter("knn_filter_bitmap:cached").value,
            METRICS.counter("knn_filter_bitmap:built").value)


def test_postings_at_the_threshold_cache_or_build_alike(corpus):
    """A posting of padded_rows / 64 ids keeps its bitmap, one id fewer is
    built every call; both answer as the same filter given as ids."""
    from repro.kernels.dispatch import padded_rows
    vecs, ids = corpus
    ix = _index(corpus)
    edge = padded_rows(len(ix.ids), ix.cfg.block_n) // 64
    inv = _tag_index(ids, [edge, edge - 1], seed=21)
    q = np.random.default_rng(22).integers(0, 64, (8, D)).astype(np.float32)
    tags = [f"s{edge}", f"s{edge - 1}"] * 4
    as_ids = [inv.lookup(t) for t in tags]
    runs = []
    for _ in range(2):
        c0, b0 = _bitmap_counts()
        runs.append(ix.search_many(q, K, allow=[inv.posting(t)
                                                for t in tags]))
        c1, b1 = _bitmap_counts()
        runs[-1] += (c1 - c0, b1 - b0)
    assert runs[0][2:] == (0, 2) and runs[1][2:] == (1, 1)
    v, i = ix.search_many(q, K, allow=as_ids)
    for rv, ri, _, _ in runs:
        assert np.array_equal(ri, i) and np.array_equal(rv, v)
    _check(vecs, ids, q, as_ids, v, i, exact=False)


def test_index_insert_is_seen_by_a_cached_bitmap(corpus):
    """A tag whose bitmap is cached, then given to one more node through
    ``InvertedIndex.insert``: the next filtered answer holds that node."""
    vecs, ids = corpus
    ix = _index(corpus)
    inv = _tag_index(ids[1:], [600], seed=23)
    new = int(ids[0])
    assert new not in inv.lookup("s600")
    q = np.repeat(vecs[:1], 4, axis=0)
    _, i = ix.search_many(q, K, allow=[inv.posting("s600")] * 4)
    assert new not in i
    c0, _ = _bitmap_counts()
    inv.insert("s600 other", new)
    _, i = ix.search_many(q, K, allow=[inv.posting("s600")] * 4)
    assert _bitmap_counts()[0] == c0            # the old bitmap unused
    assert (i[:, 0] == new).all()
    _check(vecs, ids, q, [inv.lookup("s600")] * 4, *ix.search_many(
        q, K, allow=[inv.posting("s600")] * 4), exact=False)


def test_compaction_keeps_cached_filters_exact(corpus):
    """Rows appended and compacted move every row a cached bitmap named:
    answers with pending rows (the host path) and after the compaction
    (the row-masked scan) stay inside the filter, with exact distances,
    and see the new rows."""
    vecs, ids = corpus
    ix = _index(corpus)
    inv = _tag_index(ids, [900, 30], seed=24)
    q = np.random.default_rng(25).integers(0, 64, (6, D)).astype(np.float32)
    filt = [(inv.posting("s900"),), (inv.posting("s30"),),
            (inv.posting("s900"), inv.posting("s30"))] * 2
    ix.search_many(q, K, allow=filt)                    # caches s900
    rng = np.random.default_rng(26)
    extra = q + rng.integers(0, 2, q.shape).astype(np.float32)
    extra_ids = 3 * (N + np.arange(len(q))) + 7
    ix.insert_many(extra, extra_ids)
    for t in extra_ids:
        inv.insert("s900 s30", int(t))
    allv = np.concatenate([vecs, extra])
    allid = np.concatenate([ids, extra_ids])

    def answer():
        f = [tuple(inv.posting(p.token) for p in terms) for terms in filt]
        ref = [np.intersect1d(*[inv.lookup(p.token) for p in terms])
               if len(terms) > 1 else inv.lookup(terms[0].token)
               for terms in filt]
        v, i = ix.search_many(q, K, allow=f, mode="fused")
        _check(allv, allid, q, ref, v, i, exact=False)
        assert (i[:, 0] == extra_ids).all()     # each query's own new row
        return i

    h0, gen = _host_queries(), ix.generation
    answer()
    assert _host_queries() == h0 + len(q)
    ix.compact()
    assert ix.generation == gen + 1 and ix.pending_count == 0
    answer()
    assert _host_queries() == h0 + len(q)


def test_three_tag_and_and_a_tag_absent_from_a_shard():
    """At P = 2: a three-tag AND, and a tag that only one shard's images
    carry, alone and ANDed, match a float64 brute force exactly, and the
    bitmap counters and the shard's ``knn.filter`` span count the terms."""
    from repro.obs.metrics import prometheus_dump
    from repro.obs.trace import Trace
    rng = np.random.default_rng(31)
    n = 1200
    vecs = rng.integers(0, 64, (n, D)).astype(np.float32)
    words = [f"t{j}" for j in range(10)]
    tags = [list(rng.choice(words, size=rng.integers(1, 6), replace=False))
            for _ in range(n)]
    tags[5] = ["t0", "t1", "t2", "solo"]
    db = _tag_db(2, vecs, tags)
    idxs = [db.read_db(s).scalar_indexes["tags"] for s in db.active]
    assert sorted(len(ix.lookup("solo")) for ix in idxs) == [0, 1]
    filters = [("t0", "t1", "t2"), ("solo",), ("SOLO", "t0"),
               ("t3", "t4", "t5"), ("t1", "solo", "t2"), ("solo", "t9")]
    q = rng.integers(0, 64, (len(filters), D)).astype(np.float32)
    c0, b0 = _bitmap_counts()
    trace = Trace("knn")
    v, i = db.knn("emb", q, K, mode="fused", filters=filters,
                  filter_key="tags", trace=trace)
    trace.finish()
    allowed = 0
    for qq, f, vv, ii in zip(q, filters, v, i):
        a = np.array([b for b, t in enumerate(tags)
                      if {w.lower() for w in f} <= set(t)], np.int64)
        allowed += len(a)
        d = ((vecs[a].astype(np.float64) - qq) ** 2).sum(1)
        assert len(a) <= 80                    # every allowed row re-ranked
        got = ii[ii >= 0]
        assert np.isin(got, a).all() and len(got) == min(K, len(a))
        np.testing.assert_allclose(-vv[:len(got)], np.sort(d)[:len(got)],
                                   rtol=1e-6)
    assert (i[1:3, 0] == 5).all() and (i[4, 0] == 5) and (i[5] == -1).all()
    c1, b1 = _bitmap_counts()
    spans = [s for s in trace.find("knn.filter") if "rows_allowed" in s.attrs]
    assert len(spans) == 2
    assert sum(s.attrs["rows_allowed"] for s in spans) == allowed
    assert sum(s.attrs["bitmaps_cached"] for s in spans) == c1 - c0
    assert sum(s.attrs["bitmaps_built"] for s in spans) == b1 - b0 > 0
    dump = prometheus_dump()
    assert "pandadb_knn_filter_bitmap_cached_total" in dump
    assert "pandadb_knn_filter_bitmap_built_total" in dump


def test_bitmap_cache_under_contending_threads():
    """Threads that share one index piece and alternate between two
    inverted indexes keep replacing its bitmap cache while they fill it;
    every batch still reads the bitmaps built fresh for it."""
    import sys
    import threading
    ix = _bare_index(3000, seed=41)
    invs = [_tag_index(ix.ids, [60, 300, 900], seed=s) for s in (42, 43)]
    tags = [("s60",), ("s300", "s900"), ("s900",), ("s60", "s300")]
    want = [ix._filter_words([tuple(map(inv.lookup, f)) for f in tags])[0]
            for inv in invs]
    bad, done = [], []

    def work(j):
        for r in range(40):
            inv = invs[(j + r) % 2]
            got = ix._filter_words([tuple(map(inv.posting, f))
                                    for f in tags])[0]
            if not np.array_equal(got, want[(j + r) % 2]):
                bad.append((j, r))
        done.append(j)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(j,))
                   for j in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert sorted(done) == list(range(16)) and not bad


def test_a_tuple_filter_holds_terms_only():
    """A tuple filter ANDs postings or id arrays; a tuple of ids is
    refused rather than read as an AND of one-id terms, and an empty
    tuple names no term."""
    ix = _bare_index(100)
    ids = ix.ids[:3]
    words, _, _ = ix._filter_words([(ids, ids[:2]), ids])
    assert np.bitwise_count(words).sum(axis=1).tolist() == [2, 3]
    with pytest.raises(TypeError):
        ix._filter_words([tuple(int(i) for i in ids)])
    with pytest.raises(ValueError):
        ix._filter_words([()])
