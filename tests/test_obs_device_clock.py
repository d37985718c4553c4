"""Program spans on the device trace's clock, host->device byte counts, and
the serving workers' CPU time.

Contracts pinned here:

* a live span (``Trace.span``) is also a ``pandadb.<name>`` profiler
  annotation on the thread that opened it: in a captured trace the kNN
  path's phases nest inside their ``knn.shard_scan`` in the order the
  ``Trace`` holds them, and ``serve.execute`` sits on a worker's line with
  its ``worker`` index as metadata,
* tracing off annotates nothing and reads no thread CPU clock,
* ``h2d_bytes:<site>`` counts exactly the bytes of the host arrays a device
  program is handed (the padded operands, for the kernel wrappers), and the
  enclosing span carries the same number,
* a request's ``cpu_ms`` never exceeds its ``serve.execute`` duration.
"""
import dataclasses
import glob
import threading
import time
import warnings

import jax
import numpy as np
import pytest

from repro.cluster import ShardedPandaDB
from repro.configs.pandadb import (ObsConfig, PandaDBConfig,
                                   VectorIndexConfig)
from repro.core import PandaDB
from repro.core.aipm import feature_hash_extractor, label_extractor
from repro.core.vector_index import IVFIndex
from repro.kernels.dispatch import METRICS
from repro.obs import Trace
from repro.serving.engine import QueryServer

DIM = 32
N_VECTORS = 1000
KNN_PHASES = ["knn.probe", "knn.luts", "knn.scan", "knn.rerank"]
PET_Q = "MATCH (p:Pet) WHERE p.photo->animal = 'cat' RETURN p.name"


def _cfg(trace: bool) -> PandaDBConfig:
    return dataclasses.replace(
        PandaDBConfig(index=VectorIndexConfig(
            dim=DIM, vectors_per_bucket=50, nprobe=4, pq_m=8,
            pq_residual=True, rerank_mult=4)),
        obs=ObsConfig(trace=trace))


def _vectors(n=N_VECTORS, seed=0) -> np.ndarray:
    return np.random.default_rng(seed).normal(size=(n, DIM)).astype(
        np.float32)


def _decode(raws):
    return np.stack([np.frombuffer(bytes(r), np.float32) for r in raws])


def _cluster(trace: bool, n_shards: int = 2) -> ShardedPandaDB:
    c = ShardedPandaDB(n_shards, cfg=_cfg(trace))
    c.register_extractor("emb", _decode)
    for row in _vectors():
        c.create_node("Image", vec=row.tobytes())
    c.build_index("emb", "vec")
    return c


def _pets(trace: bool) -> PandaDB:
    db = PandaDB(dataclasses.replace(PandaDBConfig(),
                                     obs=ObsConfig(trace=trace)))
    db.register_extractor("animal", label_extractor(["cat"]))
    rng = np.random.default_rng(3)
    for i in range(6):
        db.graph.create_node("Pet", name=f"pet_{i}", photo=rng.bytes(256))
    return db


def _serve_one(db, annotate_submit: bool = False):
    server = QueryServer(db, n_workers=2)
    server.start()
    try:
        if annotate_submit:
            with jax.profiler.TraceAnnotation("test.submit"):
                handle = server.submit(PET_Q)
        else:
            handle = server.submit(PET_Q)
        return handle.get(timeout=60)
    finally:
        server.close()


def _stats(ev):
    with warnings.catch_warnings():     # the stats' type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def _host_events(log_dir):
    """``[(line index, name, start_ns, end_ns, stats)]`` of the capture's
    host annotations that the program or this test wrote."""
    from jax.profiler import ProfileData
    files = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("pandadb.", "test.")):
                    out.append((li, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                _stats(ev)))
    return out


@pytest.fixture(scope="module")
def warm_cluster():
    """A traced two-shard fused PQ cluster whose programs are compiled."""
    c = _cluster(trace=True)
    c.knn("emb", _vectors(8, seed=1), 5, mode="fused")
    yield c
    c.close()


def test_program_spans_land_in_a_profiler_capture(tmp_path, warm_cluster):
    db = _pets(trace=True)
    _serve_one(db)                       # compile off the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        warm_cluster.knn("emb", _vectors(8, seed=1), 5, mode="fused")
        knn_trace = warm_cluster.tracer.last
        rows, err = _serve_one(db, annotate_submit=True)
        serve_trace = db.tracer.last
    finally:
        jax.profiler.stop_trace()
    assert err is None and rows
    events = _host_events(tmp_path)

    # every shard's phases nest inside its knn.shard_scan, on its line, in
    # the order the Trace holds them
    shard_evs = [e for e in events if e[1] == "pandadb.knn.shard_scan"]
    assert sorted(e[4]["shard"] for e in shard_evs) == [0, 1]
    for sp in knn_trace.find("knn.shard_scan"):
        want = [c.name for c in sp.children]
        assert want == KNN_PHASES
        line, _, a, b, _ = next(e for e in shard_evs
                                if e[4]["shard"] == sp.attrs["shard"])
        inside = sorted((e for e in events
                         if e[0] == line and a <= e[2] and e[3] <= b
                         and e[1] != "pandadb.knn.shard_scan"),
                        key=lambda e: e[2])
        assert [e[1] for e in inside] == [f"pandadb.{n}" for n in want]

    # the request ran on a worker's line, not the submitting thread's
    submit_line = next(e[0] for e in events if e[1] == "test.submit")
    execs = [e for e in events if e[1] == "pandadb.serve.execute"]
    assert len(execs) == 1
    line, _, a, b, stats = execs[0]
    assert line != submit_line
    assert stats["worker"] in (0, 1)
    assert serve_trace.find("serve.execute")[0].attrs["worker"] == \
        stats["worker"]
    # the request's plan and pulls are annotated on the worker's line too
    assert any(e[0] == line and e[1] == "pandadb.cursor.pull"
               and a <= e[2] and e[3] <= b for e in events)


class _Boom:
    def __init__(self, *a, **kw):
        raise AssertionError("annotated with tracing off")


def _no_thread_time():
    raise AssertionError("thread CPU clock read with tracing off")


def test_tracing_off_annotates_nothing_and_reads_no_cpu_clock(monkeypatch):
    cluster = _cluster(trace=False)
    db = _pets(trace=False)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Boom)
    monkeypatch.setattr(time, "thread_time", _no_thread_time)
    try:
        v, i = cluster.knn("emb", _vectors(8, seed=1), 5, mode="fused")
        assert (i >= 0).all()
        rows, err = _serve_one(db)
        assert err is None and rows
        # the patched names are the ones a traced run reaches
        tr = Trace("probe")
        with pytest.raises(AssertionError, match="annotated"):
            with tr.span("x"):
                pass
    finally:
        cluster.close()


def _fused_index() -> IVFIndex:
    return IVFIndex.build(_vectors(), cfg=_cfg(False).index)


def test_pq_scan_ext_counts_the_padded_operands():
    ix = _fused_index()
    m = ix.centroids.shape[0]
    q = _vectors(5, seed=2)
    # by hand: 1 000 code rows pad to two 512-row tiles, 5 queries to 8
    rows, qrows, ksub = 1024, 8, 256
    want = (qrows * 8 * ksub * 4        # LUTs, float32
            + rows * 8                  # codes, uint8
            + rows * 4 + rows * 4       # code bias f32, bucket ids i32
            + qrows * m * 4 * 2)        # centroid terms, probe mask
    before = METRICS.counter("h2d_bytes:pq_scan_ext").value
    tr = Trace("knn")
    ix.search_many(q, 5, mode="fused", trace=tr)
    assert METRICS.counter("h2d_bytes:pq_scan_ext").value - before == want
    (scan,) = tr.find("knn.scan")
    assert scan.attrs["h2d_bytes"] == want
    assert scan.attrs["kernel"] == "pq_scan_ext"
    assert scan.attrs["impl"] in ("xla", "pallas", "interpret")


def test_probe_and_merge_count_their_uploads(warm_cluster):
    q = _vectors(8, seed=4)
    probe0 = METRICS.counter("h2d_bytes:probe").value
    merge0 = METRICS.counter("h2d_bytes:topk_merge").value
    warm_cluster.knn("emb", q, 5, mode="fused")
    tr = warm_cluster.tracer.last
    centroids = warm_cluster.index_pieces("emb")[0].centroids
    per_probe = q.nbytes + centroids.nbytes
    probes = tr.find("knn.probe")
    assert len(probes) == 2
    assert all(sp.attrs["h2d_bytes"] == per_probe for sp in probes)
    assert METRICS.counter("h2d_bytes:probe").value - probe0 == 2 * per_probe
    # two shards' windows of 8 x 5 float32 values and int64 ids
    (merge,) = tr.find("knn.merge")
    assert merge.attrs["h2d_bytes"] == 2 * 8 * 5 * (4 + 8)
    assert METRICS.counter("h2d_bytes:topk_merge").value - merge0 == \
        merge.attrs["h2d_bytes"]


def test_counters_keep_out_of_the_dispatch_view():
    from repro.kernels.dispatch import dispatch_counts
    _fused_index().search_many(_vectors(4, seed=5), 3, mode="fused")
    assert not any(k.startswith("h2d_bytes") or k == "jit_compiles"
                   for k in dispatch_counts())


def test_jit_compiles_counts_a_backend_compile():
    before = METRICS.counter("jit_compiles").value
    jax.jit(lambda x: x * 3 + 1)(np.arange(7, dtype=np.float32))
    assert METRICS.counter("jit_compiles").value > before


@pytest.mark.parametrize("mode", ["fused", "adc", "float"])
def test_search_phases_nest_under_the_callers_span(mode):
    ix = _fused_index()
    tr = Trace("knn")
    with tr.span("caller") as caller:
        ix.search_many(_vectors(6, seed=6), 4, mode=mode, trace=tr)
    tr.finish()
    names =[c.name for c in caller.children]
    assert names[0] == "knn.probe" and "knn.scan" in names
    if mode != "float":
        assert "knn.luts" in names and names[-1] == "knn.rerank"
    if mode != "fused":
        assert "knn.gather" in names
    assert tr.well_nested()
    total = sum(sp.attrs.get("h2d_bytes", 0) for sp in tr.spans())
    assert total == sum(sp.attrs["h2d_bytes"] for sp in tr.spans()
                        if sp.name in ("knn.probe", "knn.scan"))


def test_single_query_takes_the_host_scan_span():
    ix = _fused_index()
    tr = Trace("knn")
    ix.search_many(_vectors(1, seed=7), 4, trace=tr)
    assert [c.name for c in tr.root.children] == ["knn.host_scan"]
    assert not tr.root.children[0].children


def test_executor_index_knn_is_live_and_keeps_its_attributes():
    cfg = dataclasses.replace(PandaDBConfig(index=VectorIndexConfig(
        dim=DIM)), obs=ObsConfig(trace=True))
    db = PandaDB(cfg)
    db.register_extractor("face", feature_hash_extractor(dim=DIM))
    rng = np.random.default_rng(5)
    photos = [rng.bytes(256) for _ in range(40)]
    nodes = [db.graph.create_node("Person", name=f"p{i}",
                                  photo=photos[i % 20]) for i in range(40)]
    for i in range(39):
        db.graph.create_relationship(nodes[i], nodes[i + 1], "knows")
    db.build_index("face", "photo")
    q = ("MATCH (n:Person)-[:knows]->(f:Person), (m:Person) "
         "WHERE n.name = $p AND f.photo->face ~: m.photo->face "
         "RETURN f.name, m.name")
    rows = db.session().run(q, {"p": "p3"}).fetchall()
    assert rows
    (sp,) = db.tracer.last.find("index.knn")
    assert {"q", "k", "nprobe", "rerank"} <= set(sp.attrs)
    assert [c.name for c in sp.children][0] in ("knn.probe", "knn.host_scan")


def test_cpu_ms_within_the_execute_span():
    db = _pets(trace=True)
    server = QueryServer(db, n_workers=3)
    server.start()
    traces = []
    lock = threading.Lock()

    def keep(tr):
        with lock:
            traces.append(tr)

    try:
        for _ in range(6):
            handle = server.submit(PET_Q)
            keep(db.tracer.last)
            rows, err = handle.get(timeout=60)
            assert err is None
    finally:
        server.close()
    for tr in traces:
        (sp,) = tr.find("serve.execute")
        assert 0.0 <= sp.attrs["cpu_ms"] <= sp.duration_s * 1e3
        assert sp.parent is tr.root and tr.well_nested()
