"""The readers of the program's own spans and counts, on a hand-built
``View``: what each reads, and that each reads nothing from a program
without those spans."""
import pytest

from bench.harness import View, metric_reader
from bench.traffic.gen import Request
from bench.traffic.loops import Record, Window
from repro.obs import Trace


def _window(sizes, failed=()):
    recs = []
    for i, size in enumerate(sizes):
        r = Record(Request("k", "ref", None, {}, size=size), due=0.0,
                   sent=0.0, done=1.0)
        if i in failed:
            r.error = "Boom: failed"
        recs.append(r)
    return Window(recs, 0.0, 10.0)


def _knn_call(probe_s, rerank_s, h2d):
    """One SIFT-shaped call: a shard scan whose phases carry the probe's
    and the scan's uploads, and the merge's."""
    tr = Trace("knn")
    shard = tr.add_timed("knn.shard_scan", probe_s + rerank_s + 0.01,
                         shard=0, rows=10, rerank=True)
    tr.add_timed("knn.probe", probe_s, parent=shard, h2d_bytes=h2d[0])
    tr.add_timed("knn.luts", 0.001, parent=shard)
    tr.add_timed("knn.scan", 0.005, parent=shard, h2d_bytes=h2d[1],
                 kernel="pq_scan_ext", impl="xla")
    tr.add_timed("knn.rerank", rerank_s, parent=shard)
    tr.add_timed("knn.merge", 0.0001, shards=1, k=10, h2d_bytes=h2d[2])
    tr.finish()
    return tr


@pytest.fixture
def sift_view():
    traces = [_knn_call(0.002, 0.010, (100, 1000, 20)),
              _knn_call(0.004, 0.030, (200, 3000, 40))]
    return View(_window([32, 64]), 1.0, "TPU v5 lite", traces=traces)


def test_probe_ms_per_call(sift_view):
    assert metric_reader("probe_ms")(sift_view) == pytest.approx(3.0)


def test_rerank_ms_per_call(sift_view):
    assert metric_reader("rerank_ms")(sift_view) == pytest.approx(20.0)


def test_h2d_bytes_per_query_answered(sift_view):
    # (100 + 1000 + 20 + 200 + 3000 + 40) bytes over 32 + 64 vectors
    assert metric_reader("h2d_bytes_per_query")(sift_view) == \
        pytest.approx(4360 / 96)


def test_h2d_bytes_per_query_counts_answered_requests_only():
    traces = [_knn_call(0.001, 0.001, (10, 90, 0)) for _ in range(3)]
    view = View(_window([1, 1, 1], failed={2}), 1.0, "TPU v5 lite",
                traces=traces)
    assert metric_reader("h2d_bytes_per_query")(view) == pytest.approx(150.0)


def _serve_request(dur_s, cpu_ms, worker):
    tr = Trace("serve", text="q")
    tr.add_timed("queue.wait", 0.001, parent=tr.root)
    tr.add_timed("serve.execute", dur_s, parent=tr.root, worker=worker,
                 cpu_ms=cpu_ms)
    tr.finish()
    return tr


def test_serve_stall_ms_is_execute_less_cpu():
    traces = [_serve_request(0.100, 40.0, 0), _serve_request(0.300, 100.0, 1)]
    view = View(_window([1, 1]), 1.0, "TPU v5 lite", traces=traces)
    # (100 - 40 + 300 - 100) / 2
    assert metric_reader("serve_stall_ms")(view) == pytest.approx(130.0)


@pytest.mark.parametrize("name", ["probe_ms", "rerank_ms",
                                  "h2d_bytes_per_query", "serve_stall_ms"])
def test_silent_without_the_programs_spans(name):
    """A program that predates these spans: the SIFT trace it gives has
    only the after-the-fact shard scan and merge, the serving trace only
    its queue wait."""
    knn = Trace("knn")
    knn.add_timed("knn.shard_scan", 0.1, shard=0, rows=10, rerank=True)
    knn.add_timed("knn.merge", 0.001, shards=1, k=10)
    knn.finish()
    serve = Trace("serve", text="q")
    serve.add_timed("queue.wait", 0.001, parent=serve.root)
    serve.finish()
    for traces in ([knn], [serve], []):
        view = View(_window([8]), 1.0, "TPU v5 lite", traces=traces)
        assert metric_reader(name)(view) is None
