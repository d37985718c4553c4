"""The filtered-search cell (``yfcc_filtered_125k.tagged``) at a CPU size:
its run reads ``correct`` with its per-layer metrics in the traced line;
runs whose answers break the filter, drop an allowed neighbour, or come
from the bfloat16 control read ``correct`` false; its readers read what
they should and nothing from a program without the filtered path."""
import functools

import numpy as np
import pytest

from bench import control, harness
from bench.harness import View, metric_reader
from bench.rowmask_roofline import rowmask_adc_scan
from bench.trace_reduce import DeviceTrace
from bench.traffic.gen import Request
from bench.traffic.loops import Record, Window
from conftest import cpu_run, small_cell

CELL = "yfcc_filtered_125k.tagged"


def test_tiny_run_is_correct_with_its_metrics():
    from repro.kernels.dispatch import padded_rows
    cell = small_cell(CELL)
    res = cpu_run(cell, seconds=3.0, trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    checks = res["checks"]
    assert checks["filter_violations"]["value"] == 0
    assert checks["missing_answers"]["value"] == 0
    assert res["dispatch"]["pq_scan_rowmask:xla"] == res["attempted"]
    m = res["metrics"]
    assert m["filter_ms"]["value"] > 0
    # one [Q/32, rows] int32 mask per call: 4 * rows / 32 bytes a query
    rows = padded_rows(cell.config["n_vectors"], 512)
    assert m["mask_h2d_bytes_per_query"]["value"] == pytest.approx(
        4 * rows / 32)
    # no device trace off the chip: the roofline share reads nothing here
    assert "rowmask_adc_roofline" not in m


def test_filter_ignored_fails(monkeypatch):
    """Each shard answers as if every row passed the filter."""
    from repro.core.vector_index import IVFIndex

    def everything(self, allow, pending=False):
        return [np.arange(len(self.ids))] * len(allow)

    monkeypatch.setattr(IVFIndex, "_allowed_rows", everything)
    res = cpu_run(small_cell(CELL))
    assert res["correct"] is False
    assert res["checks"]["filter_violations"]["value"] > 0


def test_nearest_allowed_row_dropped_fails(monkeypatch):
    """The coordinator drops each query's nearest answer."""
    from repro.cluster import ShardedPandaDB
    real = ShardedPandaDB.knn

    @functools.wraps(real)
    def dropped(self, *a, **kw):
        v, i = (np.array(x) for x in real(self, *a, **kw))
        v[:, :-1], i[:, :-1] = v[:, 1:], i[:, 1:]
        v[:, -1], i[:, -1] = -np.inf, -1
        return v, i

    monkeypatch.setattr(ShardedPandaDB, "knn", dropped)
    res = cpu_run(small_cell(CELL))
    assert res["correct"] is False
    assert res["checks"]["missing_answers"]["value"] > 0


def test_control_is_not_correct():
    program, ctl, _ = control.readings(small_cell(CELL), seed=5,
                                       seconds=3.0)
    assert all(c.ok for c in program), program
    assert not all(c.ok for c in ctl), ctl


def test_a_program_without_filters_fails_at_set_up(monkeypatch):
    """A system whose ``knn`` takes no filters (the parent of the filtered
    path) is refused before any data is made."""
    from repro.cluster import ShardedPandaDB

    def knn(self, sub_key, queries, k, nprobe=None, mode="auto"):
        raise AssertionError("not reached")

    monkeypatch.setattr(ShardedPandaDB, "knn", knn)
    cell = small_cell(CELL)
    dep = harness.deployment(cell.config).Deployment(
        cell.config, cell.traffic, 1, {})
    with pytest.raises(RuntimeError, match="filtered kNN"):
        dep.setup()
    assert dep.corpus is None


def _view(sizes, device=None, **attrs):
    recs = [Record(Request("T", "r", None, {}, size=s), due=0.0, sent=0.0,
                   done=1.0) for s in sizes]
    view = View(Window(recs, 0.0, 10.0), 1.0, "TPU v5 lite", device=device)
    for k, v in attrs.items():
        setattr(view, k, v)
    return view


def test_rowmask_roofline_reads_the_rowmask_programs():
    work = [rowmask_adc_scan(q, 125000, 24, 256, 80, 1420, True)
            for q in (32, 256)]
    need = sum(w.min_seconds("TPU v5 lite") for w in work)
    trace = DeviceTrace((0.0, 10.0), {0: [("op", 0.0, 1.0)]}, {0: [
        ("jit_pq_adc_topk_rowmask_pallas", 1.0, 1.004),
        ("jit_pq_adc_topk_rowmask_pallas", 2.0, 2.02),
        ("jit_pq_adc_topk_ext_pallas", 3.0, 4.0)]}, [])
    read = metric_reader("rowmask_adc_roofline")
    got = read(_view([32, 256], trace, rowmask_work=work))
    assert got == pytest.approx(100.0 * need / 0.024)
    assert 0 < got < 100
    assert read(_view([32], trace)) is None            # no work laid out
    other = DeviceTrace((0.0, 10.0), {0: [("op", 0.0, 1.0)]}, {0: [
        ("jit_pq_adc_topk_ext_pallas", 3.0, 4.0)]}, [])
    assert read(_view([32], other, rowmask_work=work[:1])) is None


def test_rowmask_work_adds_the_mask_to_the_extended_scan():
    from bench.roofline import adc_scan
    w = rowmask_adc_scan(64, 1000, 24, 256, 80, 12, True)
    base = adc_scan(64, 1000, 24, 256, 80, 1.0, 12, True)
    assert w.ops == base.ops
    assert w.bytes == pytest.approx(base.bytes + 64 * 1000 / 8)


def test_mask_bytes_and_filter_ms_read_nothing_without_the_path():
    assert metric_reader("mask_h2d_bytes_per_query")(_view([32])) is None
    assert metric_reader("mask_h2d_bytes_per_query")(
        _view([32, 96], row_mask_h2d_bytes=4096)) == pytest.approx(32.0)
    assert metric_reader("filter_ms")(_view([32])) is None
