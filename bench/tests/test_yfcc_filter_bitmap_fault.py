"""The filtered cell's "filter ignored" fault, planted where the filter
becomes each shard's row bitmaps: every row up to the last real one
passes every query's filter, on the row-masked scan and on the host path
alike, and the run reads ``correct`` false by ``filter_violations``."""
import numpy as np

from conftest import cpu_run, small_cell

CELL = "yfcc_filtered_125k.tagged"


def test_row_bitmaps_that_pass_every_row_fail(monkeypatch):
    from repro.core.vector_index import IVFIndex
    real = IVFIndex._filter_words

    def everything(self, allow, qrows=None):
        words, cached, built = real(self, allow, qrows)
        bits = np.zeros((words.shape[0], 32 * words.shape[1]), bool)
        bits[:len(allow), :len(self.ids)] = True
        words = np.packbits(bits, axis=1, bitorder="little").view("<u4")
        return words, cached, built

    monkeypatch.setattr(IVFIndex, "_filter_words", everything)
    res = cpu_run(small_cell(CELL))
    assert res["correct"] is False
    assert res["checks"]["filter_violations"]["value"] > 0
