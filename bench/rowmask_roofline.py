"""The operations and bytes one row-masked ADC scan dispatch needs (the
filtered kNN's ``pq_scan_rowmask``), from its shapes.

It is the extended whole-table ADC scan of ``roofline.adc_scan`` -- every
code row scored against every query, with the residual bias and centroid
term -- without the probe mask's per-bucket floats, plus the packed row
mask it reads: one bit per (query, row).  Peaks come from
``roofline.PEAKS``.
"""
from __future__ import annotations

from bench.roofline import Work, adc_scan


def rowmask_adc_scan(q: int, n: int, m: int, ksub: int, k: int,
                     buckets: int, residual: bool) -> Work:
    w = adc_scan(q, n, m, ksub, k, 1.0, buckets, residual, masked=False)
    return Work(ops=w.ops, bytes=w.bytes + q * n / 8.0)
