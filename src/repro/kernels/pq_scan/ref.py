"""Pure-numpy oracle for the PQ ADC scan kernel: LUT-sum scores + top-k.

Asymmetric distance computation (ADC): the corpus lives as uint8 PQ codes
``codes[N, M]`` (M subspaces, K = 2**bits centers each) and each query is a
per-subspace lookup table ``luts[Q, M, K]`` of *scores* (higher = better;
for L2 the LUT holds negative squared sub-distances, for IP the sub dot
products).  The scan is then M table gathers + an add per corpus row --
no floats from the corpus are ever touched.

Three optional extensions carry the residual-encoding / fused-pipeline
score decomposition (score = LUT sum + per-row bias + per-query bucket
term, masked to the probed buckets):

* ``bias [N]`` -- a per-row additive constant (residual PQ's
  ``-2*c_b.r_hat - ||r_hat||^2`` term, precomputed at encode time).
* ``row_bucket [N]`` + ``cscores [Q, MB]`` -- adds ``cscores[q,
  row_bucket[n]]`` per row (residual PQ's ``-||q - c_b||^2`` / ``q.c_b``
  centroid term, already computed by the probe).
* ``row_bucket [N]`` + ``probe_mask [Q, MB]`` -- pins rows whose bucket a
  query did not probe to -inf (the fused path scans the whole code table
  in one call instead of gathering per signature).

``row_mask [Q, N]`` (bool, filtered kNN) pins each row a query's filter
excludes to -inf; the kernel takes the same bits packed 32 queries to a
word, the layout :func:`pack_row_mask` builds from row positions.

Tie-breaking matches ``jax.lax.top_k`` (equal scores -> lower row index),
so candidate ids are byte-comparable against the kernel and the XLA twin.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from repro.kernels.dispatch import padded_queries, padded_rows


def pq_scores_ref(luts, codes, bias=None, row_bucket=None, cscores=None,
                  probe_mask=None, row_mask=None) -> np.ndarray:
    """[Q, M, K] x [N, M] -> [Q, N]: s[q, n] = sum_m luts[q, m, codes[n, m]]
    (+ bias[n] + cscores[q, row_bucket[n]], non-probed buckets and rows
    outside ``row_mask`` -> -inf)."""
    luts = np.asarray(luts, np.float32)
    codes = np.asarray(codes).astype(np.int64)
    q, m, _k = luts.shape
    s = np.zeros((q, codes.shape[0]), np.float32)
    for j in range(m):
        s += luts[:, j, :][:, codes[:, j]]
    if bias is not None:
        s += np.asarray(bias, np.float32)[None, :]
    if row_bucket is not None:
        rb = np.asarray(row_bucket).astype(np.int64)
        if cscores is not None:
            s += np.asarray(cscores, np.float32)[:, rb]
        if probe_mask is not None:
            s = np.where(np.asarray(probe_mask, bool)[:, rb], s, -np.inf)
    if row_mask is not None:
        s = np.where(np.asarray(row_mask, bool), s, -np.inf)
    return s


def pq_adc_topk_ref(luts, codes, k: int, n_valid: int = -1, bias=None,
                    row_bucket=None, cscores=None, probe_mask=None,
                    row_mask=None) -> Tuple[np.ndarray, np.ndarray]:
    """[Q, M, K] x [N, M] -> (scores [Q, k], indices [Q, k]), higher = better.

    ``n_valid`` (< N) masks trailing padding rows to -inf, mirroring the
    kernel's contract so the dispatcher can pad code tables freely."""
    s = pq_scores_ref(luts, codes, bias=bias, row_bucket=row_bucket,
                      cscores=cscores, probe_mask=probe_mask,
                      row_mask=row_mask)
    n = s.shape[1]
    if 0 <= n_valid < n:
        s[:, n_valid:] = -np.inf
    # stable descending sort == lax.top_k tie order (lower index first)
    idx = np.argsort(-s, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(s, idx, axis=1)
    if probe_mask is not None or row_mask is not None:
        # a query probing or allowed fewer than k rows pads its tail:
        # (val=-inf, id=-1)
        idx = np.where(np.isfinite(vals), idx, -1)
    return vals.astype(np.float32), idx.astype(np.int32)


def pack_row_mask(rows: Sequence[np.ndarray], n: int,
                  block_n: int = 512) -> np.ndarray:
    """Per-query allowed row positions (each < ``n``; a repeated position
    sets its bit once) -> the row-masked scan's packed allow bits,
    [W, padded_rows(n)] int32 with W = ceil(padded_queries(Q) / 32): bit j
    of word [w, r] says query 32*w + j may return row r.  Padded to the
    shapes ``ops.pq_adc_topk`` pads the batch and code table to.  The
    layout's reference: the served path builds it on the device
    (``ops.row_mask_from_bitmaps``)."""
    qn = len(rows)
    words = -(-padded_queries(qn) // 32)
    cols = padded_rows(n, block_n)
    counts = np.fromiter((len(r) for r in rows), np.int64, qn)
    q = np.repeat(np.arange(qn), counts)
    pos = (np.concatenate([np.asarray(r, np.int64) for r in rows])
           if counts.sum() else np.zeros(0, np.int64))
    packed = np.zeros(words * cols, np.uint32)
    np.bitwise_or.at(packed, (q // 32) * cols + pos,
                     np.left_shift(np.uint32(1), (q % 32).astype(np.uint32)))
    return packed.view(np.int32).reshape(words, cols)
