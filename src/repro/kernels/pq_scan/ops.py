"""Dispatching wrapper: Pallas ADC kernels on TPU, jitted XLA twins elsewhere.

Two programs, each a Pallas kernel with an XLA twin: the extended scan
``pq_scan_ext`` (LUT sum + per-row bias + per-bucket centroid term, rows of
non-probed buckets masked; a call without residual terms scores with
zero ones, the plain ADC scan) and the row-masked scan ``pq_scan_rowmask``
(filtered kNN).  The kernel path is exact for any k (per-tile top-k >=
global contribution of that tile), so parity with ref.py is bitwise on
candidate ids (the LUT sums are float32 adds in a different order).  Above
the kernels' cap (``dispatch.KERNEL_MAX_K``: k' 128, the widest at which
the extended kernel measured faster than its twin on the chip) the XLA
path runs.

Code tables are rarely block_n multiples, so the wrapper pads the codes up
to one and passes ``n_valid`` through: padded rows are masked to ``NEG``
inside the kernel (or to -inf on the XLA path) and can never appear in the
returned top-k.  Callers may also pre-pad for shape stability and pass their
own ``n_valid``.

The optional ``bias`` / ``row_bucket`` / ``cscores`` / ``probe_mask``
arguments carry the residual-PQ score decomposition and the fused
whole-table scan (see ref.py); with ``probe_mask``, queries whose probed
buckets hold fewer than k rows surface (val=-inf, id=-1) padding at the
tail -- the same contract the shard merge already truncates.  ``row_mask``
(filtered kNN) swaps the probe mask for a per-(query, row) allow bit,
packed 32 queries to an int32 word (``ref.pack_row_mask`` is the layout's
reference; :func:`row_mask_from_bitmaps` builds it on the device from
per-query row bitmaps), and runs the row-masked kernel or its twin under a
dispatch name of its own.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import (count_h2d, impl_name, on_tpu, pad_rows,
                                    padded_queries, padded_rows, use_pallas)
from repro.kernels.pq_scan.pq_scan import (pq_adc_topk_ext_pallas,
                                           pq_adc_topk_rowmask_pallas,
                                           unpack_row_mask)

_NEG_THRESH = -1.5e38   # kernel NEG mask values live below this


def _ext_scores_xla(luts, codes, bias, rb, cscores) -> jnp.ndarray:
    """[Q, N] LUT gathers + bias + per-row bucket term.  Scores accumulate
    in [Q, N] layout (one column gather per subspace) so the top-k runs
    over contiguous rows; the [N, Q] layout costs ~8x here."""
    qn, m, _ksub = luts.shape
    codes = codes.astype(jnp.int32)
    s = jnp.zeros((qn, codes.shape[0]), jnp.float32)
    for j in range(m):                      # static unroll: M is small
        s = s + luts[:, j, :][:, codes[:, j]]
    return s + bias[None, :] + cscores[:, rb]


def _topk_valid(s, n_valid, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    cols = jnp.arange(s.shape[1])[None, :]
    s = jnp.where(cols >= n_valid, -jnp.inf, s)
    vals, idx = jax.lax.top_k(s, k)
    return vals, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "masked"))
def _pq_topk_xla_ext(luts: jnp.ndarray, codes: jnp.ndarray,
                     n_valid: jnp.ndarray, bias: jnp.ndarray,
                     row_bucket: jnp.ndarray, cscores: jnp.ndarray,
                     probe_mask: jnp.ndarray, k: int, masked: bool
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Extended XLA twin: LUT gathers + bias + per-row bucket term (+ probe
    mask) + padding mask + top-k, one dispatch for the whole batch."""
    rb = row_bucket.astype(jnp.int32)
    s = _ext_scores_xla(luts, codes, bias, rb, cscores)
    if masked:
        s = jnp.where(probe_mask[:, rb] > 0.5, s, -jnp.inf)
    return _topk_valid(s, n_valid, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _pq_topk_xla_rowmask(luts: jnp.ndarray, codes: jnp.ndarray,
                         n_valid: jnp.ndarray, bias: jnp.ndarray,
                         row_bucket: jnp.ndarray, cscores: jnp.ndarray,
                         row_mask: jnp.ndarray, k: int
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-masked XLA twin: the extended scores with each query's rows
    outside its filter (the packed ``row_mask``) pinned to -inf."""
    rb = row_bucket.astype(jnp.int32)
    s = _ext_scores_xla(luts, codes, bias, rb, cscores)
    s = jnp.where(unpack_row_mask(row_mask, s.shape[0]), s, -jnp.inf)
    return _topk_valid(s, n_valid, k)


@jax.jit
def row_mask_from_bitmaps(bitmaps: jnp.ndarray) -> jnp.ndarray:
    """Per-query row bitmaps [Qp, W] uint32 (bit r % 32 of word r // 32:
    the query may return row r) -> the row-masked scan's packed allow bits
    [ceil(Qp / 32), 32 W] int32 (bit j of word [w, r]: query 32 w + j may
    return row r), the layout ``ref.pack_row_mask`` builds from row
    positions.  A bit transposition of each 32 x 32 block of (query, row)
    bits, run on the device so the host uploads the bitmaps as they are."""
    qp, w = bitmaps.shape
    groups = -(-qp // 32)
    b = jnp.pad(bitmaps.astype(jnp.uint32), ((0, 32 * groups - qp), (0, 0)))
    bit = jnp.arange(32, dtype=jnp.uint32)
    # allow[g, j, c, i]: query 32 g + j may return row 32 c + i
    allow = (b.reshape(groups, 32, w, 1) >> bit) & 1
    words = jnp.sum(allow << bit[None, :, None, None], axis=1,
                    dtype=jnp.uint32)                        # [g, w, 32]
    return jax.lax.bitcast_convert_type(words.reshape(groups, 32 * w),
                                        jnp.int32)


def pq_adc_topk(luts, codes, k: int, block_n: int = 512, n_valid: int = -1,
                force_pallas: bool = False, bias=None, row_bucket=None,
                cscores=None, probe_mask=None, row_mask=None, span=None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """[Q, M, K] x [N, M] -> (vals [Q, k'], ids [Q, k']), k' = min(k, n_valid),
    as host arrays (inputs are padded on the host: pass host arrays).

    Rows at positions >= ``n_valid`` (default: all of ``codes``) are treated
    as padding and excluded from the result; returned indices are always
    < ``n_valid``.  ``cscores`` / ``probe_mask`` require ``row_bucket``
    (see ref.py for the extended score decomposition); a term left out
    scores as zero, every row in one probed bucket.  With ``probe_mask``,
    per-query positions past that query's probed row count come back as
    (val=-inf, id=-1) padding.  ``row_mask`` (the packed allow bits of
    :func:`row_mask_from_bitmaps` or ``ref.pack_row_mask``, already
    padded, on the host or the device) takes
    the row-masked scan, ``pq_scan_rowmask``, instead of ``pq_scan_ext``:
    every bucket is scored and each query sees only its allowed rows, with
    the same (-inf, -1) tail where it has fewer than k'; it excludes
    ``probe_mask``.  The padded operands' bytes count as
    ``h2d_bytes:<kernel>`` (the row mask is the caller's to count);
    ``span`` (a live ``knn.scan`` span, or None) gets them with ``kernel``
    and ``impl``."""
    n = codes.shape[0]
    qn = luts.shape[0]
    if n_valid < 0 or n_valid > n:
        n_valid = n
    k = min(k, n_valid)
    if k <= 0:
        return (np.zeros((qn, 0), np.float32),
                np.zeros((qn, 0), np.int32))
    rowmask = row_mask is not None
    if (cscores is not None or probe_mask is not None) and row_bucket is None:
        raise ValueError("cscores/probe_mask require row_bucket")
    if rowmask and probe_mask is not None:
        raise ValueError("row_mask and probe_mask exclude each other")
    kernel = "pq_scan_rowmask" if rowmask else "pq_scan_ext"
    use_kernel = use_pallas(kernel, k, force_pallas)
    rows, qrows = padded_rows(n, block_n), padded_queries(qn)
    luts = pad_rows(luts, qrows, np.float32)
    codes = pad_rows(codes, rows)
    masked = probe_mask is not None
    mb = (cscores.shape[1] if cscores is not None
          else probe_mask.shape[1] if masked else 1)
    bias = pad_rows(np.zeros(n) if bias is None else bias, rows, np.float32)
    rb = pad_rows(np.zeros(n) if row_bucket is None else row_bucket, rows,
                  np.int32)
    cs = pad_rows(np.zeros((qn, mb)) if cscores is None else cscores, qrows,
                  np.float32)
    if rowmask:
        if row_mask.shape != (-(-qrows // 32), rows):
            raise ValueError(f"row_mask {row_mask.shape} is not packed for "
                             f"{qrows} queries x {rows} rows")
        count_h2d(kernel, span, luts, codes, bias, rb, cs, kernel=kernel,
                  impl=impl_name(use_kernel))
        if use_kernel:
            v, i = pq_adc_topk_rowmask_pallas(
                luts, codes, bias, rb, cs, row_mask, k, block_n=block_n,
                n_valid=n_valid, interpret=not on_tpu())
        else:
            v, i = _pq_topk_xla_rowmask(luts, codes, np.int32(n_valid), bias,
                                        rb, cs, row_mask, k)
        return _empty_tail(v, i, qn)
    pm = pad_rows(probe_mask if masked else np.ones((qn, mb)), qrows,
                  np.float32)
    count_h2d(kernel, span, luts, codes, bias, rb, cs, pm, kernel=kernel,
              impl=impl_name(use_kernel))
    if use_kernel:
        v, i = pq_adc_topk_ext_pallas(luts, codes, bias, rb, cs, pm, k,
                                      block_n=block_n, n_valid=n_valid,
                                      interpret=not on_tpu())
    else:
        v, i = _pq_topk_xla_ext(luts, codes, np.int32(n_valid), bias, rb,
                                cs, pm, k, masked)
    if masked:
        return _empty_tail(v, i, qn)
    return np.asarray(v)[:qn], np.asarray(i)[:qn]


def _empty_tail(v, i, qn: int) -> Tuple[np.ndarray, np.ndarray]:
    """The kernel's in-kernel NEG mask stands in for -inf: restore it and
    pin the id payload of empty positions to -1 (the merge contract)."""
    v, i = np.asarray(v)[:qn], np.asarray(i)[:qn]
    v = np.where(v <= _NEG_THRESH, -np.inf, v).astype(np.float32)
    return v, np.where(np.isfinite(v), i, -1).astype(np.int32)
