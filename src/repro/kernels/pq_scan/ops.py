"""Dispatching wrapper: Pallas ADC kernel on TPU, jitted XLA twin elsewhere.

The kernel path is exact for any k (per-tile top-k >= global contribution of
that tile), so parity with ref.py is bitwise on candidate ids (the LUT sums
are the same fp32 adds in a different order).  Large k' (> 64) falls back to
the XLA path: the L max-extract sweeps stop paying for themselves.

Code tables are rarely block_n multiples, so the wrapper pads the codes up
to one and passes ``n_valid`` through: padded rows are masked to ``NEG``
inside the kernel (or to -inf on the XLA path) and can never appear in the
returned top-k.  Callers may also pre-pad for shape stability and pass their
own ``n_valid``.

The optional ``bias`` / ``row_bucket`` / ``cscores`` / ``probe_mask``
arguments carry the residual-PQ score decomposition and the fused
whole-table scan (see ref.py); with ``probe_mask``, queries whose probed
buckets hold fewer than k rows surface (val=-inf, id=-1) padding at the
tail -- the same contract the shard merge already truncates.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import (on_tpu, pad_rows, padded_queries,
                                    padded_rows, use_pallas)
from repro.kernels.pq_scan.pq_scan import (pq_adc_topk_ext_pallas,
                                           pq_adc_topk_pallas)

_NEG_THRESH = -1.5e38   # kernel NEG mask values live below this


@functools.partial(jax.jit, static_argnames=("k",))
def _pq_topk_xla(luts: jnp.ndarray, codes: jnp.ndarray, n_valid: jnp.ndarray,
                 k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jitted XLA twin of the kernel: fused LUT gathers + padding mask +
    top-k.  Scores accumulate in [Q, N] layout (one column gather per
    subspace) so the top-k runs over contiguous rows -- the [N, Q]
    transpose layout costs ~8x here.  ``n_valid`` is traced, so every
    block-padded code-table shape compiles once and serves any padding
    amount."""
    qn, m, _ksub = luts.shape
    codes = codes.astype(jnp.int32)
    s = jnp.zeros((qn, codes.shape[0]), jnp.float32)
    for j in range(m):                      # static unroll: M is small
        s = s + luts[:, j, :][:, codes[:, j]]
    cols = jnp.arange(codes.shape[0])[None, :]
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
    qn, m, _ksub = luts.shape
    codes = codes.astype(jnp.int32)
    s = jnp.zeros((qn, codes.shape[0]), jnp.float32)
    for j in range(m):                      # static unroll: M is small
        s = s + luts[:, j, :][:, codes[:, j]]
    rb = row_bucket.astype(jnp.int32)
    s = s + bias[None, :] + cscores[:, rb]
    if masked:
        s = jnp.where(probe_mask[:, rb] > 0.5, s, -jnp.inf)
    cols = jnp.arange(codes.shape[0])[None, :]
    s = jnp.where(cols >= n_valid, -jnp.inf, s)
    vals, idx = jax.lax.top_k(s, k)
    return vals, idx.astype(jnp.int32)


def pq_adc_topk(luts, codes, k: int, block_n: int = 512, n_valid: int = -1,
                force_pallas: bool = False, bias=None, row_bucket=None,
                cscores=None, probe_mask=None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """[Q, M, K] x [N, M] -> (vals [Q, k'], ids [Q, k']), k' = min(k, n_valid),
    as host arrays (inputs are padded on the host: pass host arrays).

    Rows at positions >= ``n_valid`` (default: all of ``codes``) are treated
    as padding and excluded from the result; returned indices are always
    < ``n_valid``.  ``cscores`` / ``probe_mask`` require ``row_bucket``
    (see ref.py for the extended score decomposition); with ``probe_mask``,
    per-query positions past that query's probed row count come back as
    (val=-inf, id=-1) padding."""
    n = codes.shape[0]
    qn = luts.shape[0]
    if n_valid < 0 or n_valid > n:
        n_valid = n
    k = min(k, n_valid)
    if k <= 0:
        return (np.zeros((qn, 0), np.float32),
                np.zeros((qn, 0), np.int32))
    ext = any(a is not None for a in (bias, row_bucket, cscores, probe_mask))
    if (cscores is not None or probe_mask is not None) and row_bucket is None:
        raise ValueError("cscores/probe_mask require row_bucket")
    use_kernel = use_pallas("pq_scan_ext" if ext else "pq_scan", k,
                            force_pallas)
    rows, qrows = padded_rows(n, block_n), padded_queries(qn)
    luts = pad_rows(luts, qrows, np.float32)
    codes = pad_rows(codes, rows)
    if not ext:
        if use_kernel:
            v, i = pq_adc_topk_pallas(luts, codes, k, block_n=block_n,
                                      n_valid=n_valid,
                                      interpret=not on_tpu())
        else:
            v, i = _pq_topk_xla(luts, codes, np.int32(n_valid), k)
        return np.asarray(v)[:qn], np.asarray(i)[:qn]

    masked = probe_mask is not None
    mb = (cscores.shape[1] if cscores is not None
          else probe_mask.shape[1] if probe_mask is not None else 1)
    bias = pad_rows(np.zeros(n) if bias is None else bias, rows, np.float32)
    rb = pad_rows(np.zeros(n) if row_bucket is None else row_bucket, rows,
                  np.int32)
    cs = pad_rows(np.zeros((qn, mb)) if cscores is None else cscores, qrows,
                  np.float32)
    pm = pad_rows(np.ones((qn, mb)) if probe_mask is None else probe_mask,
                  qrows, np.float32)
    if use_kernel:
        v, i = pq_adc_topk_ext_pallas(luts, codes, bias, rb, cs, pm, k,
                                      block_n=block_n, n_valid=n_valid,
                                      interpret=not on_tpu())
    else:
        v, i = _pq_topk_xla_ext(luts, codes, np.int32(n_valid), bias, rb,
                                cs, pm, k, masked)
    v, i = np.asarray(v)[:qn], np.asarray(i)[:qn]
    if masked:
        # the kernel's in-kernel NEG mask stands in for -inf: restore it and
        # pin the id payload of empty positions to -1 (the merge contract)
        v = np.where(v <= _NEG_THRESH, -np.inf, v).astype(np.float32)
        i = np.where(np.isfinite(v), i, -1).astype(np.int32)
    return v, i
