"""Dispatching wrapper: Pallas kernel on TPU, jitted XLA twin elsewhere.

The kernel path is exact for any k (per-tile top-k >= global contribution of
that tile), so parity with ref.py is bitwise up to fp32 reduction order.
Large k (> 64) falls back to the XLA path: the L max-extract sweeps stop
paying for themselves.

Realistic corpus sizes are never block_n multiples, so the wrapper pads the
corpus up to one and passes ``n_valid`` through: padded rows are masked to
``NEG`` inside the kernel (or to -inf on the XLA path) and can never appear
in the returned top-k.  Callers may also pre-pad for shape stability and
pass their own ``n_valid``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import (count_h2d, impl_name, on_tpu, pad_rows,
                                    padded_queries, padded_rows, use_pallas)
from repro.kernels.ivf_scan.ivf_scan import ivf_scan_topk_pallas
from repro.kernels.ivf_scan.ref import scores_ref


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _scan_topk_xla(q: jnp.ndarray, corpus: jnp.ndarray, n_valid: jnp.ndarray,
                   k: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jitted XLA twin of the kernel: fused scores + padding mask + top-k.
    ``n_valid`` is traced, so every block-padded corpus shape compiles once
    and serves any padding amount."""
    s = scores_ref(q, corpus, metric)
    cols = jnp.arange(corpus.shape[0])[None, :]
    s = jnp.where(cols >= n_valid, -jnp.inf, s)
    vals, idx = jax.lax.top_k(s, k)
    return vals, idx.astype(jnp.int32)


def ivf_scan_topk(q, corpus, k: int, metric: str = "l2", block_n: int = 512,
                  n_valid: int = -1, force_pallas: bool = False, span=None
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """[Q, d] x [N, d] -> (vals [Q, k'], ids [Q, k']), k' = min(k, n_valid),
    as host arrays.

    Rows at positions >= ``n_valid`` (default: all of ``corpus``) are treated
    as padding and excluded from the result; returned indices are always
    < ``n_valid``.  Inputs are padded on the host (``dispatch.pad_rows``), so
    pass host arrays: a device array makes a round trip.  The padded
    operands' bytes count as ``h2d_bytes:ivf_scan``; ``span`` (a live
    ``knn.scan`` span, or None) gets them with ``kernel`` and ``impl``.
    """
    n = corpus.shape[0]
    if n_valid < 0 or n_valid > n:
        n_valid = n
    k = min(k, n_valid)
    if k <= 0:
        return (np.zeros((q.shape[0], 0), np.float32),
                np.zeros((q.shape[0], 0), np.int32))
    qn = q.shape[0]
    q = pad_rows(q, padded_queries(qn))
    corpus = pad_rows(corpus, padded_rows(n, block_n))
    pallas = use_pallas("ivf_scan", k, force_pallas)
    count_h2d("ivf_scan", span, q, corpus, kernel="ivf_scan",
              impl=impl_name(pallas))
    if pallas:
        vals, idx = ivf_scan_topk_pallas(q, corpus, k, metric=metric,
                                         block_n=block_n, n_valid=n_valid,
                                         interpret=not on_tpu())
    else:
        vals, idx = _scan_topk_xla(q, corpus, np.int32(n_valid), k, metric)
    return np.asarray(vals)[:qn], np.asarray(idx)[:qn]
