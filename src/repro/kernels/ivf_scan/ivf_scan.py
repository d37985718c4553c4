"""IVF scan Pallas kernel: fused similarity + per-tile top-L (TPU).

The paper's kNN hot loop (§VI-B2 / Appendix C) re-blocked for the MXU:
corpus tiles stream HBM -> VMEM; each grid step computes a [Q, BN] score
tile with one MXU matmul (L2 via the ||q||^2 - 2qc + ||c||^2 identity, norms
fused), then keeps the tile-local top-L via L vectorized max/mask sweeps --
no data-dependent control flow, no cross-tile traffic.  A tiny jnp epilogue
merges the [n_tiles, L] partials (exactly the TPU-KNN two-phase shape).

VMEM working set per grid step (defaults Q<=128, BN=512, d<=256, fp32):
  q 128x256 (128 kB) + tile 512x256 (512 kB) + scores 128x512 (256 kB)
  + out tiles  -> well under the ~16 MB VMEM budget.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sweep import (merge_tiles, n_valid_operand,
                                 tile_outputs, tile_topl)


def _ivf_kernel(nv_ref, q_ref, c_ref, c2_ref, vals_ref, idx_ref, *,
                metric: str, topl: int, block_n: int):
    qf = q_ref[...].astype(jnp.float32)            # [Q, d]
    cf = c_ref[...].astype(jnp.float32)            # [BN, d]
    s = jax.lax.dot_general(qf, cf, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, BN]
    if metric == "l2":
        q2 = jnp.sum(qf * qf, axis=-1, keepdims=True)
        s = -(q2 - 2.0 * s + c2_ref[...])          # c2 tile is [1, BN]
    tile_topl(s, nv_ref, block_n, topl, vals_ref, idx_ref)


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "block_n", "interpret"))
def ivf_scan_topk_pallas(q: jnp.ndarray, corpus: jnp.ndarray, k: int,
                         metric: str = "l2", block_n: int = 512,
                         n_valid=-1, interpret: bool = True
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[Q, d] x [N, d] -> (vals [Q, k], ids [Q, k]); N % block_n == 0.

    ``n_valid`` (< N; traced, so it never forces a recompile) marks the
    tail rows as padding: their scores are pinned to ``NEG`` inside the
    kernel, so the dispatcher can pad any corpus up to a block_n multiple
    without padded rows ever reaching the top-k.  The caller keeps
    k <= n_valid."""
    qn, d = q.shape
    n = corpus.shape[0]
    assert n % block_n == 0, (n, block_n)
    n_tiles = n // block_n
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
        corpus = corpus / jnp.maximum(
            jnp.linalg.norm(corpus, axis=-1, keepdims=True), 1e-9)
        metric = "ip"
    # [1, N]: a 1-D tile of block_n lanes has no TPU layout XLA and Mosaic
    # agree on; a 2-D row does
    c2 = jnp.sum(corpus.astype(jnp.float32) ** 2, axis=-1)[None, :]

    kernel = functools.partial(_ivf_kernel, metric=metric, topl=k,
                               block_n=block_n)
    out_specs, out_shape = tile_outputs(n_tiles, qn, k)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # n_valid
            pl.BlockSpec((qn, d), lambda i: (0, 0)),        # q: resident
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),   # corpus tile
            pl.BlockSpec((1, block_n), lambda i: (0, i)),   # ||c||^2 tile
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(n_valid_operand(n_valid, n), q, corpus, c2)
    return merge_tiles(vals, idx, k)
