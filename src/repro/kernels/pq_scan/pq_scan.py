"""PQ ADC scan Pallas kernel: fused LUT-sum + per-tile top-L (TPU).

The bandwidth-bound half of the PQ kNN hot loop: corpus *codes* (uint8, M
bytes per row instead of 4d float bytes) stream HBM -> VMEM in block_n
tiles; each grid step turns its code tile into a one-hot [BN, M*K] matrix
in registers (an iota compare -- no gather, which the MXU path cannot do
cheaply) and contracts it against the flattened query LUTs [Q, M*K] with
ONE MXU matmul, yielding the [Q, BN] ADC score tile.  Tile-local top-L then
runs the same L vectorized max/mask sweeps as ``ivf_scan`` -- no
data-dependent control flow, no cross-tile traffic -- and a tiny jnp
epilogue merges the [n_tiles, L] partials.

The *extended* kernel adds the residual / fused score decomposition

    s[q, n] = LUT sum + bias[n] + cscores[q, row_bucket[n]],
    masked to -inf where probe_mask[q, row_bucket[n]] is False

with the same one-hot trick on the bucket axis: a [BN, MB] bucket one-hot
contracts against ``cscores`` / ``probe_mask`` [Q, MB] in two more MXU
passes -- no per-lane gather, and the fused probe->ADC->top-k pipeline can
scan the *whole* code table in one call with non-probed buckets masked
in-kernel.

VMEM working set per grid step (Q<=128, BN=512, M=8, K=256, fp32):
  luts 128x2048 (1 MB) + codes 512x8 (16 kB int32) + onehot 512x2048 (4 MB)
  + scores 128x512 (256 kB)  -> comfortably under the ~16 MB VMEM budget.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sweep import (NEG, merge_tiles, n_valid_operand,
                                 tile_outputs, tile_topl)


def _adc_tile(luts_ref, codes_ref, ksub: int):
    """[Q, BN] ADC scores of one code tile: one-hot the codes,
    onehot[n, j*K + c] = (codes[n, j] == c), and contract against the
    flattened LUTs.  An iota compare keeps everything dense/vectorized --
    the TPU has no cheap per-lane gather, but a [Q, M*K] x [M*K, BN]
    contraction is one MXU pass."""
    codes = codes_ref[...].astype(jnp.int32)              # [BN, M]
    bn, m = codes.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, m, ksub), 2)
    onehot = (codes[:, :, None] == iota).astype(jnp.float32)
    onehot = onehot.reshape(bn, m * ksub)
    return jax.lax.dot_general(luts_ref[...], onehot,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pq_kernel(nv_ref, luts_ref, codes_ref, vals_ref, idx_ref, *, topl: int,
               block_n: int, ksub: int):
    s = _adc_tile(luts_ref, codes_ref, ksub)                      # [Q, BN]
    tile_topl(s, nv_ref, block_n, topl, vals_ref, idx_ref)


def _pq_kernel_ext(nv_ref, luts_ref, codes_ref, bias_ref, rb_ref, cs_ref,
                   pm_ref, vals_ref, idx_ref, *, topl: int, block_n: int,
                   ksub: int, mb: int):
    s = _adc_tile(luts_ref, codes_ref, ksub)                      # [Q, BN]
    # bucket terms: one-hot the per-row bucket id and contract the per-query
    # centroid scores / probe mask against it -- two more MXU passes instead
    # of a per-lane gather
    rb = rb_ref[...]                                      # [1, BN] int32
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (mb, rb.shape[1]), 0)
    onehot_b = (iota_b == rb).astype(jnp.float32)                 # [MB, BN]
    cterm = jax.lax.dot_general(cs_ref[...], onehot_b,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    mterm = jax.lax.dot_general(pm_ref[...], onehot_b,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    s = s + cterm + bias_ref[...]                         # bias tile [1, BN]
    s = jnp.where(mterm > 0.5, s, NEG)
    tile_topl(s, nv_ref, block_n, topl, vals_ref, idx_ref)


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def pq_adc_topk_pallas(luts: jnp.ndarray, codes: jnp.ndarray, k: int,
                       block_n: int = 512, n_valid=-1,
                       interpret: bool = True
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[Q, M, K] x [N, M] -> (vals [Q, k], ids [Q, k]); N % block_n == 0.

    ``n_valid`` (< N; traced) marks the tail rows as padding: their scores
    are pinned to ``NEG`` inside the kernel, so the dispatcher can pad any
    code table up to a block_n multiple without padded rows ever reaching
    the top-k.  The caller keeps k <= n_valid."""
    qn, m, ksub = luts.shape
    n = codes.shape[0]
    assert codes.shape[1] == m, (codes.shape, m)
    assert n % block_n == 0, (n, block_n)
    n_tiles = n // block_n
    luts_flat = luts.astype(jnp.float32).reshape(qn, m * ksub)
    out_specs, out_shape = tile_outputs(n_tiles, qn, k)

    kernel = functools.partial(_pq_kernel, topl=k, block_n=block_n,
                               ksub=ksub)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # n_valid
            pl.BlockSpec((qn, m * ksub), lambda i: (0, 0)),  # luts: resident
            pl.BlockSpec((block_n, m), lambda i: (i, 0)),    # code tile
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(n_valid_operand(n_valid, n), luts_flat, codes.astype(jnp.int32))
    return merge_tiles(vals, idx, k)


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def pq_adc_topk_ext_pallas(luts: jnp.ndarray, codes: jnp.ndarray,
                           bias: jnp.ndarray, row_bucket: jnp.ndarray,
                           cscores: jnp.ndarray, probe_mask: jnp.ndarray,
                           k: int, block_n: int = 512, n_valid=-1,
                           interpret: bool = True
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Extended ADC scan: LUT sum + bias[n] + cscores[q, row_bucket[n]],
    rows of non-probed buckets (probe_mask False) pinned to ``NEG``.
    Shapes: luts [Q, M, K], codes [N, M], bias [N], row_bucket [N] in
    [0, MB), cscores/probe_mask [Q, MB]; N % block_n == 0."""
    qn, m, ksub = luts.shape
    n = codes.shape[0]
    mb = cscores.shape[1]
    assert codes.shape[1] == m, (codes.shape, m)
    assert n % block_n == 0, (n, block_n)
    assert probe_mask.shape == cscores.shape, (probe_mask.shape,
                                               cscores.shape)
    n_tiles = n // block_n
    luts_flat = luts.astype(jnp.float32).reshape(qn, m * ksub)
    out_specs, out_shape = tile_outputs(n_tiles, qn, k)

    kernel = functools.partial(_pq_kernel_ext, topl=k, block_n=block_n,
                               ksub=ksub, mb=mb)
    # bias and row_bucket ride as [1, N] rows: 1-D tiles of block_n lanes
    # have no TPU layout XLA and Mosaic agree on, and an [N, 1] column
    # would pad every row out to 128 lanes in HBM
    vals, idx = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # n_valid
            pl.BlockSpec((qn, m * ksub), lambda i: (0, 0)),  # luts: resident
            pl.BlockSpec((block_n, m), lambda i: (i, 0)),    # code tile
            pl.BlockSpec((1, block_n), lambda i: (0, i)),    # bias tile
            pl.BlockSpec((1, block_n), lambda i: (0, i)),    # bucket tile
            pl.BlockSpec((qn, mb), lambda i: (0, 0)),        # cscores: res
            pl.BlockSpec((qn, mb), lambda i: (0, 0)),        # mask: res
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(n_valid_operand(n_valid, n), luts_flat, codes.astype(jnp.int32),
      bias.astype(jnp.float32)[None, :],
      row_bucket.astype(jnp.int32)[None, :], cscores.astype(jnp.float32),
      probe_mask.astype(jnp.float32))
    return merge_tiles(vals, idx, k)
