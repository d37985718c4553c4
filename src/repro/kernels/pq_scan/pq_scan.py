"""PQ ADC scan Pallas kernels: fused LUT-sum + per-tile top-L (TPU).

The bandwidth-bound half of the PQ kNN hot loop: corpus *codes* (M bytes
per row instead of 4d float bytes) stream HBM -> VMEM in block_n tiles;
each grid step turns its code tile into a one-hot [M*K, BN] matrix (an iota
compare -- no gather, which the MXU path cannot do cheaply) and contracts
the flattened query LUTs [Q, M*K] against it on the MXU, yielding the
[Q, BN] ADC score tile.  A bitonic network then keeps the tile's top-L
(``sweep.bitonic_topl``, L <= 128) -- no cross-tile traffic -- and a jnp
epilogue, inside the same jitted program, merges the [n_tiles, L] partials.

The *extended* kernel scores the residual / fused decomposition

    s[q, n] = LUT sum + bias[n] + cscores[q, row_bucket[n]],
    masked to NEG where probe_mask[q, row_bucket[n]] is False

with the same one-hot trick on the bucket axis: a [128, BN] one-hot of the
tile's bucket ids contracts against a 128-bucket block of ``cscores`` /
``probe_mask``.  A tile only visits the blocks its rows' buckets fall in
(their range per tile rides in as scalar prefetch), so a code table sorted
by bucket -- as a compacted index is -- pays for one or two blocks per tile
instead of the whole bucket axis, and an unsorted one is still exact.  A
plain ADC scan is its case of zero bias and centroid terms over one
all-probed bucket, which ``ops.pq_adc_topk`` fills in.

The *row-masked* kernel (filtered kNN) keeps the LUT sum, bias and
centroid term and replaces the probe mask by a per-(query, row) allow bit:
each tile reads its [ceil(Q/32), BN] slice of a packed int32 mask, spreads
every word over 32 sublanes with a shift, and pins the rows a query's
filter excludes to ``NEG`` before the top-L.

Precision.  Scores are float32 sums, as the XLA twin's gathers give: the
one-hot is exact in bfloat16, and every float operand (LUTs, centroid
terms) is split on the way in into three bfloat16 pieces whose sum is the
float32 value exactly (:func:`split3`), so three single bf16 MXU passes
with float32 accumulation carry all 24 significand bits.  The centroid
term comes out bit-identical to the gather (one nonzero product per
column); the LUT sum differs from a sequential float32 sum only by the
order of its float32 roundings.

VMEM: the LUT pieces and bucket blocks stay resident (one copy each); the
one-hot, the score pieces and the sweep state are per tile.  At Q=256,
M=16, K=256, BN=512, 1 420 buckets that is ~27 MiB, past the 16 MiB
default scoped limit, so each call sets its limit from its own shapes
(:func:`_vmem_limit`).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sweep import (NEG, merge_tiles, n_valid_operand,
                                 tile_outputs, tile_topl_sorted)

BUCKET_BLOCK = 128      # buckets per block of the bucket axis (one lane row)
_Q_ALIGN = 16           # bf16 sublane tile: query pieces stack on it
_HI16 = -65536          # 0xFFFF0000 as int32: a float32's bf16-exact top half


def split3(x: jnp.ndarray) -> jnp.ndarray:
    """float32 [R, C] -> bfloat16 [3R, C], rows (hi; mid; lo) with
    hi + mid + lo == x exactly.  Each piece is cut with a bit mask, never a
    float round trip, so the compiler's excess-precision rewrites (which
    may drop an f32 -> bf16 -> f32 pair) cannot fold it away."""
    def top16(v):
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(v, jnp.int32) & _HI16, jnp.float32)
    x = x.astype(jnp.float32)
    hi = top16(x)
    r = x - hi                      # the low 16 significand bits, exact
    mid = top16(r)
    lo = r - mid                    # at most 8 significant bits
    return jnp.concatenate([hi, mid, lo]).astype(jnp.bfloat16)


def _lut_scores(luts_ref, codes_ref, ksub: int, qp: int) -> jnp.ndarray:
    """[Q, BN] float32 ADC scores of one code tile: one-hot the codes,
    onehot[j*K + c, n] = (codes[j, n] == c), and contract the three LUT
    pieces [3Q, M*K] against it in one MXU call."""
    codes = codes_ref[...]                                 # [M, BN] int32
    m, bn = codes.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (m, ksub, bn), 1)
    onehot = jnp.where(codes[:, None, :] == iota, 1.0, 0.0)
    onehot = onehot.astype(jnp.bfloat16).reshape(m * ksub, bn)
    r = jnp.dot(luts_ref[...], onehot, preferred_element_type=jnp.float32)
    return (r[2 * qp:] + r[qp:2 * qp]) + r[:qp]            # (lo+mid)+hi


def _bucket_terms(blo_ref, bhi_ref, rb_ref, bt_ref, acc_ref,
                  block_n: int) -> jnp.ndarray:
    """For each 128-bucket block this tile's rows fall in, one-hot the
    rows' bucket ids and contract the block's bucket rows [R, 128]
    (centroid-term pieces, and the probe mask where there is one) against
    it: [R, BN] float32."""
    rb = rb_ref[...]                                       # [1, BN] int32
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def block(b, carry):
        iota = jax.lax.broadcasted_iota(jnp.int32, (BUCKET_BLOCK, block_n),
                                        0) + b * BUCKET_BLOCK
        onehot = jnp.where(iota == rb, 1.0, 0.0).astype(jnp.bfloat16)
        acc_ref[...] += jnp.dot(bt_ref[b], onehot,
                                preferred_element_type=jnp.float32)
        return carry

    i = pl.program_id(0)
    jax.lax.fori_loop(blo_ref[i], bhi_ref[i] + 1, block, 0)
    return acc_ref[...]


def _pq_kernel_ext(nv_ref, blo_ref, bhi_ref, luts_ref, codes_ref, bias_ref,
                   rb_ref, bt_ref, vals_ref, idx_ref, acc_ref, *, topl: int,
                   block_n: int, ksub: int, qp: int):
    s = _lut_scores(luts_ref, codes_ref, ksub, qp)                # [Q, BN]
    acc = _bucket_terms(blo_ref, bhi_ref, rb_ref, bt_ref, acc_ref, block_n)
    cterm = (acc[2 * qp:3 * qp] + acc[qp:2 * qp]) + acc[:qp]
    s = (s + bias_ref[...]) + cterm                       # bias tile [1, BN]
    s = jnp.where(acc[3 * qp:] > 0.5, s, NEG)
    tile_topl_sorted(s, nv_ref, block_n, topl, vals_ref, idx_ref)


def unpack_row_mask(words: jnp.ndarray, qn: int) -> jnp.ndarray:
    """[W, C] int32 words -> [qn, C] bool: bit j of word [w, c] is query
    32*w + j's allow bit for column c (the layout of ``ops.pack_row_mask``).
    Each word row is broadcast over 32 sublanes and shifted by their index,
    so no lane ever moves."""
    c = words.shape[1]
    shift = jax.lax.broadcasted_iota(jnp.int32, (32, c), 0)
    bits = jnp.concatenate([(words[w:w + 1] >> shift) & 1
                            for w in range(words.shape[0])])
    return bits[:qn] != 0


def _pq_kernel_rowmask(nv_ref, blo_ref, bhi_ref, luts_ref, codes_ref,
                       bias_ref, rb_ref, bt_ref, mask_ref, vals_ref, idx_ref,
                       acc_ref, *, topl: int, block_n: int, ksub: int,
                       qp: int):
    s = _lut_scores(luts_ref, codes_ref, ksub, qp)                # [Q, BN]
    acc = _bucket_terms(blo_ref, bhi_ref, rb_ref, bt_ref, acc_ref, block_n)
    cterm = (acc[2 * qp:3 * qp] + acc[qp:2 * qp]) + acc[:qp]
    s = (s + bias_ref[...]) + cterm                       # bias tile [1, BN]
    s = jnp.where(unpack_row_mask(mask_ref[...], qp), s, NEG)
    tile_topl_sorted(s, nv_ref, block_n, topl, vals_ref, idx_ref)


def _vmem_limit(qp: int, mk: int, block_n: int, k: int,
                bucket_bytes: int) -> int:
    """Scoped VMEM for one call, from its shapes: the resident LUT pieces
    and bucket blocks, the tile's one-hot (float32 compare and bf16 copy),
    score pieces, bucket accumulator and sweep state, and double-buffered
    tiles; with half again as headroom."""
    need = (3 * qp * mk * 2 + bucket_bytes          # resident operands
            + mk * block_n * 6                      # one-hot
            + (3 + 4 + 4) * qp * block_n * 4        # pieces, acc, sweep
            + 2 * (mk // 16 + 2) * block_n * 4      # code/bias/bucket tiles
            + 2 * 2 * qp * k * 4)                   # out tiles
    return int(min(max(need * 3 // 2, 32 << 20), 100 << 20))


def _bucket_scan(kernel, luts, codes, bias, row_bucket, cscores, masks,
                 tiled, k: int, block_n: int, n_valid, interpret: bool):
    """The two scans' shared pallas_call: LUT pieces, code / bias /
    bucket tiles and the bucket operand -- the centroid-term pieces
    followed by ``masks`` ([R, MB] rows per query: the probe mask, or
    none) cut into 128-bucket blocks -- then ``tiled``, further [rows, N]
    operands tiled along the rows (the packed row mask, or none)."""
    qn, m, ksub = luts.shape
    n = codes.shape[0]
    assert codes.shape[1] == m, (codes.shape, m)
    assert n % block_n == 0, (n, block_n)
    mb = cscores.shape[1]
    # query rows padded to the bf16 sublane tile, the LUTs flattened and
    # split into their three bf16 pieces, the codes laid out [M, N] so each
    # tile's one-hot is a sublane broadcast
    qp = -(-qn // _Q_ALIGN) * _Q_ALIGN
    luts = jnp.pad(luts.astype(jnp.float32), ((0, qp - qn), (0, 0), (0, 0)))
    luts3 = split3(luts.reshape(qp, m * ksub))
    codes_t = codes.astype(jnp.int32).T
    n_tiles = n // block_n
    nblk = -(-mb // BUCKET_BLOCK)
    out_specs, out_shape = tile_outputs(n_tiles, qp, k)

    # bucket operand: (cscores hi; mid; lo; masks) rows, cut into 128-bucket
    # blocks [nblk, R*Q, 128] so a block is a leading-axis index
    pad = ((0, qp - qn), (0, nblk * BUCKET_BLOCK - mb))
    bt = jnp.concatenate(
        [split3(jnp.pad(cscores.astype(jnp.float32), pad))]
        + [jnp.pad(x.astype(jnp.float32), pad).astype(jnp.bfloat16)
           for x in masks])
    rows = bt.shape[0]
    bt = bt.reshape(rows, nblk, BUCKET_BLOCK).transpose(1, 0, 2)
    # each tile's range of bucket blocks over its real rows; an all-padding
    # tile gets an empty range
    nv = n_valid_operand(n_valid, n)
    rb = row_bucket.astype(jnp.int32)
    real = jnp.arange(n) < nv[0]
    blo = jnp.min(jnp.where(real, rb, mb).reshape(n_tiles, block_n), axis=1)
    bhi = jnp.max(jnp.where(real, rb, -1).reshape(n_tiles, block_n), axis=1)
    blo = jnp.clip(blo // BUCKET_BLOCK, 0, nblk)
    bhi = jnp.clip(bhi // BUCKET_BLOCK, -1, nblk - 1)

    kernel = functools.partial(kernel, topl=k, block_n=block_n, ksub=ksub,
                               qp=qp)
    tile = lambda i, *_: (0, i)                                  # noqa: E731
    # bias and row_bucket ride as [1, N] rows: 1-D tiles of block_n lanes
    # have no TPU layout XLA and Mosaic agree on, and an [N, 1] column
    # would pad every row out to 128 lanes in HBM
    vals, idx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                  # n_valid, block ranges
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),   # LUT pieces
                pl.BlockSpec((m, block_n), tile),        # code tile
                pl.BlockSpec((1, block_n), tile),        # bias tile
                pl.BlockSpec((1, block_n), tile),        # bucket tile
                pl.BlockSpec(memory_space=pltpu.VMEM),   # bucket blocks
            ] + [pl.BlockSpec((x.shape[0], block_n), tile) for x in tiled],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((rows, block_n), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(qp, m * ksub, block_n, k,
                                         bucket_bytes=bt.size * 2)),
        interpret=interpret,
    )(nv, blo.astype(jnp.int32), bhi.astype(jnp.int32), luts3, codes_t,
      bias.astype(jnp.float32)[None, :], rb[None, :], bt, *tiled)
    return merge_tiles(vals[:, :qn], idx[:, :qn], k)


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
    assert probe_mask.shape == cscores.shape, (probe_mask.shape,
                                               cscores.shape)
    return _bucket_scan(_pq_kernel_ext, luts, codes, bias, row_bucket,
                        cscores, [probe_mask], [], k, block_n, n_valid,
                        interpret)


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def pq_adc_topk_rowmask_pallas(luts: jnp.ndarray, codes: jnp.ndarray,
                               bias: jnp.ndarray, row_bucket: jnp.ndarray,
                               cscores: jnp.ndarray, row_mask: jnp.ndarray,
                               k: int, block_n: int = 512, n_valid=-1,
                               interpret: bool = True
                               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-masked ADC scan (filtered kNN): LUT sum + bias[n] +
    cscores[q, row_bucket[n]] over every bucket, each query's rows outside
    its filter pinned to ``NEG`` before the tile top-L.  ``row_mask`` is
    the packed allow bits [ceil(Q/32), N] int32 of ``ops.pack_row_mask``
    (bit j of word [w, n]: query 32*w + j may return row n).  Shapes
    otherwise as :func:`pq_adc_topk_ext_pallas`, without a probe mask."""
    qp = -(-luts.shape[0] // _Q_ALIGN) * _Q_ALIGN
    words = -(-qp // 32)
    assert row_mask.shape[1] == codes.shape[0], (row_mask.shape,
                                                 codes.shape)
    row_mask = jnp.pad(row_mask.astype(jnp.int32),
                       ((0, words - row_mask.shape[0]), (0, 0)))
    return _bucket_scan(_pq_kernel_rowmask, luts, codes, bias, row_bucket,
                        cscores, [], [row_mask], k, block_n, n_valid,
                        interpret)
