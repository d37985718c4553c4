"""Tile-local top-L, the step every kNN kernel ends in, and the per-tile
plumbing the scan kernels (``ivf_scan``, ``pq_scan``) share around it.

Two ways to take it, both exact with ties to the lower column (the order
``lax.top_k`` gives):

* :func:`topl_sweep`, L repeated max-extracts (``ivf_scan``,
  ``topk_merge``).  Each step takes the row max and the lowest column
  holding it, records both, and masks that column to ``NEG`` -- vectorized
  over the query rows.  The steps run as a ``fori_loop`` that writes into
  [Q, L] accumulators, so the chip's compiler sees one loop body instead of
  L unrolled copies of a [Q, BN] tile (a 64-step unroll took ~20 s to
  compile at Q=256).  The lowest column is taken with a min over the
  maxima, not ``argmax``: inside a TPU kernel argmax does not break exact
  ties toward the first column (a tied pair came back swapped against the
  oracle on a v5e).  Each step waits on two cross-lane reductions, so at
  small Q the sweep is bound by their latency, L times per tile.
* :func:`bitonic_topl`, a sorting network (``pq_scan``, L <= 128): 42
  compare-exchange stages of lane rotations and selects, none waiting on a
  reduction.  On a v5e, for a [Q, 512] tile at L = 80, it took 0.41x the
  sweep's time at Q = 32, 0.64x at 64, 0.87x at 128 and 1.11x at 256
  (PERF.md).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -3.0e38
_LANES = 128
_LOG_LANES = 7
_NO_COL = jnp.iinfo(jnp.int32).max


def topl_sweep(s: jnp.ndarray, cols: jnp.ndarray, topl: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[Q, C] scores (``cols`` its column iota) -> (vals [Q, L] f32,
    positions [Q, L] int32), descending."""
    qn = s.shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (qn, topl), 1)

    def step(l, carry):
        s, vals, pos = carry
        mx = jnp.max(s, axis=-1, keepdims=True)                     # [Q, 1]
        a = jnp.min(jnp.where(s == mx, cols, _NO_COL), axis=-1,
                    keepdims=True)                                  # [Q, 1]
        vals = jnp.where(slot == l, mx, vals)
        pos = jnp.where(slot == l, a, pos)
        return jnp.where(cols == a, NEG, s), vals, pos

    init = (s, jnp.zeros((qn, topl), jnp.float32),
            jnp.zeros((qn, topl), jnp.int32))
    _, vals, pos = jax.lax.fori_loop(0, topl, step, init)
    return vals, pos


def tile_topl(s, nv_ref, block_n: int, topl: int, vals_ref, idx_ref) -> None:
    """Tile-local top-L of one [Q, BN] score tile into its output block.
    Rows past n_valid (the dispatcher's block_n padding) are pinned to NEG
    first; n_valid is a scalar SMEM operand, so one compiled kernel serves
    every padding amount."""
    base = pl.program_id(0) * block_n
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols + base >= nv_ref[0], NEG, s)
    vals, pos = topl_sweep(s, cols, topl)
    vals_ref[...] = vals
    idx_ref[...] = pos + base


def _lane_iota():
    return jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)


def _exchange(v, i, j, desc):
    """One bitonic compare-exchange stage on [R, 128] (value, column)
    pairs: lane l meets lane l ^ j; where ``desc`` (a bool broadcastable to
    [R, 128]) the pair's lower lane keeps the better entry (higher value,
    then lower column), elsewhere the upper lane does."""
    lane = _lane_iota()
    # which of the two rotations brings lane l ^ j to lane l, read off a
    # rotated iota so the rotate's direction convention cannot matter
    fwd = pltpu.roll(lane, j, 1) == (lane ^ j)

    def partner(x):
        return jnp.where(fwd, pltpu.roll(x, j, 1),
                         pltpu.roll(x, _LANES - j, 1))

    pv, pi = partner(v), partner(i)
    better = (v > pv) | ((v == pv) & (i < pi))
    hold_better = jnp.logical_not(jnp.logical_xor((lane & j) == 0, desc))
    keep = jnp.logical_not(jnp.logical_xor(better, hold_better))
    return jnp.where(keep, v, pv), jnp.where(keep, i, pi)


def bitonic_topl(s: jnp.ndarray, base, topl: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[Q, C] scores of columns ``base`` .. ``base + C - 1`` (C = 128 * 2**p,
    L <= 128) -> (vals [Q, L], columns [Q, L]), descending, ties to the
    lower column: the order ``lax.top_k`` gives.

    A sorting network instead of L dependent max/mask steps: the C/128 lane
    chunks of every row are stacked on sublanes and each is sorted by a
    bitonic network (28 compare-exchange stages), even chunks descending
    and odd ones ascending; then pairs of chunks merge, each lane keeping
    the better of the two (a bitonic sequence that holds the pair's best
    128), and 7 more stages sort it, until one chunk is left.  Every stage
    is lane rotations and selects over the whole tile at once, with no
    reduction on its critical path.  Column ids are built per chunk, never
    sliced out of a wider iota (which Mosaic cannot lay out)."""
    q, c = s.shape
    n = c // _LANES
    assert n * _LANES == c and n & (n - 1) == 0 and topl <= _LANES, (c, topl)
    lane = _lane_iota()
    v = jnp.concatenate([s[:, t * _LANES:(t + 1) * _LANES]
                         for t in range(n)], axis=0)            # [n*Q, 128]
    row = jax.lax.broadcasted_iota(jnp.int32, (n * q, _LANES), 0)
    i = (jax.lax.broadcasted_iota(jnp.int32, (n * q, _LANES), 1)
         + (row // q) * _LANES + base)

    def row_desc(chunks):                       # [chunks*q, 1]: even chunks
        r = jax.lax.broadcasted_iota(jnp.int32, (chunks * q, 1), 0)
        return (r // q) % 2 == 0

    def stages(v, i, log_hi, desc_of):
        """Exchanges at lane distances 2**log_hi, .., 2, 1 (a loop: the
        unrolled network took the chip's compiler ~40 s at Q=256)."""
        log_hi = jnp.asarray(log_hi, jnp.int32)

        def body(t, vi):
            j = jnp.left_shift(1, log_hi - t)
            return _exchange(*vi, j, desc_of(j))
        return jax.lax.fori_loop(0, log_hi + 1, body, (v, i))

    desc = row_desc(n)

    def sort_level(a, vi):                      # blocks of 2**a lanes
        k = jnp.left_shift(1, a)
        # blocks of k lanes alternate direction; the last (k = 128) takes
        # the chunk's own
        block_desc = jnp.logical_not(jnp.logical_xor((lane & k) == 0, desc))
        return stages(*vi, a - 1, lambda j: block_desc)

    v, i = jax.lax.fori_loop(1, _LOG_LANES + 1, sort_level, (v, i))
    while n > 1:                                # merge chunk pairs
        n //= 2
        ev, ei, ov, oi = (jnp.concatenate(
            [x[(2 * t + odd) * q:(2 * t + odd + 1) * q] for t in range(n)],
            axis=0) for odd in (0, 1) for x in (v, i))
        take = (ev > ov) | ((ev == ov) & (ei < oi))
        v, i = jnp.where(take, ev, ov), jnp.where(take, ei, oi)
        desc = row_desc(n)
        v, i = stages(v, i, _LOG_LANES - 1, lambda j, d=desc: d)
    return v[:, :topl], i[:, :topl]


def tile_topl_sorted(s, nv_ref, block_n: int, topl: int, vals_ref,
                     idx_ref) -> None:
    """:func:`tile_topl` through :func:`bitonic_topl` (L <= 128, block_n a
    power-of-two multiple of 128)."""
    base = pl.program_id(0) * block_n
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + base
    s = jnp.where(cols >= nv_ref[0], NEG, s)
    vals_ref[...], idx_ref[...] = bitonic_topl(s, base, topl)


def n_valid_operand(n_valid, n: int) -> jnp.ndarray:
    """n_valid (-1: all ``n`` rows) as the (1,) int32 SMEM operand."""
    return jnp.where(n_valid < 0, n, n_valid).astype(jnp.int32).reshape(1)


def tile_outputs(n_tiles: int, qn: int, k: int):
    """Out specs and shapes of the per-tile partials, laid out [tile, Q, k]:
    a (Q, k) block over a (Q, n_tiles*k) array breaks the TPU rule that a
    block's last dim is a multiple of 128 or the whole axis.  The index map
    ignores any scalar-prefetch refs a grid spec hands it."""
    specs = [pl.BlockSpec((None, qn, k), lambda i, *_: (i, 0, 0))] * 2
    shapes = [jax.ShapeDtypeStruct((n_tiles, qn, k), jnp.float32),
              jax.ShapeDtypeStruct((n_tiles, qn, k), jnp.int32)]
    return specs, shapes


def merge_tiles(vals: jnp.ndarray, idx: jnp.ndarray, k: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Epilogue: merge the [n_tiles, Q, k] per-tile partials (tiny).
    Tile-major columns keep lax.top_k's lower-row-first tie order."""
    n_tiles, qn, _ = vals.shape
    vals = jnp.transpose(vals, (1, 0, 2)).reshape(qn, n_tiles * k)
    idx = jnp.transpose(idx, (1, 0, 2)).reshape(qn, n_tiles * k)
    mv, mi = jax.lax.top_k(vals, k)
    return mv, jnp.take_along_axis(idx, mi, axis=1)
