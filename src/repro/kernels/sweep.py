"""Top-L by repeated max-extract: the sweep every kNN kernel ends in, and
the per-tile plumbing the two scan kernels (``ivf_scan``, ``pq_scan``)
share around it.

Each of the L steps takes the row max and the lowest column holding it,
records both, and masks that column to ``NEG`` -- vectorized over the query
rows, no data-dependent control flow.  The steps run as a ``fori_loop`` that
writes into [Q, L] accumulators, so the chip's compiler sees one loop body
instead of L unrolled copies of a [Q, BN] tile (a 64-step unroll took
~20 s to compile at Q=256; the loop compiles in about a second at any L).
Ties resolve to the lower column, the order ``lax.top_k`` gives.  The
lowest column is taken with a min over the maxima, not ``argmax``: inside a
TPU kernel argmax does not break exact ties toward the first column (a tied
pair came back swapped against the oracle on a v5e).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.0e38
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


def n_valid_operand(n_valid, n: int) -> jnp.ndarray:
    """n_valid (-1: all ``n`` rows) as the (1,) int32 SMEM operand."""
    return jnp.where(n_valid < 0, n, n_valid).astype(jnp.int32).reshape(1)


def tile_outputs(n_tiles: int, qn: int, k: int):
    """Out specs and shapes of the per-tile partials, laid out [tile, Q, k]:
    a (Q, k) block over a (Q, n_tiles*k) array breaks the TPU rule that a
    block's last dim is a multiple of 128 or the whole axis."""
    specs = [pl.BlockSpec((None, qn, k), lambda i: (i, 0, 0))] * 2
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
