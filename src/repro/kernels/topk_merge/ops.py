"""Dispatching wrapper: Pallas merge kernel on TPU, jnp twin elsewhere.

The k-way merge is one fused device call either way -- the point is that
the coordinator's reduce step stops being four host-side array ops under
the GIL per batch.  Shard windows are tiny ([P, Q, K] with C = P*K a few
hundred), so the whole candidate set stays resident per query tile and the
kernel's top-k sweep is global -- no cross-tile epilogue.

Shard padding arrives as (val=-inf, id=-1) columns *inside* the input (a
shard with fewer than K real rows), not only as a tail: the kernel clamps
inputs to ``CLAMP`` so -inf columns stay selectable exactly once (the
in-sweep mask value ``NEG`` sits strictly below), and the wrapper restores
-inf on the way out.  Ties therefore resolve to the lower flattened column
-- identical to ``lax.top_k`` on the raw -inf scores -- and all-padding
merges reproduce the oracle byte-for-byte.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import on_tpu, use_pallas
from repro.kernels.topk_merge.topk_merge import merge_topk_pallas


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_topk_xla(flat_v: jnp.ndarray, flat_i: jnp.ndarray,
                    n_valid: jnp.ndarray, k: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jitted XLA twin of the kernel: padding mask + top-k + id gather in
    one dispatch.  ``n_valid`` is traced, so every [Q, C] shape compiles
    once and serves any shard-axis padding amount."""
    cols = jnp.arange(flat_v.shape[1])[None, :]
    s = jnp.where(cols >= n_valid, -jnp.inf, flat_v)
    mv, pos = jax.lax.top_k(s, k)
    return mv, jnp.take_along_axis(flat_i, pos, axis=1)


def merge_topk_dev(vals: jnp.ndarray, ids: jnp.ndarray, k: int,
                   block_q: int = 128, n_valid: int = -1,
                   force_pallas: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[P, Q, K] x [P, Q, K] -> (vals [Q, k'], ids [Q, k']), k' = min(k, C).

    Flattened candidate columns at positions >= ``n_valid`` (default: all
    C = P*K of them) are treated as padding and excluded; column p*K + j is
    shard p's rank-j candidate.  (-inf, -1) padding *within* the window --
    a shard holding fewer than K real rows -- flows through: -inf entries
    sink below every real candidate and surface in ascending column order,
    so the merged prefix is always the real global top-k and callers
    truncate the tail to the real candidate count."""
    p, qn, kk = vals.shape
    c = p * kk
    if n_valid < 0 or n_valid > c:
        n_valid = c
    k = min(k, n_valid)
    if k <= 0:
        return (jnp.zeros((qn, 0), jnp.float32),
                jnp.zeros((qn, 0), jnp.int32))
    flat_v = jnp.transpose(jnp.asarray(vals, jnp.float32),
                           (1, 0, 2)).reshape(qn, c)
    flat_i = jnp.transpose(jnp.asarray(ids), (1, 0, 2)).reshape(qn, c)
    if use_pallas("topk_merge", k, force_pallas):
        pad = (-qn) % block_q
        if pad:
            flat_v = jnp.pad(flat_v, ((0, pad), (0, 0)))
            flat_i = jnp.pad(flat_i, ((0, pad), (0, 0)))
        mv, mi = merge_topk_pallas(flat_v, flat_i, k, block_q=block_q,
                                   n_valid=n_valid,
                                   interpret=not on_tpu())
        return mv[:qn], mi[:qn]
    return _merge_topk_xla(flat_v, flat_i, jnp.int32(n_valid), k)
