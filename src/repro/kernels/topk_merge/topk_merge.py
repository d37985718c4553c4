"""k-way top-k merge Pallas kernel: the cluster reduce step on-device (TPU).

Input is the flattened shard window ``flat_v [Q, C]`` (C = P*K candidate
columns per query, column p*K + j = shard p's rank-j value).  Queries tile
over the grid in ``block_q`` rows; the whole candidate axis is resident (C
is a few hundred), so each grid step runs a *global* top-k sweep for its
query tile -- k vectorized max/argmax/mask passes, exactly the ``ivf_scan``
sweep shape -- and there is no cross-tile epilogue.

Two sentinels keep shard padding honest without data-dependent control
flow.  Shard windows carry (val=-inf, id=-1) columns wherever a shard held
fewer than K real rows, and -inf is *below* the in-sweep mask value ``NEG``
-- a naive sweep would re-select the same all-padding column k times
(masking it to NEG *raises* it back above its -inf neighbours).  So inputs
are first clamped up to ``CLAMP`` (> NEG): every padding column becomes a
selectable CLAMP tie, the sweep consumes them left-to-right exactly once
each -- matching ``lax.top_k``'s lower-index-first tie order on the raw
-inf scores -- and the wrapper restores -inf on the way out.  Values at or
below CLAMP (-1e38) are indistinguishable from padding; real similarity
scores never live there.

VMEM working set per grid step (BQ=128, C<=8*320, fp32):
  flat_v 128x2560 (1.3 MB) + sweep state  -> well under the ~16 MB budget.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sweep import NEG, topl_sweep  # NEG: in-sweep mask

CLAMP = -1.0e38   # input floor: -inf padding clamps here, above NEG


def _merge_kernel(v_ref, vals_ref, pos_ref, *, topl: int, n_valid: int,
                  c_total: int):
    s = jnp.maximum(v_ref[...].astype(jnp.float32), CLAMP)     # [BQ, C]
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if n_valid < c_total:
        # columns past n_valid are shard-axis padding (dispatcher contract);
        # k <= n_valid, so the sweep never runs out of CLAMP-or-better
        # columns and NEG-masked ones are never selected
        s = jnp.where(cols >= n_valid, NEG, s)
    vals_ref[...], pos_ref[...] = topl_sweep(s, cols, topl)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "n_valid", "interpret"))
def merge_topk_pallas(flat_v: jnp.ndarray, flat_i: jnp.ndarray, k: int,
                      block_q: int = 128, n_valid: int = -1,
                      interpret: bool = True
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[Q, C] x [Q, C] -> (vals [Q, k], ids [Q, k]); Q % block_q == 0.

    ``n_valid`` (< C) marks trailing candidate columns as shard-axis
    padding: they are pinned to ``NEG`` inside the kernel and can never be
    selected (the dispatcher guarantees k <= n_valid).  Returned values at
    (-inf, id) padding positions are restored to -inf; ids carry whatever
    payload the column held (the shards' -1 padding contract)."""
    qn, c = flat_v.shape
    assert qn % block_q == 0, (qn, block_q)
    if n_valid < 0:
        n_valid = c
    assert k <= n_valid, (k, n_valid)
    q_tiles = qn // block_q

    kernel = functools.partial(_merge_kernel, topl=k, n_valid=n_valid,
                               c_total=c)
    vals, pos = pl.pallas_call(
        kernel,
        grid=(q_tiles,),
        in_specs=[
            pl.BlockSpec((block_q, c), lambda i: (i, 0)),   # query tile
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, k), jnp.float32),
            jax.ShapeDtypeStruct((qn, k), jnp.int32),
        ],
        interpret=interpret,
    )(flat_v)

    # epilogue: gather id payloads + restore the -inf the clamp absorbed
    ids = jnp.take_along_axis(flat_i, pos, axis=1)
    vals = jnp.where(vals <= CLAMP, -jnp.inf, vals)
    return vals, ids
