"""Distributed collective schedules (shard_map level).

``sharded_topk``: the vector-index / retrieval pattern -- local exact top-k
per shard, all-gather of the tiny (val, id) pairs, final merge.  One
collective of O(shards * k) instead of gathering O(corpus).

``partial_softmax_combine``: the flash-decoding combine used when the KV
cache is sequence-sharded (long_500k): psum of (max-shifted sum, acc).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def sharded_topk(mesh: Mesh, axis: str, q: jnp.ndarray, corpus: jnp.ndarray,
                 ids: jnp.ndarray, k: int, metric: str = "l2"
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """corpus/ids sharded over `axis`; q replicated. Returns global top-k."""
    from repro.core.vector_index import merge_topk, pairwise_scores

    def local(q_l, c_l, id_l):
        s = pairwise_scores(q_l, c_l, metric)
        v, i = jax.lax.top_k(s, min(k, c_l.shape[0]))
        vals = id_l[i]
        # gather per-shard candidates ([n_shards, Q, k]) and reduce through
        # the ONE merge schedule every scatter-gather kNN shares
        v_all = jax.lax.all_gather(v, axis)
        i_all = jax.lax.all_gather(vals, axis)
        return merge_topk(v_all, i_all, k)

    fn = _shard_map(local, mesh,
                    in_specs=(P(), P(axis), P(axis)),
                    out_specs=(P(), P()))
    return fn(q, corpus, ids)


def _shard_map(f, mesh, in_specs, out_specs):
    """shard_map with replication checking off (top_k after all_gather is
    replicated, but the checker cannot infer that statically)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def partial_softmax_combine(mesh: Mesh, axis: str, scores: jnp.ndarray,
                            values: jnp.ndarray) -> jnp.ndarray:
    """scores [..., S_local], values [..., S_local, D] sharded over `axis` on
    the S dim: returns softmax(scores) @ values with one psum."""
    def local(s_l, v_l):
        m_l = jnp.max(s_l, axis=-1, keepdims=True)
        m = jax.lax.pmax(m_l, axis)
        p = jnp.exp(s_l - m)
        num = jax.lax.psum(jnp.einsum("...s,...sd->...d", p, v_l), axis)
        den = jax.lax.psum(jnp.sum(p, axis=-1, keepdims=True), axis)
        return num / jnp.maximum(den, 1e-30)

    fn = _shard_map(local, mesh,
                    in_specs=(P(None, axis), P(None, axis, None)),
                    out_specs=P())
    return fn(scores, values)
