"""Index query speed (paper Fig 12, extended for the batched kNN path).

Three search drivers over the same IVF index, Q in {1, 32, 256}:

* ``loop``    -- the seed's per-query host loop (one small device call per
                 query; kept here as the baseline),
* ``batched`` -- ``IVFIndex.search_many`` (probe-signature grouping, fused
                 scans, the only path the index ships now),
* ``kernel``  -- the Pallas ``ivf_scan`` kernel itself (interpret mode off
                 TPU, so it is timed on a reduced shape purely as a dispatch
                 proof; on TPU ``batched`` == ``kernel``).

Plus DynamicIndexing: 1000 single-vector inserts into a 100k index, the
seed's ``np.insert`` layout-rewrite baseline vs the buffered append path
(including one final ``compact()``).

Raw numbers land in ``BENCH_index_knn.json``; byte-identical top-k ids
between loop and batched at nprobe=m (exact mode) are asserted, not assumed.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.configs.pandadb import VectorIndexConfig
from repro.core.vector_index import IVFIndex, pairwise_scores, scan_topk
from repro.data.synthetic_graph import sift_like_vectors
from repro.kernels.ivf_scan.ops import ivf_scan_topk
from repro.kernels.ivf_scan.ref import ivf_scan_topk_ref


def _search_loop(index: IVFIndex, queries: np.ndarray, k: int,
                 nprobe: int) -> tuple:
    """The seed's per-query host loop, verbatim shape: one gather + one
    small device scan per query row."""
    q = jnp.asarray(queries, jnp.float32)
    cscores = pairwise_scores(q, jnp.asarray(index.centroids),
                              index.cfg.metric)
    _, probe = jax.lax.top_k(cscores, nprobe)
    probe = np.asarray(probe)
    out_v = np.full((queries.shape[0], k), -np.inf, np.float32)
    out_i = np.full((queries.shape[0], k), -1, np.int64)
    for qi in range(queries.shape[0]):
        segs = [index.bucket_slice(int(b)) for b in probe[qi]]
        rows = np.concatenate([np.arange(lo, hi) for lo, hi in segs]) \
            if segs else np.array([], np.int64)
        if rows.size == 0:
            continue
        vals, ids = scan_topk(q[qi:qi + 1], jnp.asarray(index.vectors[rows]),
                              jnp.asarray(index.ids[rows]), k,
                              index.cfg.metric)
        kk = vals.shape[1]
        out_v[qi, :kk] = np.asarray(vals)[0]
        out_i[qi, :kk] = np.asarray(ids)[0]
    return out_v, out_i


def _np_insert_baseline(index: IVFIndex, vecs: np.ndarray,
                        ids: np.ndarray) -> None:
    """The seed's DynamicIndexing: O(N) layout rewrite per vector."""
    bucket_of, vectors, ext = index.bucket_of, index.vectors, index.ids
    cent = index.centroids
    for vec, eid in zip(vecs, ids):
        scores = np.asarray(pairwise_scores(
            jnp.asarray(vec[None], jnp.float32),
            jnp.asarray(cent), index.cfg.metric))[0]
        b = int(scores.argmax())
        pos = np.searchsorted(bucket_of, b, side="right")
        bucket_of = np.insert(bucket_of, pos, b)
        vectors = np.insert(vectors, pos, vec.astype(np.float32), axis=0)
        ext = np.insert(ext, pos, eid)


def run() -> None:
    n, dim = 20_000, 64
    vecs = sift_like_vectors(n, dim=dim, n_clusters=128, seed=0)
    cfg = VectorIndexConfig(dim=dim, metric="l2", vectors_per_bucket=1_000,
                            min_buckets=8, nprobe=6, kmeans_iters=4)
    index = IVFIndex.build(vecs, cfg=cfg, seed=0)
    m = index.centroids.shape[0]
    rng = np.random.default_rng(2)
    payload: dict = {"config": dict(n=n, dim=dim, m=m, nprobe=cfg.nprobe),
                     "search": {}, "kernel": {}, "insert": {}}

    k = 10
    for q_count in (1, 32, 256):
        sel = rng.choice(n, q_count)
        queries = vecs[sel] + \
            rng.standard_normal((q_count, dim)).astype(np.float32) * 0.01
        t_loop = timeit(lambda: _search_loop(index, queries, k, cfg.nprobe),
                        repeats=3)
        t_batch = timeit(lambda: index.search_many(queries, k, cfg.nprobe),
                         repeats=3)
        speedup = t_loop / t_batch
        emit(f"index_knn/loop/Q={q_count}", t_loop,
             f"per_q_us={t_loop / q_count:.0f}")
        emit(f"index_knn/batched/Q={q_count}", t_batch,
             f"per_q_us={t_batch / q_count:.0f},speedup={speedup:.1f}x")
        payload["search"][f"Q={q_count}"] = dict(
            loop_us=t_loop, batched_us=t_batch, speedup=speedup)

    # exact mode (nprobe=m): one probe signature, one fused scan; ids must be
    # byte-identical to the per-query loop
    sel = rng.choice(n, 256)
    queries = vecs[sel] + \
        rng.standard_normal((256, dim)).astype(np.float32) * 0.01
    _, ids_loop = _search_loop(index, queries, k, m)
    _, ids_batch = index.search_many(queries, k, m)
    identical = bool(np.array_equal(ids_loop, ids_batch))
    assert identical, "exact-mode ids diverged between loop and batched"
    t_loop = timeit(lambda: _search_loop(index, queries, k, m), repeats=3)
    t_batch = timeit(lambda: index.search_many(queries, k, m), repeats=3)
    emit("index_knn/exact/Q=256", t_batch,
         f"loop_us={t_loop:.0f},speedup={t_loop / t_batch:.1f}x")
    payload["search"]["exact_Q=256"] = dict(
        loop_us=t_loop, batched_us=t_batch, speedup=t_loop / t_batch)
    payload["exact_ids_identical"] = identical

    # kernel dispatch proof: the Pallas path (interpret mode off TPU) against
    # the XLA oracle on a reduced shape -- interpret mode is an emulator, so
    # off-TPU this measures correctness wiring, not kernel speed
    on_tpu = jax.default_backend() == "tpu"
    kq, kn = 32, 2048
    q_small = jnp.asarray(rng.standard_normal((kq, dim)), jnp.float32)
    c_small = jnp.asarray(vecs[:kn])
    v_kern, i_kern = ivf_scan_topk(q_small, c_small, k, metric="l2",
                                   force_pallas=True)
    v_ref, i_ref = ivf_scan_topk_ref(q_small, c_small, k, "l2")
    assert np.array_equal(np.asarray(i_kern), np.asarray(i_ref))
    # the wrapper returns host arrays: the call has finished when it returns
    t_kern = timeit(lambda: ivf_scan_topk(q_small, c_small, k, metric="l2",
                                          force_pallas=True), repeats=3)
    t_ref = timeit(lambda: ivf_scan_topk_ref(q_small, c_small, k, "l2")[0]
                   .block_until_ready(), repeats=3)
    emit(f"index_knn/kernel/Q={kq}", t_kern,
         f"ref_us={t_ref:.0f},backend={'tpu' if on_tpu else 'interpret'}")
    payload["kernel"] = dict(Q=kq, n=kn, kernel_us=t_kern, ref_us=t_ref,
                             backend="tpu" if on_tpu else "interpret",
                             ids_match_ref=True)

    # DynamicIndexing: 1000 single inserts into a 100k index
    n_big, n_ins = 100_000, 1000
    big = sift_like_vectors(n_big, dim=dim, n_clusters=128, seed=3)
    big_cfg = VectorIndexConfig(dim=dim, metric="l2",
                                vectors_per_bucket=1_000, min_buckets=8,
                                nprobe=6, kmeans_iters=2)
    big_index = IVFIndex.build(big, cfg=big_cfg, seed=0)
    new_vecs = rng.standard_normal((n_ins, dim)).astype(np.float32)
    new_ids = np.arange(n_big, n_big + n_ins)

    t_np = timeit(lambda: _np_insert_baseline(big_index, new_vecs, new_ids),
                  repeats=1, warmup=0)

    def buffered():
        idx = IVFIndex(big_cfg, big_index.centroids,
                       big_index.bucket_of.copy(), big_index.vectors.copy(),
                       big_index.ids.copy())
        for vec, eid in zip(new_vecs, new_ids):
            idx.insert(vec, eid)
        idx.compact()

    t_buf = timeit(buffered, repeats=1, warmup=0)
    speedup = t_np / t_buf
    emit(f"index_knn/insert_{n_ins}_into_{n_big}", t_buf,
         f"np_insert_us={t_np:.0f},speedup={speedup:.1f}x")
    payload["insert"] = dict(n_index=n_big, n_inserts=n_ins,
                             np_insert_us=t_np, buffered_us=t_buf,
                             speedup=speedup)

    out = Path(__file__).resolve().parent.parent / "BENCH_index_knn.json"
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    run()
