#!/usr/bin/env python3
"""Bring-up smoke: PandaDB's served kNN + graph path on one TPU chip.

    python chip_smoke.py            # from the checkout root, on a TPU host

One process, one chip, everything built from a seed through the entry
points a user calls:

  (a) load    ``build_snb`` at 200 000 persons with 2 KiB photos (~400 MB
              of BLOBs), φ = ``feature_hash_extractor(dim=128)``, then
              ``db.build_index`` as IVF-PQ (~100 buckets, M=16, residual).
  (b) serve   a ``QueryServer`` answers ``repro.launch.serve.QUERIES``
              (graph lookups, a scan, the ``~:`` similarity join); rows must
              match a sequential run, and the similarity answer a numpy
              cosine reference.
  (c) knn     ``search_many`` at Q in {32, 256}, k=10, modes float / adc /
              fused, ``rerank_mult`` 8 (k'=80: the XLA twin) and 4 (k'=40:
              the Pallas kernel); recall@10 >= 0.95 against ``search_exact``,
              which must itself match a float64 numpy brute force.
  (d) kernels ``ivf_scan``, ``pq_scan`` (base and extended) and
              ``topk_merge`` at N=131072, d=128, M=16, K=256 against their
              numpy oracles: ids equal except at near-ties inside a stated
              tolerance, merge byte-identical.
  (e) sharded a 2-shard ``ShardedPandaDB`` answers kNN through
              ``scatter_gather_knn`` -> ``merge_topk_dev`` with ids
              byte-identical to a single-node ``PandaDB``.

Photos come ten per identity and queries are fresh photos of known
identities (a face search): the top-10 then has a true answer.  With three
photos per identity the tail of the top-10 is a run of near-ties (median
10th-to-11th gap ~1e-3 of ~0.15), and recall measures tie order instead.

Earlier lines are one JSON object per phase (wall time, backend compiles,
checks) plus the per-kernel dispatch counts and peak device memory; they
are bring-up facts, not benchmark results.  The last line is
``{"ok": true, "device": {...}}``, printed only when every check of every
phase passed.  Without a TPU, or outside a checkout, the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0
K = 10
DIM = 128
PHOTO_BYTES = 2048
PHOTOS_PER_IDENTITY = 10
PERSONS = 200_000
SHARD_PERSONS = 20_000
KERNEL_ROWS = 131_072
QUERY_SIZES = (32, 256)
BF16_REL = 2.0 ** -8      # one bf16 MXU pass: rel. error of a product


class Smoke:
    """Phase clock, backend-compile counter and check ledger."""

    def __init__(self):
        import jax
        from jax import monitoring
        self.compiles = 0
        self.failures = []

        def on_event(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
        monitoring.register_event_duration_secs_listener(on_event)
        self.device = jax.devices()[0]

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
        return bool(ok)

    def phase(self, name: str, fn, *args, **kw):
        t0, c0 = time.perf_counter(), self.compiles
        out, facts = fn(self, *args, **kw)
        line = {"phase": name,
                "wall_s": time.perf_counter() - t0,
                "compiles": self.compiles - c0, **facts}
        self.emit(line)
        return out

    def emit(self, line: dict) -> None:
        print(json.dumps(line, default=_jsonable), flush=True)


def _jsonable(x):
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, np.ndarray):
        return x.tolist()
    raise TypeError(type(x))


# -- data -----------------------------------------------------------------


def _identities(persons: int, seed: int) -> np.ndarray:
    """The identity vectors ``build_snb`` draws first from its seed."""
    n_id = max(2, persons // PHOTOS_PER_IDENTITY)
    return np.random.default_rng(seed).standard_normal((n_id, 64))


def _fresh_queries(phi, identities: np.ndarray, n: int, seed: int):
    """φ of new photos of ``n`` distinct known identities."""
    from repro.data.synthetic_graph import identity_photo
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(identities), n, replace=False)
    raws = [np.frombuffer(identity_photo(rng, identities[p], PHOTO_BYTES),
                          np.uint8) for p in pick]
    return np.asarray(phi(raws), np.float32)


def _l2_topk(q: np.ndarray, corpus: np.ndarray, k: int) -> np.ndarray:
    """float64 brute force: positions of the k nearest rows (L2), ties to
    the lower row."""
    q64, c64 = q.astype(np.float64), corpus.astype(np.float64)
    s = 2.0 * (q64 @ c64.T) - (c64 * c64).sum(1)[None, :]
    return np.argsort(-s, axis=1, kind="stable")[:, :k]


def _recall(got: np.ndarray, want: np.ndarray) -> float:
    k = want.shape[1]
    return float(np.mean([len(set(g) & set(w)) / k
                          for g, w in zip(got.tolist(), want.tolist())]))


# -- (a) load ----------------------------------------------------------------


def load_store(sm: Smoke, persons: int):
    from repro.configs.pandadb import PandaDBConfig, VectorIndexConfig
    from repro.core import PandaDB
    from repro.core.aipm import feature_hash_extractor
    from repro.data.synthetic_graph import SNBConfig, build_snb

    icfg = VectorIndexConfig(dim=DIM, metric="l2",
                             vectors_per_bucket=max(1, persons // 100),
                             min_buckets=8, pq_m=16, pq_residual=True)
    db = PandaDB(PandaDBConfig(index=icfg))
    phi = feature_hash_extractor(dim=DIM)
    db.register_extractor("face", phi)
    t0 = time.perf_counter()
    build_snb(db, SNBConfig(
        n_persons=persons, photo_bytes=PHOTO_BYTES, seed=SEED,
        n_identities=max(2, persons // PHOTOS_PER_IDENTITY)))
    t_graph = time.perf_counter() - t0
    index = db.build_index("face", "photo")
    sm.check(index.n_total == persons, "load: index holds every photo")
    sm.check(index.codes is not None and index.codes.shape == (persons, 16),
             "load: PQ codes [N, 16]")
    facts = {"persons": persons, "nodes": db.graph.n_nodes,
             "blob_bytes": persons * PHOTO_BYTES,
             "buckets": int(index.centroids.shape[0]),
             "graph_s": t_graph,
             "index_s": time.perf_counter() - t0 - t_graph}
    return (db, index, phi), facts


# -- (b) serve ---------------------------------------------------------------


def serve(sm: Smoke, db, index, rounds: int = 8):
    from repro.core.executor import SIM_THRESHOLD
    from repro.launch.serve import QUERIES
    from repro.serving.engine import QueryServer

    want = {text: db.query(text) for text in QUERIES}
    server = QueryServer(db, n_workers=2)
    server.start()
    try:
        pending = [(text, server.submit(text))
                   for _ in range(rounds) for text in QUERIES]
        answers = [(text, box.get(timeout=600)) for text, box in pending]
    finally:
        server.close()
    errors = [repr(err) for _, (_, err) in answers if err is not None]
    sm.check(not errors, f"serve: request errors {errors[:3]}")
    sm.check(all(rows == want[text] for text, (rows, _) in answers),
             "serve: served rows differ from a sequential run")

    # the ~: join against numpy cosine over every person's face vector
    sim_q = next(t for t in QUERIES if "~:" in t)
    store = db.graph.store
    names = np.asarray(store.node_props.column("name").values, object)
    photo = np.asarray(store.node_props.column("photo").values, np.int64)
    person = next(i for i, n in enumerate(names) if n == "person_2")
    row_of = {int(b): r for r, b in enumerate(index.ids)}
    has = photo >= 0
    vecs = index.vectors[[row_of[int(b)] for b in photo[has]]]
    v0 = index.vectors[row_of[int(photo[person])]].astype(np.float64)
    cos = vecs.astype(np.float64) @ v0 / np.maximum(
        np.linalg.norm(vecs, axis=1) * np.linalg.norm(v0), 1e-9)
    ref = set(names[has][cos >= SIM_THRESHOLD].tolist())
    got = {r["m.name"] for r in want[sim_q]}
    edge = set(names[has][np.abs(cos - SIM_THRESHOLD) < 1e-5].tolist())
    sm.check(got - edge <= ref, "serve: ~: returned a non-match")
    sim_recall = len(got & ref) / max(1, len(ref))
    sm.check(sim_recall >= 0.95, f"serve: ~: recall {sim_recall}")
    facts = {"requests": len(answers), "errors": len(errors),
             "sim_matches": len(got), "sim_reference": len(ref),
             "sim_recall": sim_recall}
    return None, facts


# -- (c) batched kNN ---------------------------------------------------------


def knn(sm: Smoke, index, phi, persons: int, qs=QUERY_SIZES):
    import jax
    import jax.numpy as jnp
    from repro.core.vector_index import pairwise_scores

    queries = _fresh_queries(phi, _identities(persons, SEED), max(qs),
                             SEED + 1)
    truth = index.ids[_l2_topk(queries, index.vectors, K)]
    nprobe = index.cfg.nprobe
    cs = np.asarray(pairwise_scores(jnp.asarray(queries),
                                    jnp.asarray(index.centroids), "l2"))
    probe_dev = np.asarray(jax.lax.top_k(cs, nprobe)[1])
    probe_np = _l2_topk(queries, index.centroids, nprobe)
    facts = {"probe_set_agreement": _recall(probe_dev, probe_np),
             "runs": []}
    for qn in qs:
        q = queries[:qn]
        _, ex = index.search_exact(q, K)
        r_ex = _recall(ex, truth[:qn])
        sm.check(r_ex >= 0.95, f"knn: search_exact recall {r_ex} Q={qn}")
        facts[f"exact_vs_numpy_recall_q{qn}"] = r_ex
        for mode, rm in (("float", None), ("adc", 8), ("adc", 4),
                         ("fused", 8), ("fused", 4)):
            for rep in ("cold", "warm"):
                t0, c0 = time.perf_counter(), sm.compiles
                _, ids = index.search_many(q, K, mode=mode, rerank_mult=rm)
                run = {"q": qn, "mode": mode, "rerank_mult": rm, "rep": rep,
                       "s": time.perf_counter() - t0,
                       "compiles": sm.compiles - c0,
                       "recall_at_10": _recall(ids, ex)}
                facts["runs"].append(run)
                sm.check(run["recall_at_10"] >= 0.95,
                         f"knn: recall {run['recall_at_10']} {mode} "
                         f"rm={rm} Q={qn}")
    return None, facts


# -- (d) kernels vs oracles --------------------------------------------------


def _near_tie_check(sm, name, vals, ids, exact, tol):
    """Every returned id must score within ``tol`` of the oracle's k-th
    best, carry its own exact score within ``tol``, and appear once."""
    k = ids.shape[1]
    kth = -np.partition(-exact, k - 1, axis=1)[:, k - 1]
    rows = np.arange(ids.shape[0])[:, None]
    got = exact[rows, ids]
    err = np.abs(vals.astype(np.float64) - got)
    in_set = got >= (kth - tol)[:, None]
    distinct = all(len(set(r)) == k for r in ids.tolist())
    sm.check(bool(in_set.all()) and distinct, f"kernels: {name} ids")
    sm.check(bool((err <= tol[:, None]).all()), f"kernels: {name} values")
    return float(err.max()), float(tol.min())


def kernels(sm: Smoke, rows: int = KERNEL_ROWS, qs=QUERY_SIZES,
            ks=(10, 64)):
    from repro.kernels.ivf_scan.ops import ivf_scan_topk
    from repro.kernels.pq_scan.ops import pq_adc_topk
    from repro.kernels.pq_scan.ref import pq_adc_topk_ref, pq_scores_ref
    from repro.kernels.topk_merge.ops import merge_topk_dev
    from repro.kernels.topk_merge.ref import merge_topk_ref

    rng = np.random.default_rng(SEED + 2)
    m, ksub, mb, shards, window = 16, 256, 100, 8, 80
    corpus = rng.standard_normal((rows, DIM)).astype(np.float32)
    codes = rng.integers(0, ksub, (rows, m)).astype(np.uint8)
    bias = rng.standard_normal(rows).astype(np.float32)
    row_bucket = rng.integers(0, mb, rows).astype(np.int32)
    c64 = corpus.astype(np.float64)
    c2 = (c64 * c64).sum(1)
    facts = {"cases": []}
    for qn in qs:
        q = rng.standard_normal((qn, DIM)).astype(np.float32)
        q64 = q.astype(np.float64)
        ivf_exact = -((q64 * q64).sum(1)[:, None] - 2.0 * (q64 @ c64.T)
                      + c2[None, :])
        ivf_tol = 2 * BF16_REL * (np.linalg.norm(q64, axis=1)
                                  * np.sqrt(c2.max())) + 1e-3
        luts = rng.standard_normal((qn, m, ksub)).astype(np.float32)
        cscores = rng.standard_normal((qn, mb)).astype(np.float32)
        probe = np.zeros((qn, mb), bool)
        probe[np.arange(qn)[:, None],
              np.argsort(rng.random((qn, mb)), axis=1)[:, :8]] = True
        pq_exact = pq_scores_ref(luts, codes).astype(np.float64)
        ext_exact = pq_scores_ref(luts, codes, bias=bias,
                                  row_bucket=row_bucket, cscores=cscores,
                                  probe_mask=probe).astype(np.float64)
        lut_mag = np.abs(luts).max(axis=2).sum(axis=1)
        pq_tol = BF16_REL * lut_mag + 1e-4
        ext_tol = BF16_REL * (lut_mag + np.abs(cscores).max(1)) + 1e-4
        for k in ks:
            v, i = ivf_scan_topk(q, corpus, k, force_pallas=True)
            i_ref = np.argsort(-ivf_exact, axis=1, kind="stable")[:, :k]
            err, tol = _near_tie_check(sm, f"ivf_scan q={qn} k={k}",
                                       np.asarray(v), np.asarray(i),
                                       ivf_exact, ivf_tol)
            facts["cases"].append({"kernel": "ivf_scan", "q": qn, "k": k,
                                   "max_abs_err": err, "tol_min": tol,
                                   "ids_equal": _ids_equal(i, i_ref)})
            for name, exact, tol_q, kw in (
                    ("pq_scan", pq_exact, pq_tol, {}),
                    ("pq_scan_ext", ext_exact, ext_tol,
                     dict(bias=bias, row_bucket=row_bucket,
                          cscores=cscores, probe_mask=probe))):
                v, i = pq_adc_topk(luts, codes, k, force_pallas=True, **kw)
                _, i_ref = pq_adc_topk_ref(luts, codes, k, **kw)
                err, tol = _near_tie_check(sm, f"{name} q={qn} k={k}",
                                           np.asarray(v), np.asarray(i),
                                           exact, tol_q)
                facts["cases"].append({"kernel": name, "q": qn, "k": k,
                                       "max_abs_err": err, "tol_min": tol,
                                       "ids_equal": _ids_equal(i, i_ref)})
            # shard windows: sorted per shard, a starved shard padded, and
            # shard 1 repeating a quarter of shard 0's scores: exact ties
            # across shards must resolve to the lower column, as the
            # oracle's do
            wv = rng.standard_normal((shards, qn, window)).astype(np.float32)
            wv[1, :, :window // 4] = wv[0, :, :window // 4]
            wv = -np.sort(-wv, axis=2)
            wi = rng.integers(0, 1 << 30, (shards, qn, window)).astype(
                np.int32)
            wv[-1, :, window // 2:] = -np.inf
            wi[-1, :, window // 2:] = -1
            mv, mi = merge_topk_dev(wv, wi, k, force_pallas=True)
            rv, ri = merge_topk_ref(wv, wi, k)
            same = (np.array_equal(np.asarray(mv), rv)
                    and np.array_equal(np.asarray(mi), ri))
            sm.check(same, f"kernels: topk_merge q={qn} k={k} not "
                           f"byte-identical")
            facts["cases"].append({"kernel": "topk_merge", "q": qn, "k": k,
                                   "max_abs_err": 0.0 if same else None,
                                   "ids_equal": same})
    return None, facts


def _ids_equal(got, want) -> float:
    """Share of query rows whose ordered id list equals the oracle's."""
    return float(np.mean(np.all(np.asarray(got) == np.asarray(want),
                                axis=1)))


# -- (e) sharded scatter on one chip -----------------------------------------


def sharded(sm: Smoke, persons: int = SHARD_PERSONS, qn: int = 32):
    from repro.cluster import ShardedPandaDB
    from repro.configs.pandadb import PandaDBConfig, VectorIndexConfig
    from repro.core import PandaDB
    from repro.core.aipm import feature_hash_extractor
    from repro.data.synthetic_graph import identity_photo

    cfg = PandaDBConfig(index=VectorIndexConfig(
        dim=DIM, metric="l2", vectors_per_bucket=max(1, persons // 100),
        min_buckets=8))
    identities = _identities(persons, SEED + 3)
    rng = np.random.default_rng(SEED + 3)
    photos = [identity_photo(rng, identities[i % len(identities)],
                             PHOTO_BYTES) for i in range(persons)]
    phi = feature_hash_extractor(dim=DIM)
    single, cluster = PandaDB(cfg), ShardedPandaDB(2, cfg=cfg)
    try:
        for db, create in ((single, single.graph.create_node),
                           (cluster, cluster.create_node)):
            db.register_extractor("face", phi)
            for i, photo in enumerate(photos):
                create("Person", name=f"person_{i}", photo=photo)
            db.build_index("face", "photo")
        index = single.indexes["face"]
        q = _fresh_queries(phi, identities, qn, SEED + 4)
        facts = {"persons": persons, "shards": 2, "q": qn}
        for nprobe in (index.cfg.nprobe, index.centroids.shape[0]):
            v_s, i_s = index.search_many(q, K, nprobe=nprobe, mode="float")
            v_c, i_c = cluster.knn("face", q, K, nprobe=nprobe, mode="float")
            same = i_s.dtype == i_c.dtype and np.array_equal(i_s, i_c)
            sm.check(same, f"sharded: ids differ from single node "
                           f"(nprobe={nprobe})")
            facts[f"ids_identical_nprobe{nprobe}"] = bool(same)
            facts[f"vals_identical_nprobe{nprobe}"] = bool(
                np.array_equal(v_s, v_c))
    finally:
        cluster.close()
    return None, facts


# -- driver ------------------------------------------------------------------


def run(sm: Smoke, persons: int = PERSONS, shard_persons: int = SHARD_PERSONS,
        kernel_rows: int = KERNEL_ROWS, qs=QUERY_SIZES) -> None:
    db, index, phi = sm.phase("load", load_store, persons)
    sm.phase("serve", serve, db, index)
    sm.phase("knn", knn, index, phi, persons, qs)
    sm.phase("kernels", kernels, kernel_rows, qs)
    sm.phase("sharded", sharded, shard_persons)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--persons", type=int, default=PERSONS,
                    help="store size of phases (a)-(c); a cut is printed")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {backend!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.dispatch import dispatch_counts
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    sm = Smoke()
    if args.persons != PERSONS:
        print(json.dumps({"cut": {"persons": args.persons,
                                  "from": PERSONS}}), flush=True)
    sm.emit({"device": str(sm.device), "compile_cache": cache})
    run(sm, persons=args.persons)

    counts = dispatch_counts()
    sm.emit({"dispatch": counts})
    for kernel in ("ivf_scan", "pq_scan", "pq_scan_ext", "topk_merge"):
        sm.check(counts.get(f"{kernel}:pallas", 0) > 0,
                 f"dispatch: {kernel} Pallas kernel never ran")
    sm.check(counts.get("pq_scan_ext:xla", 0) > 0,
             "dispatch: the default k'=80 path never took the XLA twin")
    sm.check(not any(key.endswith(":interpret") for key in counts),
             "dispatch: a kernel ran in interpret mode on the chip")
    stats = sm.device.memory_stats() or {}
    sm.emit({"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
             "bytes_limit": stats.get("bytes_limit")})
    if sm.failures:
        print(json.dumps({"failures": sm.failures}), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": sm.device.platform, "kind": sm.device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
