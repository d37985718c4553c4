"""Deployment: a tagged image-embedding corpus (YFCC-10M-shaped:
192-d uint8 vectors, each image a bag of tags) under an IVF-PQ index and an
inverted tag index, served through ``ShardedPandaDB.knn`` with one tag
filter per query.

Set-up generates the corpus from the seed (``traffic/yfcc_gen.py``),
stores each image as an ``Image`` node's BLOB (the 192 vector bytes, then
its tags as words), registers φ ``emb`` (the bytes as a vector) and φ
``tags`` (the words), builds the vector index with the configuration's
settings and the tag index with ``build_scalar_index``.  The window calls
the coordinator's batched ``knn`` with each query's tags: the system
resolves them on its inverted index, masks every other row inside one ADC
scan of the whole code table, re-ranks the candidates exactly and merges.
The check compares a seeded sample of the window's queries, the largest
call's whole batch among them, with a float64 brute force over the rows
that carry every tag of each query's filter, both computed from the
generated arrays.
"""
from __future__ import annotations

import inspect
import json
import sys
import time
from typing import Dict, List

import numpy as np

from bench.harness import Check, CollectingTracer
from bench.reference import filtered_knn as ref
from bench.rowmask_roofline import rowmask_adc_scan
from bench.traffic import yfcc_gen


class Tagged(np.ndarray):
    """A batch of query vectors [Q, dim] that carries each query's filter:
    ``tags`` (tag ids, ANDed), ``words`` (their tokens), ``share`` (the
    drawn share of the corpus's rows) and ``rows`` (the rows it allows)."""


def blob(vec: np.ndarray, tags: np.ndarray) -> bytes:
    return (np.asarray(vec, np.uint8).tobytes()
            + " ".join(map(yfcc_gen.word, tags)).encode())


def extractors(dim: int):
    """φ ``emb`` (the first ``dim`` bytes as a float32 vector) and φ
    ``tags`` (the rest, the image's words)."""
    def emb(raws: List[np.ndarray]) -> np.ndarray:
        return np.stack([np.asarray(r, np.uint8)[:dim].astype(np.float32)
                         for r in raws])

    def tags(raws: List[np.ndarray]) -> List[str]:
        return [np.asarray(r, np.uint8)[dim:].tobytes().decode()
                for r in raws]
    return emb, tags


class Deployment:
    def __init__(self, cfg: Dict, traffic: Dict, seed: int,
                 phases: Dict[str, float], trace: bool = False):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.phases, self.trace = phases, trace
        self.population = {}
        self.k = int(traffic["k"])
        self.corpus = self.tagsets = None
        self.cluster = self.tracer = None
        self.nlist = 0
        self._mask0 = 0

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from repro.cluster import ShardedPandaDB
        from repro.configs.pandadb import PandaDBConfig, VectorIndexConfig
        from repro.obs import Tracer

        if "filters" not in inspect.signature(ShardedPandaDB.knn).parameters:
            raise RuntimeError("the system under test has no filtered kNN "
                               "(ShardedPandaDB.knn takes no filters)")
        cfg, ix = self.cfg, self.cfg["index"]
        dim = int(cfg["dim"])
        t = time.perf_counter()
        self.corpus = c = yfcc_gen.corpus(cfg, self.seed)
        n = len(c.vectors)
        lo = 2.0 ** float(self.traffic["filter"]["share_lo_log2"])
        self.tagsets = yfcc_gen.TagSets(c, int(cfg["tag_vocabulary"]),
                                        max(1, int(lo * n)))
        self.phases["generate_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.cluster = db = ShardedPandaDB(int(cfg["shards"]),
                                           cfg=PandaDBConfig(
            index=VectorIndexConfig(
                dim=dim, metric=ix["metric"],
                vectors_per_bucket=int(ix["vectors_per_bucket"]),
                min_buckets=int(ix["min_buckets"]),
                nprobe=int(ix["nprobe"]), pq_m=int(ix["pq_m"]),
                pq_bits=int(ix["pq_bits"]),
                pq_residual=bool(ix["pq_residual"]),
                rerank_mult=int(ix["rerank_mult"]))))
        emb, tags = extractors(dim)
        db.register_extractor("emb", emb)
        db.register_extractor("tags", tags)
        for i in range(n):
            db.create_node("Image", photo=blob(c.vectors[i], c.tags_of(i)))
        self.phases["load_s"] = time.perf_counter() - t

        t = time.perf_counter()
        pieces = db.build_index("emb", "photo")
        self.nlist = int(pieces[0].centroids.shape[0])
        db.build_scalar_index("tags", "photo")
        self.phases["index_s"] = time.perf_counter() - t
        if self.trace:
            self.tracer = db.tracer = CollectingTracer(Tracer)

    def render(self, entity: str, values: np.ndarray, seed: int) -> list:
        if entity != "query:tagged":
            raise ValueError(f"unknown entity {entity!r}")
        sizes = np.asarray(values, np.int64)
        q, qcluster = yfcc_gen.queries(self.cfg, int(sizes.sum()), seed)
        tags, share, rows = yfcc_gen.filters(
            self.cfg, self.traffic["filter"], self.tagsets, qcluster, seed)
        n = len(self.corpus.vectors)
        octave = np.floor(np.log2(np.maximum(rows, 1) / n)).astype(int)
        print(json.dumps({"filters": {
            "queries": len(tags),
            "two_tags": sum(len(f) == 2 for f in tags),
            "empty": int((rows == 0).sum()),
            "octaves_log2_share": {
                int(o): int((octave == o).sum()) for o in np.unique(octave)},
            "allowed_rows_p50": float(np.median(rows)),
            "allowed_rows_mean": float(rows.mean())}}), file=sys.stderr)
        out = []
        for lo, hi in zip(np.cumsum(sizes) - sizes, np.cumsum(sizes)):
            b = q[lo:hi].view(Tagged)
            b.tags = tags[lo:hi]
            b.words = [tuple(map(yfcc_gen.word, f)) for f in b.tags]
            b.share, b.rows = share[lo:hi], rows[lo:hi]
            out.append(b)
        return out

    def warm(self, requests) -> None:
        """One call of each batch size the window sends: the programs of
        the row-masked path follow the batch size alone."""
        first = {}
        for r in requests:
            first.setdefault(r.size, r)
        for r in first.values():
            self.call(r)

    # -- the window ---------------------------------------------------------

    def _mask_bytes(self) -> int:
        from repro.kernels.dispatch import METRICS
        return int(METRICS.counter("h2d_bytes:row_mask").value)

    def before_window(self) -> None:
        if self.tracer is not None:
            self.tracer.traces.clear()
        self._mask0 = self._mask_bytes()

    def call(self, req):
        q = req.params["q"]
        return self.cluster.knn("emb", np.asarray(q), self.k,
                                mode=self.cfg["index"]["scan_mode"],
                                filters=q.words, filter_key="tags")

    def after_window(self, view) -> None:
        """The row-mask bytes the window uploaded, and (traced) the scan
        work of its row-masked dispatches: one ``rowmask_adc_scan`` per
        call where the dispatch counters show exactly one
        ``pq_scan_rowmask`` dispatch per call and no other scan kernel."""
        view.row_mask_h2d_bytes = self._mask_bytes() - self._mask0
        if self.tracer is None:
            return
        view.traces = list(self.tracer.traces)
        ix = self.cfg["index"]
        recs = view.window.records
        scans = {k: v for k, v in view.dispatch.items()
                 if k.split(":")[0] in ("ivf_scan", "pq_scan", "pq_scan_ext",
                                        "pq_scan_rowmask")}
        masked = sum(v for k, v in scans.items()
                     if k.startswith("pq_scan_rowmask:")
                     and not k.endswith(":interpret"))
        if masked != len(recs) or masked != sum(
                v for k, v in scans.items() if not k.endswith(":interpret")):
            return
        kprime = int(ix["rerank_mult"]) * self.k
        view.rowmask_work = [rowmask_adc_scan(
            rec.req.size, len(self.corpus.vectors), int(ix["pq_m"]),
            1 << int(ix["pq_bits"]), kprime, self.nlist,
            bool(ix["pq_residual"])) for rec in recs]

    def release(self) -> None:
        if self.cluster is not None:
            self.cluster.close()
        self.cluster = self.tracer = None

    def close(self) -> None:
        self.release()

    # -- the check ----------------------------------------------------------

    def served(self, rec):
        """(values, ids) of one call, [size, k]: a row or slot the call left
        out reads id -1, a missing answer."""
        size = rec.req.size
        v = np.full((size, self.k), -np.inf)
        i = np.full((size, self.k), -1, np.int64)
        sv, si = (np.asarray(x) for x in rec.result)
        if si.ndim == 2:
            n, m = min(len(si), size), min(si.shape[1], self.k)
            v[:n, :m], i[:n, :m] = sv[:n, :m], si[:n, :m]
        return v, i

    def sample(self, window, seed: int):
        """(query vectors, filters, served values, served ids) of a seeded
        sample of the window's answered calls' queries, the largest call
        whole."""
        done = [r for r in window.records if r.error is None]
        dim = self.corpus.vectors.shape[1]
        if not done:
            return (np.zeros((0, dim), np.float32), [],
                    np.zeros((0, self.k)), np.zeros((0, self.k), np.int64))
        q = np.concatenate([np.asarray(r.req.params["q"]) for r in done])
        tags = [f for r in done for f in r.req.params["q"].tags]
        v, i = (np.concatenate(x) for x in zip(*map(self.served, done)))
        big = int(np.argmax([r.req.size for r in done]))
        start = sum(r.req.size for r in done[:big])
        whole = np.arange(start, start + done[big].req.size)
        rest = np.setdiff1d(np.arange(len(q)), whole)
        rng = np.random.default_rng([int(seed), 3])
        n = max(0, int(self.cfg["check_queries"]) - len(whole))
        pick = np.concatenate([whole, rng.choice(rest, min(n, len(rest)),
                                                 replace=False)])
        return q[pick], [tags[j] for j in pick], v[pick], i[pick]

    def numbers(self, q, tags, vals, ids, log: bool = False) -> List[Check]:
        """Of served (vals, ids): rows outside their query's filter, empty
        slots where the reference has a row, recall@10 against the float64
        filtered brute force, and the worst gap between a served distance
        and the reference distance of the same row, relative to the
        reference's farthest (10th, or last allowed) distance."""
        k, corpus = self.k, self.corpus.vectors
        allowed = ref.allowed_rows(self.corpus.tag_ptr, self.corpus.tag_ids,
                                   tags)
        ref_d, ref_pos = ref.filtered_topk(q, corpus, allowed, k)
        # blob ids follow creation order: blob i is corpus row i
        bad = (ids < 0) | (ids >= len(corpus))
        pos = np.where(bad, 0, ids)
        outside = np.array([int((~np.isin(p[~b], a)).sum())
                            for p, b, a in zip(pos, bad, allowed)])
        n_ref = (ref_pos >= 0).sum(1)
        missing = int(((ref_pos >= 0) & bad).sum())
        got = ref.l2_to(q, corpus, pos)
        last = ref_d[np.arange(len(q)), np.maximum(n_ref - 1, 0)]
        scale = np.where(n_ref > 0, np.maximum(last, 1e-12), np.inf)
        err = np.where(bad, 0.0, np.abs(-vals - got) / scale[:, None])
        hits = np.array([len(set(a[~b].tolist()) & set(r[r >= 0].tolist()))
                         for a, b, r in zip(pos, bad, ref_pos)])
        if log:
            share = np.array([len(a) for a in allowed]) / len(corpus)
            octave = np.floor(np.log2(np.maximum(share, 1e-12)))
            print(json.dumps({"recall_by_octave_log2_share": {
                int(o): {"queries": int((octave == o).sum()),
                         "recall": float(hits[octave == o].sum()
                                         / max(1, n_ref[octave == o].sum()))}
                for o in np.unique(octave)}}), file=sys.stderr)
        lim = self.traffic["limits"]
        return [Check("filter_violations", int(outside.sum()),
                      float(lim["filter_violations"])),
                Check("missing_answers", missing,
                      float(lim["missing_answers"])),
                Check("recall_at_10",
                      float(hits.sum() / n_ref.sum()) if n_ref.sum() else 0.0,
                      float(self.cfg["recall_at_10_min"]),
                      higher_is_worse=False),
                Check("score_rel_err", float(err.max(initial=0.0)),
                      float(lim["score_rel_err"]))]

    def check(self, window, seed: int) -> List[Check]:
        q, tags, vals, ids = self.sample(window, seed)
        return self.numbers(q, tags, vals, ids, log=True)

    def control_answers(self, q, tags, dtype):
        """What the plain reference serves with its distances computed in
        ``dtype``: (negated distances, ids), as ``knn`` returns them."""
        allowed = ref.allowed_rows(self.corpus.tag_ptr, self.corpus.tag_ids,
                                   tags)
        d, pos = ref.filtered_topk(q, self.corpus.vectors, allowed, self.k,
                                   dtype=dtype)
        return -d, pos

    def control_check(self, window, seed: int, dtype: str) -> List[Check]:
        q, tags, _, _ = self.sample(window, seed)
        return self.numbers(q, tags, *self.control_answers(q, tags, dtype))
