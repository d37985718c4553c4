"""Semantic-information vector index: IVF-Flat / IVF-PQ (paper §VI-B2 +
Algorithm 2, extended with product-quantized storage).

BatchIndexing: m = |S| / 100_000 buckets (empirical value from the paper),
random core vectors refined by a few k-means iterations, every vector
assigned to its nearest core.  DynamicIndexing: new vectors land in
per-bucket append buffers (amortized O(1) per insert) and are folded into
the sorted bucket layout by a deferred compaction pass; searches always see
the uncompacted rows.  kNN: queries are batched -- one centroid probe for
the whole query set, then queries sharing a probe signature are scanned
together through ``kernels.ivf_scan.ops.ivf_scan_topk`` (the Pallas kernel
on TPU, the fused XLA oracle elsewhere) over a gathered, block-padded
corpus, followed by the ``merge_topk``-shaped epilogue inside the kernel
dispatch.  There is no per-query Python loop.

IVF-PQ (``cfg.pq_m > 0``): :class:`PQCodebook` trains per-subspace k-means
codebooks at build time and every bucket stores uint8 codes (M bytes per
row instead of 4*dim) alongside the append-buffer machinery.  Search is
two-stage: per-query score LUTs + an asymmetric-distance (ADC) top-k' scan
of the probed buckets through ``kernels.pq_scan.ops.pq_adc_topk``, then an
exact re-rank of the k' candidates against the original float vectors
(primary storage) that returns true top-k scores -- so similarity
thresholds downstream see exact values, and recall lost to quantization is
recovered (cf. proxy-then-rerank pipelines).  The cost model picks ADC vs
float scan per query batch from observed throughputs
(``StatisticsService.choose_knn_scan``).

Distributed layout (paper §VII-A: property data sharded): centroids are
replicated, bucket contents are sharded (:meth:`IVFIndex.shard`); a query
does a local scan per shard + per-shard top-k + a k-way merge --
:func:`scatter_gather_knn` below is that schedule.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import wait as futures_wait
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.pandadb import VectorIndexConfig
from repro.core.scalar_index import Posting
from repro.kernels.dispatch import (METRICS, count_h2d, padded_queries,
                                    padded_rows)
from repro.kernels.ivf_scan.ops import ivf_scan_topk
from repro.kernels.pq_scan.ops import pq_adc_topk, row_mask_from_bitmaps
from repro.kernels.topk_merge.ops import merge_topk_dev
from repro.obs.trace import maybe_span


# ---------------------------------------------------------------------------
# scoring primitives (ops.py of the ivf_scan kernel wraps these on TPU)
# ---------------------------------------------------------------------------


def pairwise_scores(q: jnp.ndarray, c: jnp.ndarray, metric: str) -> jnp.ndarray:
    """[Q, d] x [N, d] -> [Q, N]; higher is better."""
    if metric == "ip":
        return q @ c.T
    if metric == "cosine":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
        cn = c / jnp.maximum(jnp.linalg.norm(c, axis=-1, keepdims=True), 1e-9)
        return qn @ cn.T
    # l2: negative squared distance via the matmul identity (MXU-friendly)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    return -(q2 - 2.0 * (q @ c.T) + c2[None, :])


def _pairwise_scores_np(q: np.ndarray, c: np.ndarray, metric: str) -> np.ndarray:
    """Host-side twin of :func:`pairwise_scores` for tiny shapes (insert's
    centroid pick), where one device dispatch would dominate the work."""
    q = np.asarray(q, np.float32)
    c = np.asarray(c, np.float32)
    if metric == "ip":
        return q @ c.T
    if metric == "cosine":
        qn = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
        cn = c / np.maximum(np.linalg.norm(c, axis=-1, keepdims=True), 1e-9)
        return qn @ cn.T
    q2 = np.sum(q * q, axis=-1, keepdims=True)
    c2 = np.sum(c * c, axis=-1)
    return -(q2 - 2.0 * (q @ c.T) + c2[None, :])


@partial(jax.jit, static_argnames=("k", "metric"))
def scan_topk(q: jnp.ndarray, corpus: jnp.ndarray, ids: jnp.ndarray,
              k: int, metric: str = "l2") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact scored top-k of `corpus` rows for each query row."""
    scores = pairwise_scores(q, corpus, metric)
    vals, idx = jax.lax.top_k(scores, min(k, corpus.shape[0]))
    return vals, ids[idx]


def merge_topk(vals_parts: jnp.ndarray, ids_parts: jnp.ndarray, k: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge per-shard top-k: [P, Q, k] -> [Q, k] (associative).

    Padding entries (val=-inf, id=-1) sink to the tail of the merge; callers
    that may hold fewer than ``k`` real candidates in total should truncate
    or mask afterwards (:func:`scatter_gather_knn` does)."""
    p, qn, kk = vals_parts.shape
    flat_v = jnp.transpose(vals_parts, (1, 0, 2)).reshape(qn, p * kk)
    flat_i = jnp.transpose(ids_parts, (1, 0, 2)).reshape(qn, p * kk)
    v, pos = jax.lax.top_k(flat_v, min(k, p * kk))
    return v, jnp.take_along_axis(flat_i, pos, axis=1)


def stable_id_hash(ids: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over external ids: the cluster-wide ownership
    hash.  Stable under row reordering (it sees the *id*, not the row
    position), so compaction / rebuilds never move a row between shards --
    the property deterministic owner-shard routing depends on."""
    x = np.asarray(ids).astype(np.uint64)
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def owner_shard(ids: np.ndarray, n_shards: int) -> np.ndarray:
    """Owning shard per external id: ``stable_id_hash(id) % n_shards``."""
    return (stable_id_hash(ids) % np.uint64(max(1, n_shards))).astype(np.int64)


def scatter_gather_knn(shards: Sequence["IVFIndex"], queries: np.ndarray,
                       k: int, nprobe: Optional[int] = None,
                       mode: str = "auto", rerank: bool = True,
                       stats=None, record: Optional[Callable] = None,
                       pool=None, split_rerank_budget: bool = False,
                       deadline=None, trace=None,
                       allow: Optional[Sequence[Sequence[np.ndarray]]] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """THE cluster merge schedule: per-shard ``search_many`` (ADC, float or
    fused, per each shard's cost-model call) -> one-dispatch k-way
    ``merge_topk_dev`` reduce (the Pallas merge kernel on TPU, its jitted
    XLA twin elsewhere) -> truncation of shard padding to min(k, total
    rows).  Every scatter-gather kNN in the tree -- ``ShardedPandaDB.knn``,
    the serving path -- routes through here, so the merge semantics cannot
    drift.

    Output invariant (the ``merge_topk`` padding contract, enforced here
    rather than trusted): a position holds id=-1 exactly where its value is
    -inf, i.e. where fewer real candidates existed than ``k`` -- no shard's
    -1 padding can ever surface with a finite score attached.

    ``stats`` is either one StatisticsService (shared feedback) or a
    sequence with one entry per shard (each shard's ADC-vs-float choice then
    uses its own observed throughputs).  ``record(shard_idx, dt, rows)``,
    if given, receives per-shard wall time + rows scanned (the
    coordinator's per-shard EWMAs).  ``pool`` is an optional
    ``concurrent.futures`` executor: shards scatter in parallel; results
    are merged in shard order either way, so the output is deterministic.

    ``split_rerank_budget=True`` divides the *global* re-rank candidate
    budget across shards -- each shard scans ADC top-``ceil(rerank_mult/P)
    * k`` instead of ``rerank_mult * k`` -- so total exact-re-rank work
    (the host-side term that otherwise grows linearly with P) stays
    constant as shards are added.  The merged result is the exact top-k of
    a candidate pool that hash-sharding spreads ~budget/P per shard, so
    it matches the unsharded pool in practice (the bench asserts it);
    residual PQ tightens ADC ordering precisely so this split is safe.

    ``deadline`` (a :class:`~repro.core.deadline.Deadline`, optional) is
    the degradation ladder's last resort: shards whose scans miss the
    remaining budget are *dropped* and the merge returns partial top-k
    from the shards that answered -- the padding contract above already
    guarantees dropped contributions surface as (-inf, -1) slots, never
    as fabricated candidates.  ``partial_topk`` is noted on the deadline;
    if NO shard answers in time, :class:`DeadlineExceeded` is raised.

    ``trace`` (a :class:`repro.obs.Trace`, optional) records one live
    ``knn.shard_scan`` span per shard (attributed with rows scanned and
    re-rank mode, correct even off pool threads; the shard's
    ``search_many`` nests its phases under it), a ``knn.merge`` span for
    the device-side reduce with the bytes it uploads (``h2d_bytes``), and
    a ``degradation`` event when the partial top-k ladder step fires.

    ``allow`` (filtered kNN, optional) holds, per shard, each query's
    filter over that shard's rows (allowed external ids, or a tuple of the
    shard's postings to AND; :meth:`IVFIndex.search_many`): every shard
    answers each query from the rows that pass it only, and the merge is
    unchanged."""
    queries = np.asarray(queries, np.float32)
    qn = queries.shape[0]
    out_v = np.full((qn, k), -np.inf, np.float32)
    out_i = np.full((qn, k), -1, np.int64)
    if qn == 0 or not shards:
        return out_v, out_i
    per_stats = (list(stats) if isinstance(stats, (list, tuple))
                 else [stats] * len(shards))
    rm = None
    if split_rerank_budget and rerank and len(shards) > 1:
        rm = max(1, -(-max(sh.cfg.rerank_mult for sh in shards)
                      // len(shards)))

    # spans from pool threads attach to the caller's current span, captured
    # here (the pool thread's own stack is empty, so parent= is explicit)
    t_parent = trace.current() if trace is not None else None

    def scan_one(s: int):
        t0 = time.perf_counter()
        rows0 = shards[s].scan_rows
        kw = {} if allow is None else {"allow": allow[s]}
        with maybe_span(trace, "knn.shard_scan", parent=t_parent,
                        shard=s) as sp:
            v, i = shards[s].search_many(queries, k, nprobe,
                                         stats=per_stats[s], mode=mode,
                                         rerank=rerank, rerank_mult=rm,
                                         trace=trace, **kw)
            scanned = shards[s].scan_rows - rows0
            if sp is not None:
                sp.set(rows=int(scanned), rerank=rerank)
        dt = time.perf_counter() - t0
        if record is not None:
            record(s, dt, scanned)
        return v, i

    pad = (np.full((qn, k), -np.inf, np.float32),
           np.full((qn, k), -1, np.int64))
    if pool is not None and len(shards) > 1:
        if deadline is None:
            parts = list(pool.map(scan_one, range(len(shards))))
        else:
            futs = [pool.submit(scan_one, s) for s in range(len(shards))]
            futures_wait(futs, timeout=max(0.0, deadline.remaining()))
            parts, answered = [], 0
            for f in futs:
                if f.done() and f.exception() is None:
                    parts.append(f.result())
                    answered += 1
                else:
                    f.cancel()      # queued legs are withdrawn; running
                    parts.append(pad)   # legs finish unobserved
            if answered == 0:
                deadline.check("knn scatter")
            if answered < len(shards):
                deadline.note_degradation("partial_topk")
                if trace is not None:
                    trace.event("degradation", parent=t_parent,
                                step="partial_topk",
                                answered=answered, shards=len(shards))
    elif deadline is not None:
        parts, answered = [], 0
        for s in range(len(shards)):
            if deadline.expired():
                if answered == 0:
                    deadline.check("knn scatter")
                parts.append(pad)   # serial last resort: keep what we have
                continue
            parts.append(scan_one(s))
            answered += 1
        if answered < len(shards):
            deadline.note_degradation("partial_topk")
            if trace is not None:
                trace.event("degradation", parent=t_parent,
                            step="partial_topk", answered=answered,
                            shards=len(shards))
    else:
        parts = [scan_one(s) for s in range(len(shards))]
    t_merge = time.perf_counter()
    up = count_h2d("topk_merge", None, *(a for p in parts for a in p))
    v, i = merge_topk_dev(jnp.stack([jnp.asarray(p[0]) for p in parts]),
                          jnp.stack([jnp.asarray(p[1]) for p in parts]), k)
    if trace is not None:
        trace.add_timed("knn.merge", time.perf_counter() - t_merge,
                        parent=t_parent, shards=len(parts), k=k,
                        h2d_bytes=up)
    total = sum(sh.n_total for sh in shards)
    kk = min(k, total, v.shape[1])
    v = np.asarray(v)[:, :kk]
    i = np.asarray(i)[:, :kk]
    out_v[:, :kk] = v
    # pin the padding invariant structurally: wherever the merged window
    # still holds -inf (a query whose probed buckets had < k real rows
    # in total), the id is -1 -- whatever payload the shard windows carried
    out_i[:, :kk] = np.where(np.isfinite(v), i, -1)
    return out_v[:, :k], out_i[:, :k]


# ---------------------------------------------------------------------------
# product quantization
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PQCodebook:
    """Per-subspace k-means codebooks: dim splits into ``m`` contiguous
    subspaces of ``dsub`` dims, each quantized to one of ``ksub = 2**bits``
    centers.  A vector becomes ``m`` uint8 codes; reconstruction error is
    the sum of per-subspace quantization errors.

    Codes are always assigned by nearest center in L2 (minimum
    reconstruction error) regardless of the search metric; the *LUTs* carry
    the metric: negative squared sub-distances for L2, sub dot products for
    IP (cosine callers normalize upstream, then IP == cosine)."""

    codebooks: np.ndarray        # [m, ksub, dsub] float32
    metric: str = "l2"

    @property
    def m(self) -> int:
        return self.codebooks.shape[0]

    @property
    def ksub(self) -> int:
        return self.codebooks.shape[1]

    @property
    def dsub(self) -> int:
        return self.codebooks.shape[2]

    @property
    def dim(self) -> int:
        return self.m * self.dsub

    @property
    def nbytes(self) -> int:
        return int(self.codebooks.nbytes)

    @staticmethod
    def train(vectors: np.ndarray, m: int, bits: int = 8, iters: int = 6,
              metric: str = "l2", seed: int = 0) -> "PQCodebook":
        """Lloyd k-means per subspace (init: random corpus rows)."""
        vectors = np.asarray(vectors, np.float32)
        n, dim = vectors.shape
        if dim % m:
            raise ValueError(f"dim {dim} not divisible by pq_m {m}")
        if not 1 <= bits <= 8:
            raise ValueError(f"pq_bits must be in [1, 8] (uint8 codes), "
                             f"got {bits}")
        ksub = min(1 << bits, n)
        dsub = dim // m
        rng = np.random.default_rng(seed)
        books = np.empty((m, ksub, dsub), np.float32)
        subs = vectors.reshape(n, m, dsub)
        for j in range(m):
            sv = subs[:, j, :]
            centers = sv[rng.choice(n, size=ksub, replace=False)].copy()
            for _ in range(iters):
                assign = _nearest_l2(sv, centers)
                for c in range(ksub):
                    sel = assign == c
                    if sel.any():
                        centers[c] = sv[sel].mean(axis=0)
            books[j] = centers
        return PQCodebook(books, metric=metric)

    def encode(self, vectors: np.ndarray) -> np.ndarray:
        """[N, dim] -> uint8 codes [N, m] (nearest L2 center per subspace)."""
        vectors = np.asarray(vectors, np.float32)
        n = vectors.shape[0]
        subs = vectors.reshape(n, self.m, self.dsub)
        codes = np.empty((n, self.m), np.uint8)
        for j in range(self.m):
            codes[:, j] = _nearest_l2(subs[:, j, :], self.codebooks[j])
        return codes

    def decode(self, codes: np.ndarray) -> np.ndarray:
        """uint8 codes [N, m] -> reconstructed vectors [N, dim]."""
        codes = np.asarray(codes)
        parts = [self.codebooks[j][codes[:, j].astype(np.int64)]
                 for j in range(self.m)]
        return np.concatenate(parts, axis=1)

    def luts(self, queries: np.ndarray) -> np.ndarray:
        """[Q, dim] -> score LUTs [Q, m, ksub], higher = better.  The ADC
        scan then evaluates s[q, n] = sum_j lut[q, j, codes[n, j]]."""
        queries = np.asarray(queries, np.float32)
        qn = queries.shape[0]
        qsubs = queries.reshape(qn, self.m, self.dsub)
        # [Q, m, ksub]: einsum over dsub against every center
        ip = np.einsum("qmd,mkd->qmk", qsubs, self.codebooks,
                       dtype=np.float32)
        if self.metric == "ip":
            return np.ascontiguousarray(ip, np.float32)
        q2 = np.sum(qsubs * qsubs, axis=-1)[:, :, None]
        c2 = np.sum(self.codebooks * self.codebooks, axis=-1)[None, :, :]
        return np.ascontiguousarray(-(q2 - 2.0 * ip + c2), np.float32)


def _nearest_l2(x: np.ndarray, centers: np.ndarray) -> np.ndarray:
    """argmin_c ||x - c||^2 via the matmul identity; [N, d] x [K, d] -> [N]."""
    c2 = np.sum(centers * centers, axis=-1)
    # ||x||^2 is constant per row: argmin over centers needs only -2xc + c2
    d = c2[None, :] - 2.0 * (x @ centers.T)
    return d.argmin(axis=1)


def _residual_bias(pq: PQCodebook, codes: np.ndarray, centroids: np.ndarray,
                   buckets: np.ndarray, metric: str) -> np.ndarray:
    """Per-row additive constant of the residual-PQ score decomposition

        s(q, row) = cterm[q, bucket] + sum_j lut[q, j, code_j] + bias[row]

    For L2, expanding -||q - (c_b + r_hat)||^2 leaves the query-independent
    ``-2 c_b . r_hat - ||r_hat||^2`` on the row (r_hat = decode(codes), the
    reconstructed residual); for ip/cosine the cross term vanishes and the
    bias is zero.  Precomputed at encode time so the ADC scan stays one LUT
    sum + two adds per row."""
    n = len(codes)
    if metric != "l2":
        return np.zeros(n, np.float32)
    r = pq.decode(codes)                                     # [N, d]
    c = centroids[np.asarray(buckets).astype(np.int64)]      # [N, d]
    return (-2.0 * np.einsum("nd,nd->n", c, r)
            - np.einsum("nd,nd->n", r, r)).astype(np.float32)


def _terms(entry) -> tuple:
    """A query's filter as its terms: a tuple of terms ANDs them; a lone
    posting or array of ids is the one-term filter.  A tuple whose items
    are not terms (say, a tuple of ids) is refused, not read as terms."""
    if not isinstance(entry, tuple):
        return (entry,)
    if not entry:
        raise ValueError("a filter names at least one term")
    if not all(isinstance(t, (Posting, np.ndarray)) for t in entry):
        raise TypeError("a tuple filter ANDs postings or id arrays; give "
                        "one term's ids as an array")
    return entry


def _term_ids(term) -> np.ndarray:
    """The external ids a filter term (a posting or ids) allows."""
    ids = term.ids if isinstance(term, Posting) else term
    return np.asarray(ids, np.int64).reshape(-1)


# ---------------------------------------------------------------------------
# IVF-Flat / IVF-PQ
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class IVFIndex:
    cfg: VectorIndexConfig
    centroids: np.ndarray                 # [m, d]
    bucket_of: np.ndarray                 # [N] bucket id per vector (sorted)
    vectors: np.ndarray                   # [N, d] compacted rows
    ids: np.ndarray                       # [N] external ids
    serial: int = 1                       # model serial this index was built for
    # IVF-PQ mode (cfg.pq_m > 0): trained codebooks + uint8 codes aligned
    # row-for-row with ``vectors``; the ADC scan reads only ``codes``, the
    # exact re-rank reads ``vectors`` (primary storage).  Residual mode
    # (cfg.pq_residual) quantizes vector - centroid[bucket]; ``code_bias``
    # then carries each row's precomputed score constant (L2's
    # -2*c_b.r_hat - ||r_hat||^2 term; zeros for ip/cosine) so the ADC scan
    # stays one LUT sum + adds per row
    pq: Optional[PQCodebook] = None
    codes: Optional[np.ndarray] = None    # [N, pq_m] uint8
    code_bias: Optional[np.ndarray] = None  # [N] f32 (residual mode only)
    # dynamic-insert append buffers (bucket -> uncompacted rows); searches
    # always include these, compaction folds them into the sorted layout
    _pend_vecs: Dict[int, List[np.ndarray]] = dataclasses.field(
        default_factory=dict, repr=False)
    _pend_ids: Dict[int, List[int]] = dataclasses.field(
        default_factory=dict, repr=False)
    _pend_codes: Dict[int, List[np.ndarray]] = dataclasses.field(
        default_factory=dict, repr=False)
    _pend_bias: Dict[int, List[float]] = dataclasses.field(
        default_factory=dict, repr=False)
    pending_count: int = 0
    # rows scanned so far (the coordinator's per-shard throughput feed)
    scan_rows: int = 0
    # compactions so far: the compacted rows' layout, which a filter
    # bitmap cached for it (:meth:`_filter_words`) is keyed by
    generation: int = dataclasses.field(default=0, init=False,
                                        compare=False)
    # external id -> compacted row lookup of filtered searches, built on
    # the first one after a build or compaction (:meth:`_id_rows`)
    _row_lookup: Optional[tuple] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    # ((generation, (index uid, index generation)), {token: bitmap}): the
    # row bitmaps of long postings of one inverted index's generation
    _bitmaps: Optional[tuple] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    @property
    def n_total(self) -> int:
        """Indexed vectors, compacted + pending."""
        return int(self.ids.shape[0]) + self.pending_count

    def index_bytes(self) -> int:
        """Scan-resident bytes: what a bucket scan actually streams.  PQ
        mode streams uint8 codes (+ codebooks + centroids); flat mode
        streams the float32 rows.  Original vectors kept for re-rank are
        primary storage, touched only for k' candidates per query."""
        base = int(self.centroids.nbytes)
        if self.pq is not None and self.codes is not None:
            pend = sum(len(v) for v in self._pend_codes.values()) * self.pq.m
            return base + int(self.codes.nbytes) + pend + self.pq.nbytes
        pend = self.pending_count * self.vectors.shape[1] * 4
        return base + int(self.vectors.nbytes) + pend

    # -- Algorithm 2: BatchIndexing -------------------------------------------

    @staticmethod
    def build(vectors: np.ndarray, ids: Optional[np.ndarray] = None,
              cfg: Optional[VectorIndexConfig] = None, serial: int = 1,
              seed: int = 0) -> "IVFIndex":
        cfg = cfg or VectorIndexConfig(dim=vectors.shape[1])
        n = vectors.shape[0]
        ids = np.arange(n) if ids is None else np.asarray(ids)
        m = max(cfg.min_buckets, n // cfg.vectors_per_bucket)
        m = min(m, max(1, n))
        rng = np.random.default_rng(seed)
        # random core vectors (paper lines 13-16) ...
        cores = vectors[rng.choice(n, size=m, replace=False)].astype(np.float32)
        # ... plus a few k-means refinements (improves recall, noted in DESIGN)
        v = jnp.asarray(vectors, jnp.float32)
        for _ in range(cfg.kmeans_iters):
            assign = np.asarray(jnp.argmax(
                pairwise_scores(v, jnp.asarray(cores), cfg.metric), axis=1))
            for b in range(m):
                sel = assign == b
                if sel.any():
                    cores[b] = vectors[sel].mean(axis=0)
        assign = np.asarray(jnp.argmax(
            pairwise_scores(v, jnp.asarray(cores), cfg.metric), axis=1))
        order = np.argsort(assign, kind="stable")
        sorted_vecs = np.asarray(vectors, np.float32)[order]
        if cfg.metric == "cosine":
            # normalize once so PQ codes / IP LUTs realize cosine exactly
            sorted_vecs = sorted_vecs / np.maximum(
                np.linalg.norm(sorted_vecs, axis=-1, keepdims=True), 1e-9)
        pq = codes = bias = None
        if cfg.pq_m > 0:
            train_rows = sorted_vecs
            pq_metric = "ip" if cfg.metric in ("ip", "cosine") else "l2"
            if cfg.pq_residual:
                # quantize the residual vector - centroid[bucket]: smaller,
                # better-centered inputs for the same codebook budget.  The
                # LUTs then carry plain sub dot products against the query
                # (the metric lives in the decomposition, not the LUT).
                train_rows = sorted_vecs - cores[assign[order]]
                pq_metric = "ip"
            pq = PQCodebook.train(
                train_rows, cfg.pq_m, bits=cfg.pq_bits,
                iters=cfg.pq_kmeans_iters, metric=pq_metric, seed=seed)
            codes = pq.encode(train_rows)
            if cfg.pq_residual:
                bias = _residual_bias(pq, codes, cores, assign[order],
                                      cfg.metric)
        return IVFIndex(cfg, cores, assign[order], sorted_vecs, ids[order],
                        serial=serial, pq=pq, codes=codes, code_bias=bias)

    # -- Algorithm 2: DynamicIndexing ------------------------------------------

    def insert(self, vec: np.ndarray, ext_id: int) -> int:
        """PickBucket + buffered append (dynamic build for new items).

        Amortized O(1) array work per insert: the vector joins its bucket's
        append buffer and the sorted layout is rebuilt only when the pending
        set crosses the compaction threshold (``pending_compact_frac``)."""
        vec = np.asarray(vec, np.float32)
        if self.cfg.metric == "cosine":
            vec = vec / max(float(np.linalg.norm(vec)), 1e-9)
        scores = _pairwise_scores_np(vec[None], self.centroids,
                                     self.cfg.metric)[0]
        b = int(scores.argmax())
        self._pend_vecs.setdefault(b, []).append(vec)
        self._pend_ids.setdefault(b, []).append(int(ext_id))
        if self.pq is not None:
            enc = vec[None]
            if self.cfg.pq_residual:
                enc = enc - self.centroids[b][None]
            code = self.pq.encode(enc)[0]
            self._pend_codes.setdefault(b, []).append(code)
            if self.cfg.pq_residual:
                self._pend_bias.setdefault(b, []).append(float(
                    _residual_bias(self.pq, code[None], self.centroids,
                                   np.asarray([b]), self.cfg.metric)[0]))
        self.pending_count += 1
        if self.pending_count >= self._compact_threshold():
            self.compact()
        return b

    def insert_many(self, vecs: np.ndarray, ext_ids: np.ndarray) -> np.ndarray:
        """Batched DynamicIndexing: one centroid scoring for all vectors."""
        vecs = np.asarray(vecs, np.float32)
        if self.cfg.metric == "cosine":
            vecs = vecs / np.maximum(
                np.linalg.norm(vecs, axis=-1, keepdims=True), 1e-9)
        assign = np.asarray(jnp.argmax(pairwise_scores(
            jnp.asarray(vecs), jnp.asarray(self.centroids), self.cfg.metric),
            axis=1))
        codes = bias = None
        if self.pq is not None:
            enc = vecs
            if self.cfg.pq_residual:
                enc = vecs - self.centroids[assign]
            codes = self.pq.encode(enc)
            if self.cfg.pq_residual:
                bias = _residual_bias(self.pq, codes, self.centroids,
                                      assign, self.cfg.metric)
        for i, (v, b, eid) in enumerate(zip(vecs, assign,
                                            np.asarray(ext_ids))):
            b = int(b)
            self._pend_vecs.setdefault(b, []).append(v)
            self._pend_ids.setdefault(b, []).append(int(eid))
            if codes is not None:
                self._pend_codes.setdefault(b, []).append(codes[i])
            if bias is not None:
                self._pend_bias.setdefault(b, []).append(float(bias[i]))
        self.pending_count += len(vecs)
        if self.pending_count >= self._compact_threshold():
            self.compact()
        return assign

    def _compact_threshold(self) -> int:
        return max(self.cfg.pending_compact_min,
                   int(self.cfg.pending_compact_frac * len(self.ids)))

    def compact(self) -> None:
        """Fold append buffers into the sorted bucket layout (one stable
        argsort over the concatenation; preserves ``bucket_slice``)."""
        if not self.pending_count:
            return
        add_b: List[int] = []
        add_v: List[np.ndarray] = []
        add_i: List[int] = []
        add_c: List[np.ndarray] = []
        add_s: List[float] = []
        for b in sorted(self._pend_vecs):
            add_b += [b] * len(self._pend_vecs[b])
            add_v += self._pend_vecs[b]
            add_i += self._pend_ids[b]
            if self.pq is not None:
                add_c += self._pend_codes.get(b, [])
                add_s += self._pend_bias.get(b, [])
        bucket_of = np.concatenate(
            [self.bucket_of, np.asarray(add_b, self.bucket_of.dtype)])
        order = np.argsort(bucket_of, kind="stable")
        self.bucket_of = bucket_of[order]
        self.vectors = np.concatenate(
            [self.vectors, np.stack(add_v)])[order]
        self.ids = np.concatenate(
            [self.ids, np.asarray(add_i, self.ids.dtype)])[order]
        if self.pq is not None and self.codes is not None:
            self.codes = np.concatenate(
                [self.codes, np.stack(add_c)])[order]
        if self.code_bias is not None:
            self.code_bias = np.concatenate(
                [self.code_bias, np.asarray(add_s, np.float32)])[order]
        self._pend_vecs.clear()
        self._pend_ids.clear()
        self._pend_codes.clear()
        self._pend_bias.clear()
        self.pending_count = 0
        self._row_lookup = None
        self.generation += 1

    # -- kNN search -------------------------------------------------------------

    def bucket_slice(self, b: int) -> Tuple[int, int]:
        lo = int(np.searchsorted(self.bucket_of, b, side="left"))
        hi = int(np.searchsorted(self.bucket_of, b, side="right"))
        return lo, hi

    def _gather_buckets(self, buckets: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Float rows of the probed buckets, compacted slices + pending
        appends (the float-scan view; ADC scans gather through
        :meth:`_gather_codes` instead and never copy vectors)."""
        if len(buckets) == self.centroids.shape[0]:
            corpus, ids = self._full_corpus()   # exact mode: no copy
            return corpus, ids
        segs = [self.bucket_slice(int(b)) for b in buckets]
        rows = (np.concatenate([np.arange(lo, hi) for lo, hi in segs])
                if segs else np.empty(0, np.int64))
        corpus = self.vectors[rows]
        ids = self.ids[rows]
        pend_v: List[np.ndarray] = []
        pend_i: List[int] = []
        for b in buckets:
            b = int(b)
            if b in self._pend_vecs:
                pend_v += self._pend_vecs[b]
                pend_i += self._pend_ids[b]
        if pend_v:
            corpus = np.concatenate([corpus, np.stack(pend_v)])
            ids = np.concatenate([ids, np.asarray(pend_i, ids.dtype)])
        return corpus, ids

    def search(self, queries: np.ndarray, k: int,
               nprobe: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """ANN search: probe ``nprobe`` nearest buckets, exact scan inside.
        Thin alias of :meth:`search_many` (the batched path is the only
        path)."""
        return self.search_many(queries, k, nprobe)

    def search_many(self, queries: np.ndarray, k: int,
                    nprobe: Optional[int] = None, stats=None,
                    mode: str = "auto", rerank: bool = True,
                    rerank_mult: Optional[int] = None, trace=None,
                    allow: Optional[Sequence[np.ndarray]] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched two-phase kNN over the whole query set.

        Phase 1: one centroid scoring + top-``nprobe`` for all queries.
        Phase 2 takes one of these paths:

        * **signature groups** (float mode) -- queries sharing a probe
          signature (the same bucket set) scan together: their buckets are
          gathered once into a corpus padded to a ``block_n`` multiple
          (stable shapes; the kernel precondition) and dispatched through
          ``ivf_scan_topk`` (Pallas kernel on TPU, fused XLA scan
          elsewhere).  Exact mode (nprobe=m) is one signature, one scan.
        * **staged ADC + exact re-rank** (PQ mode) -- per-query score LUTs,
          an asymmetric-distance top-k' scan of each signature's gathered
          uint8 codes through ``pq_adc_topk`` (k' = ``rerank_mult * k``),
          then an exact re-rank of the k' candidates against the original
          float vectors.  Returned scores are exact, so downstream
          similarity thresholds are unaffected by quantization.
        * **fused probe->ADC->top-k'** (PQ mode) -- ONE masked whole-table
          ADC dispatch for the entire batch: every code row is scanned and
          rows of non-probed buckets are pinned to -inf *in-kernel*
          (``probe_mask``), so there are no per-signature gathers and no
          per-group dispatches at all.  Requires a compacted index (the
          candidate positions must be table rows); pending appends fall
          back to the staged ADC path.  Candidates, scores and tie order
          are identical to the staged path.
        * **row-masked ADC** (filtered, ``allow``) -- one whole-table ADC
          dispatch with each query's filter as a per-row mask; filtered
          batches that cannot take it are scored exactly on the host
          (:meth:`_search_filtered`).
        * **single query** -- a host-side numpy path that skips the
          signature grouping, block padding and device dispatch entirely
          (the per-call overhead dominates one small scan).

        ``mode`` is ``"auto"`` (consult ``stats.choose_knn_scan`` when
        given, else ADC whenever PQ codebooks exist), ``"adc"``,
        ``"float"`` or ``"fused"`` (a hint: batches that cannot fuse --
        single query, pending appends, no codebooks -- silently take the
        staged path).  ``rerank=False`` returns raw ADC scores/ids
        truncated to ``k`` (recall instrumentation).  ``rerank_mult``
        overrides ``cfg.rerank_mult`` for this call (the shard scatter
        splits the global candidate budget this way).  Positions with no
        candidate (probe set smaller than ``k``) hold val=-inf / id=-1.
        ``stats``, if given, receives the observed scan throughput via
        ``record_knn_scan`` / ``record_pq_scan`` / ``record_fused_scan``
        (cost-model feedback).

        ``trace`` (a :class:`repro.obs.Trace`, optional) gets a live span
        per phase, nested under the caller's current span: ``knn.probe``
        (centroid scoring, top-``nprobe``, the copy back to the host, the
        residual centroid term and the signature sort), ``knn.luts``,
        ``knn.gather`` (bucket gathers of the staged paths), one
        ``knn.scan`` per kernel call (``kernel``, ``impl``), ``knn.rerank``
        (row fetch, exact scores, ordering), and ``knn.host_scan`` for the
        single-query host path.  Spans that hand host arrays to a device
        program carry their bytes as ``h2d_bytes``.

        ``allow`` (filtered kNN) gives each query its filter: one term or
        a tuple of terms, ANDed, each term an array of allowed external
        ids (a repeated id counts once) or a
        :class:`~repro.core.scalar_index.Posting`.  A query is answered
        from the rows that pass its filter only, so it may return fewer
        than ``k`` (the tail is -inf / -1) and never another row.  A
        filtered query probes every bucket, the filter being its only mask
        (``nprobe`` does not apply).  See :meth:`_search_filtered` for the
        paths it takes."""
        if mode not in ("auto", "adc", "float", "fused"):
            raise ValueError(f"unknown scan mode {mode!r}; "
                             f"expected auto | adc | float | fused")
        queries = np.asarray(queries, np.float32)
        qn = queries.shape[0]
        out_v = np.full((qn, k), -np.inf, np.float32)
        out_i = np.full((qn, k), -1, np.int64)
        if allow is not None and len(allow) != qn:
            raise ValueError(f"{len(allow)} filters for {qn} queries")
        if qn == 0 or self.n_total == 0:
            return out_v, out_i
        if allow is not None:
            self._search_filtered(queries, allow, k, mode, stats, out_v,
                                  out_i, rerank, rerank_mult, trace)
            return out_v, out_i
        m = self.centroids.shape[0]
        nprobe = min(nprobe or self.cfg.nprobe, m)
        kind = self._pick_scan(mode, stats, qn, k)
        if qn == 1:
            t0 = time.perf_counter()
            with maybe_span(trace, "knn.host_scan"):
                rows_scanned = self._search_one(queries, k, nprobe, out_v,
                                                out_i, kind == "adc", rerank,
                                                rerank_mult)
            self._note_scan(stats, time.perf_counter() - t0, rows_scanned,
                            kind)
            return out_v, out_i
        with maybe_span(trace, "knn.probe") as sp:
            count_h2d("probe", sp, queries, self.centroids)
            cscores = pairwise_scores(jnp.asarray(queries),
                                      jnp.asarray(self.centroids),
                                      self.cfg.metric)
            _, probe = jax.lax.top_k(cscores, nprobe)      # [Q, nprobe]
            cterm = None
            if self.cfg.pq_residual and kind in ("adc", "fused"):
                cterm = self._cterm_np(queries, np.asarray(cscores))
            # probe *signature* = the bucket set; sort so order never
            # splits groups
            probe = np.sort(np.asarray(probe), axis=1)
        t0 = time.perf_counter()
        if kind == "fused":
            rows_scanned = self._scan_fused(queries, cterm, probe, k,
                                            out_v, out_i, rerank,
                                            rerank_mult, trace)
        else:
            sigs, inverse = np.unique(probe, axis=0, return_inverse=True)
            if kind == "adc":
                rows_scanned = self._scan_groups_pq(queries, sigs, inverse,
                                                    k, out_v, out_i, rerank,
                                                    cterm, rerank_mult,
                                                    trace)
            else:
                rows_scanned = self._scan_groups(queries, sigs, inverse, k,
                                                 out_v, out_i, trace)
        self._note_scan(stats, time.perf_counter() - t0, rows_scanned,
                        kind)
        return out_v, out_i

    def _pick_scan(self, mode: str, stats, qn: int, k: int) -> str:
        """Resolve the scan layout: "float" | "adc" | "fused".  The fused
        hint degrades to staged ADC whenever its preconditions fail (one
        query, pending appends); "auto" asks the cost model, which only
        returns "fused" after observing a real fused measurement."""
        if self.pq is None or self.codes is None or mode == "float":
            return "float"
        if mode == "fused":
            return ("fused" if qn > 1 and self.pending_count == 0
                    else "adc")
        if mode == "adc":
            return "adc"
        if stats is not None:
            return stats.choose_knn_scan(self, q=qn, k=k)
        return "adc"

    def _note_scan(self, stats, dt: float, rows_scanned: int,
                   kind: str) -> None:
        self.scan_rows += rows_scanned
        if stats is not None and rows_scanned:
            if kind == "fused":
                stats.record_fused_scan(dt, rows_scanned)
            elif kind == "adc":
                stats.record_pq_scan(dt, rows_scanned)
            else:
                stats.record_knn_scan(dt, rows_scanned)

    def _norm_queries(self, queries: np.ndarray) -> np.ndarray:
        """Cosine realizes as IP over unit vectors (stored rows are
        normalized at build/insert); l2/ip pass through."""
        if self.cfg.metric != "cosine":
            return queries
        return queries / np.maximum(
            np.linalg.norm(queries, axis=-1, keepdims=True), 1e-9)

    def _kprime(self, k_eff: int, n_real: int, rerank: bool,
                rerank_mult: Optional[int] = None) -> int:
        """ADC candidate fanout: the re-rank stage reads this many rows.
        ``rerank_mult`` overrides the config multiplier -- the shard
        scatter path splits the *global* candidate budget across shards
        (``ceil(cfg.rerank_mult / P)`` each) so total re-rank work stays
        constant as the shard count grows."""
        if not rerank:
            return k_eff
        rm = self.cfg.rerank_mult if rerank_mult is None else rerank_mult
        return min(n_real, max(k_eff, rm * k_eff))

    def _pq_luts(self, queries: np.ndarray) -> np.ndarray:
        """Score LUTs for the ADC scan.  Residual L2 doubles the IP LUTs:
        the decomposition's query term is ``2 q . r_hat`` (the codebook is
        trained metric="ip", so ``pq.luts`` yields plain sub dot
        products)."""
        luts = self.pq.luts(self._norm_queries(queries))
        if self.cfg.pq_residual and self.cfg.metric == "l2":
            luts = luts * np.float32(2.0)
        return luts

    def _cterm_np(self, queries: np.ndarray, cscores: np.ndarray
                  ) -> np.ndarray:
        """[Q, m] per-query centroid term of the residual decomposition.
        For l2/ip it IS the probe score (``-||q - c_b||^2`` / ``q . c_b``);
        cosine probes score against *normalized* centroids but the residual
        sits on the raw centroid, so recompute q_hat . c_b here."""
        if self.cfg.metric != "cosine":
            return np.asarray(cscores, np.float32)
        qn_ = self._norm_queries(queries)
        return (qn_ @ self.centroids.T).astype(np.float32)

    def _gather_codes(self, buckets: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 Optional[np.ndarray], Optional[np.ndarray],
                                 Optional[np.ndarray]]:
        """ADC view of the probed buckets: (codes, ids, comp_rows,
        pend_stack, row_bucket, bias).  Only the uint8 codes are copied;
        original float rows stay in place -- re-rank fetches just the k'
        candidates through :meth:`_fetch_rows`.  Result positions <
        len(comp_rows) map to compacted table rows ``comp_rows[pos]``;
        later positions map into ``pend_stack[pos - len(comp_rows)]``
        (uncompacted appends).  ``row_bucket`` / ``bias`` carry the
        residual decomposition's per-row terms and are None unless
        ``cfg.pq_residual``."""
        residual = self.cfg.pq_residual
        if len(buckets) == self.centroids.shape[0]:
            # exact mode: identity row map, no table copy
            comp_rows = np.arange(len(self.ids))
            pend_sel = sorted(self._pend_vecs)
            codes, ids = self.codes, self.ids
            rb = self.bucket_of if residual else None
            bias = self.code_bias if residual else None
        else:
            segs = [self.bucket_slice(int(b)) for b in buckets]
            comp_rows = (np.concatenate([np.arange(lo, hi)
                                         for lo, hi in segs])
                         if segs else np.empty(0, np.int64))
            pend_sel = [int(b) for b in buckets if int(b) in self._pend_vecs]
            codes = self.codes[comp_rows]
            ids = self.ids[comp_rows]
            rb = self.bucket_of[comp_rows] if residual else None
            bias = (self.code_bias[comp_rows] if residual else None)
        pend_v: List[np.ndarray] = []
        pend_i: List[int] = []
        pend_c: List[np.ndarray] = []
        pend_s: List[float] = []
        pend_b: List[int] = []
        for b in pend_sel:
            pend_v += self._pend_vecs[b]
            pend_i += self._pend_ids[b]
            pend_c += self._pend_codes.get(b, [])
            if residual:
                pend_s += self._pend_bias.get(b, [])
                pend_b += [b] * len(self._pend_vecs[b])
        pend_stack = None
        if pend_v:
            pend_stack = np.stack(pend_v)
            codes = np.concatenate([codes, np.stack(pend_c)])
            ids = np.concatenate([ids, np.asarray(pend_i, ids.dtype)])
            if residual:
                rb = np.concatenate(
                    [rb, np.asarray(pend_b, self.bucket_of.dtype)])
                bias = np.concatenate(
                    [bias, np.asarray(pend_s, np.float32)])
        return codes, ids, comp_rows, pend_stack, rb, bias

    def _fetch_rows(self, comp_rows: np.ndarray,
                    pend_stack: Optional[np.ndarray],
                    idx: np.ndarray) -> np.ndarray:
        """Original float rows of ADC candidates: [..., k'] local positions
        -> [..., k', d] vectors (the re-rank's only float traffic)."""
        nc = len(comp_rows)
        flat = idx.reshape(-1)
        out = np.empty((flat.size, self.vectors.shape[1]), np.float32)
        is_comp = flat < nc
        out[is_comp] = self.vectors[comp_rows[flat[is_comp]]]
        if pend_stack is not None and not is_comp.all():
            out[~is_comp] = pend_stack[flat[~is_comp] - nc]
        return out.reshape(*idx.shape, -1)

    def _search_one(self, queries: np.ndarray, k: int, nprobe: int,
                    out_v: np.ndarray, out_i: np.ndarray,
                    use_adc: bool, rerank: bool,
                    rerank_mult: Optional[int] = None) -> int:
        """Single-query fast path: numpy end-to-end.  One centroid scoring,
        one bucket gather, one scan -- no signature grouping, no block
        padding, no device round-trip.  Candidate order matches the batched
        path (descending score, ties to the lower row index)."""
        m = self.centroids.shape[0]
        cscores = _pairwise_scores_np(queries, self.centroids,
                                      self.cfg.metric)[0]
        if nprobe >= m:
            buckets = np.arange(m)
        else:
            buckets = np.sort(np.argpartition(-cscores, nprobe - 1)[:nprobe])
        if use_adc:
            codes, ids, comp_rows, pend_stack, rb, bias = \
                self._gather_codes(buckets)
            n_real = codes.shape[0]
            if n_real == 0:
                return 0
            k_eff = min(k, n_real)
            lut = self._pq_luts(queries)[0]                  # [m, ksub]
            s = lut[np.arange(self.pq.m)[None, :],
                    codes.astype(np.int64)].sum(axis=1)
            if rb is not None:
                # residual decomposition: + per-row bias + centroid term
                cterm = self._cterm_np(queries, cscores[None])[0]
                s = s + bias + cterm[rb.astype(np.int64)]
            kprime = self._kprime(k_eff, n_real, rerank, rerank_mult)
            # sort candidate positions ascending so score ties resolve to
            # the lower row index (argpartition's order is arbitrary; the
            # batched path's lax.top_k is stable)
            cand = (np.sort(np.argpartition(-s, kprime - 1)[:kprime])
                    if kprime < n_real else np.arange(n_real))
            if rerank:
                vecs = self._fetch_rows(comp_rows, pend_stack, cand)
                exact = _exact_scores_np(queries, vecs[None],
                                         self.cfg.metric)[0]
                order = _stable_topk_desc(exact, k_eff)
                out_v[0, :k_eff] = exact[order]
            else:
                adc = s[cand]
                order = _stable_topk_desc(adc, k_eff)
                out_v[0, :k_eff] = adc[order]
            out_i[0, :k_eff] = ids[cand[order]]
            return n_real
        corpus, ids = self._gather_buckets(buckets)
        n_real = corpus.shape[0]
        if n_real == 0:
            return 0
        k_eff = min(k, n_real)
        s = _pairwise_scores_np(queries, corpus, self.cfg.metric)[0]
        # ascending candidate positions: ties resolve to the lower row
        # index, matching the batched path's lax.top_k order
        top = (np.sort(np.argpartition(-s, k_eff - 1)[:k_eff])
               if k_eff < n_real else np.arange(n_real))
        order = top[_stable_topk_desc(s[top], k_eff)]
        out_v[0, :k_eff] = s[order]
        out_i[0, :k_eff] = ids[order]
        return n_real

    def _scan_groups(self, queries: np.ndarray, sigs: np.ndarray,
                     inverse: np.ndarray, k: int,
                     out_v: np.ndarray, out_i: np.ndarray,
                     trace=None) -> int:
        """One fused gathered scan per distinct probe signature."""
        rows_scanned = 0
        for g in range(sigs.shape[0]):
            qsel = np.nonzero(inverse == g)[0]
            with maybe_span(trace, "knn.gather"):
                corpus, ids = self._gather_buckets(sigs[g])
            n_real = corpus.shape[0]
            if n_real == 0:
                continue
            k_eff = min(k, n_real)
            with maybe_span(trace, "knn.scan") as sp:
                vals, idx = ivf_scan_topk(queries[qsel], corpus, k_eff,
                                          metric=self.cfg.metric,
                                          block_n=self.cfg.block_n, span=sp)
            out_v[qsel[:, None], np.arange(k_eff)[None, :]] = vals
            out_i[qsel[:, None], np.arange(k_eff)[None, :]] = ids[idx]
            rows_scanned += n_real * len(qsel)
        return rows_scanned

    def _scan_groups_pq(self, queries: np.ndarray, sigs: np.ndarray,
                        inverse: np.ndarray, k: int,
                        out_v: np.ndarray, out_i: np.ndarray,
                        rerank: bool, cterm: Optional[np.ndarray] = None,
                        rerank_mult: Optional[int] = None,
                        trace=None) -> int:
        """PQ two-stage scan, one dispatch per distinct probe signature:
        ADC top-k' over the gathered uint8 codes (``pq_adc_topk``: Pallas
        kernel on TPU, fused XLA gathers elsewhere), then exact re-rank of
        the k' candidates against the original float rows.  ``cterm``
        ([Q, m], residual mode) carries each query's centroid term; the
        per-row bias + bucket id ride along from :meth:`_gather_codes`."""
        with maybe_span(trace, "knn.luts"):
            luts = self._pq_luts(queries)                    # [Q, m, ksub]
        rows_scanned = 0
        for g in range(sigs.shape[0]):
            qsel = np.nonzero(inverse == g)[0]
            with maybe_span(trace, "knn.gather"):
                codes, ids, comp_rows, pend_stack, rb, bias = \
                    self._gather_codes(sigs[g])
            n_real = codes.shape[0]
            if n_real == 0:
                continue
            k_eff = min(k, n_real)
            kprime = self._kprime(k_eff, n_real, rerank, rerank_mult)
            with maybe_span(trace, "knn.scan") as sp:
                vals, idx = pq_adc_topk(
                    luts[qsel], codes, kprime, block_n=self.cfg.block_n,
                    bias=bias, row_bucket=rb,
                    cscores=(None if cterm is None else cterm[qsel]),
                    span=sp)
            idx = idx.astype(np.int64)                       # [Qg, k']
            if rerank:
                with maybe_span(trace, "knn.rerank"):
                    cand = self._fetch_rows(comp_rows, pend_stack,
                                            idx)             # [Qg, k', d]
                    exact = _exact_scores_np(queries[qsel], cand,
                                             self.cfg.metric)  # [Qg, k']
                    order = np.argsort(-exact, axis=1,
                                       kind="stable")[:, :k_eff]
                rows = np.arange(len(qsel))[:, None]
                out_v[qsel[:, None], np.arange(k_eff)[None, :]] = \
                    exact[rows, order]
                out_i[qsel[:, None], np.arange(k_eff)[None, :]] = \
                    ids[idx[rows, order]]
            else:
                out_v[qsel[:, None], np.arange(k_eff)[None, :]] = \
                    vals[:, :k_eff]
                out_i[qsel[:, None], np.arange(k_eff)[None, :]] = \
                    ids[idx[:, :k_eff]]
            rows_scanned += n_real * len(qsel)
        return rows_scanned

    def _scan_fused(self, queries: np.ndarray, cterm: Optional[np.ndarray],
                    probe: np.ndarray, k: int,
                    out_v: np.ndarray, out_i: np.ndarray,
                    rerank: bool, rerank_mult: Optional[int] = None,
                    trace=None) -> int:
        """Fused probe->ADC->top-k': ONE ``pq_adc_topk`` dispatch over the
        whole code table for the entire batch, each query's non-probed
        buckets pinned to -inf in-kernel via ``probe_mask`` -- no signature
        grouping, no per-group gathers or dispatches.  Precondition (held
        by :meth:`_pick_scan`): the index is compacted, so candidate
        positions ARE table rows and the re-rank fetch is an identity
        gather.  Candidates, tie order and returned scores are identical
        to the staged ADC path: probed rows enter the top-k' in the same
        ascending-row order the per-signature gathers would produce, and a
        query probing fewer than k' rows surfaces the same (-inf, -1)
        tail."""
        m = self.centroids.shape[0]
        qn = queries.shape[0]
        n_real = len(self.ids)
        if n_real == 0:
            return 0
        k_eff = min(k, n_real)
        kprime = self._kprime(k_eff, n_real, rerank, rerank_mult)
        pm = np.zeros((qn, m), bool)
        pm[np.arange(qn)[:, None], probe] = True
        with maybe_span(trace, "knn.luts"):
            luts = self._pq_luts(queries)                    # [Q, m, ksub]
        residual = cterm is not None
        with maybe_span(trace, "knn.scan") as sp:
            vals, idx = pq_adc_topk(
                luts, self.codes, kprime, block_n=self.cfg.block_n,
                bias=(self.code_bias if residual else None),
                row_bucket=self.bucket_of,
                cscores=(cterm if residual else None), probe_mask=pm,
                span=sp)
        self._rerank_table(queries, vals, idx, k_eff, rerank, out_v, out_i,
                           trace)
        return qn * n_real

    def _rerank_table(self, queries: np.ndarray, vals: np.ndarray,
                      idx: np.ndarray, k_eff: int, rerank: bool,
                      out_v: np.ndarray, out_i: np.ndarray,
                      trace=None) -> None:
        """A whole-table scan's top-k' (table rows; -1 where a query had
        no candidate) -> ``out_v`` / ``out_i``: the exact re-rank of the
        candidates (or their ADC scores with ``rerank=False``).  A query
        with fewer candidates than ``k_eff`` keeps its (-inf, -1) tail."""
        qn = queries.shape[0]
        idx = idx.astype(np.int64)                           # [Q, k']; -1 pad
        valid = idx >= 0
        safe = np.where(valid, idx, 0)
        rows = np.arange(qn)[:, None]
        if rerank:
            with maybe_span(trace, "knn.rerank"):
                cand = self.vectors[safe]                    # [Q, k', d]
                exact = _exact_scores_np(queries, cand, self.cfg.metric)
                exact = np.where(valid, exact, -np.inf)
                order = np.argsort(-exact, axis=1, kind="stable")[:, :k_eff]
            v = exact[rows, order]
            gid = self.ids[safe][rows, order]
        else:
            v = vals[:, :k_eff]
            gid = self.ids[safe[:, :k_eff]]
        out_v[:, :k_eff] = v
        out_i[:, :k_eff] = np.where(np.isfinite(v), gid, -1)

    # -- filtered kNN -----------------------------------------------------------

    def _search_filtered(self, queries: np.ndarray,
                         allow: Sequence[np.ndarray], k: int, mode: str,
                         stats, out_v: np.ndarray, out_i: np.ndarray,
                         rerank: bool, rerank_mult: Optional[int],
                         trace=None) -> None:
        """Filtered kNN over every bucket, each query restricted to the
        rows its ``allow`` filter passes.  A batch on a compacted PQ index
        takes ONE
        row-masked ADC dispatch over the whole code table
        (:meth:`_scan_rowmask`), then the exact re-rank.  One query,
        pending appends, a flat index or ``mode="float"`` take the host
        path instead (:meth:`_scan_filtered_host`): exact scores of the
        allowed rows, counted as ``knn_filter_host_queries``.  No path
        returns a row outside a query's filter; no staged path takes a
        filter."""
        t0 = time.perf_counter()
        if (self.pq is not None and self.codes is not None
                and mode != "float" and queries.shape[0] > 1
                and self.pending_count == 0):
            rows_scanned = self._scan_rowmask(queries, allow, k, out_v,
                                              out_i, rerank, rerank_mult,
                                              trace)
            self._note_scan(stats, time.perf_counter() - t0, rows_scanned,
                            "fused")
        else:
            self.scan_rows += self._scan_filtered_host(queries, allow, k,
                                                       out_v, out_i, trace)

    def _id_rows(self, ext_ids: np.ndarray) -> np.ndarray:
        """Compacted table row of each external id, -1 where it has none.
        The lookup is built once per build or compaction, on the first
        filtered search after it: a dense table where the ids are dense
        (a shard of ``P`` holds about every ``P``-th id), else the sorted
        ids.  The dense table is kept for speed: over 10**5 rows a batch's
        10**6 lookups cost about a tenth of the binary searches."""
        lk = self._row_lookup
        if lk is None:
            ids = self.ids.astype(np.int64)
            top = int(ids.max()) + 1 if len(ids) else 0
            if len(ids) and int(ids.min()) >= 0 and top <= 128 * len(ids):
                dense = np.full(top, -1, np.int32)
                dense[ids] = np.arange(len(ids), dtype=np.int32)
                lk = (dense,)
            else:
                order = np.argsort(ids, kind="stable")
                lk = (ids[order], order)
            self._row_lookup = lk
        ext_ids = np.asarray(ext_ids, np.int64)
        if len(lk) == 1:
            dense = lk[0]
            ok = (ext_ids >= 0) & (ext_ids < len(dense))
            return np.where(ok, dense[np.where(ok, ext_ids, 0)], -1)
        keys, order = lk
        if not len(keys):
            return np.full(len(ext_ids), -1, np.int64)
        j = np.minimum(np.searchsorted(keys, ext_ids), len(keys) - 1)
        return np.where(keys[j] == ext_ids, order[j], -1)

    def _filter_words(self, allow: Sequence, qrows: Optional[int] = None
                      ) -> Tuple[np.ndarray, int, int]:
        """Each query's filter as a bitmap over the compacted rows:
        ([qrows or Q, padded_rows(n) / 32] uint32, cached, built), bit
        r % 32 of word r // 32 set where the query may return row r; rows
        past the queries, and bits past the last real row, are zero.

        A query's filter is one term or a tuple of terms, ANDed.  A term is
        a :class:`~repro.core.scalar_index.Posting` or an array of external
        ids; ids with no compacted row drop out.  A batch builds each
        distinct term's bitmap once.  A posting of at least
        padded_rows / 64 ids, whose bitmap is then no larger than the
        posting, keeps it in a cache keyed by this index's generation and
        the posting's key, so a compaction or an index insert never serves
        a stale one; shorter terms are built per call.  ``cached`` and
        ``built`` count the distinct terms each way, also as the
        ``knn_filter_bitmap:cached`` / ``:built`` counters."""
        rows = padded_rows(len(self.ids), self.cfg.block_n)
        w = rows // 32
        slot_of: Dict[object, int] = {}
        slots: List[object] = []
        index: List[List[int]] = []
        for entry in allow:
            mine = []
            for t in _terms(entry):
                key = ((t.owner, t.token) if isinstance(t, Posting)
                       else id(t))
                if key not in slot_of:
                    slot_of[key] = len(slots)
                    slots.append(t)
                mine.append(slot_of[key])
            index.append(mine)
        table = np.empty((len(slots) + 1, w), np.uint32)
        table[-1] = np.uint32(0xFFFFFFFF)        # pads a shorter AND
        small: List[int] = []
        cached = built = 0
        for j, t in enumerate(slots):
            if not isinstance(t, Posting) or len(t.ids) * 64 < rows:
                small.append(j)
                continue
            cache = self._bitmap_cache(t.owner)
            bm = cache.get(t.token)
            if bm is None:
                bm = cache[t.token] = self._term_bitmaps([t], w)[0]
                built += 1
            else:
                cached += 1
            table[j] = bm
        if small:
            table[small] = self._term_bitmaps([slots[j] for j in small], w)
            built += len(small)
        METRICS.counter("knn_filter_bitmap:cached").inc(cached)
        METRICS.counter("knn_filter_bitmap:built").inc(built)
        depth = max(map(len, index), default=1)
        cols = np.full((len(index), depth), len(slots), np.int64)
        for q, m in enumerate(index):
            cols[q, :len(m)] = m
        out = np.zeros((qrows or len(index), w), np.uint32)
        np.take(table, cols[:, 0], axis=0, out=out[:len(index)])
        for d in range(1, depth):
            sel = np.flatnonzero(cols[:, d] < len(slots))
            out[sel] &= table[cols[sel, d]]
        return out, cached, built

    def _bitmap_cache(self, owner: tuple) -> dict:
        """token -> cached row bitmap, for postings of ``owner`` (an
        inverted index and its generation) on this index's current rows;
        a posting of another index, generation or row layout starts a new
        cache, so the cache holds one inverted index's long postings at
        most."""
        key = (self.generation, owner)
        held = self._bitmaps
        if held is None or held[0] != key:
            held = self._bitmaps = (key, {})
        return held[1]

    def _term_bitmaps(self, terms: Sequence, w: int) -> np.ndarray:
        """[len(terms), w] uint32 row bitmaps of ``terms`` (postings or id
        arrays), from one id -> row lookup and one scatter."""
        ids = [_term_ids(t) for t in terms]
        counts = np.fromiter((len(a) for a in ids), np.int64, len(ids))
        out = np.zeros(len(ids) * w, np.uint32)
        if counts.sum():
            pos = self._id_rows(np.concatenate(ids))
            term = np.repeat(np.arange(len(ids)), counts)
            hit = pos >= 0
            pos, term = pos[hit], term[hit]
            np.bitwise_or.at(out, term * w + (pos >> 5),
                             np.left_shift(np.uint32(1),
                                           (pos & 31).astype(np.uint32)))
        return out.reshape(len(ids), w)

    def _allowed_rows(self, allow: Sequence, pending: bool = False
                      ) -> Tuple[List[np.ndarray], int, int]:
        """Each query's allowed row positions, sorted, for the host path:
        the compacted rows of its bitmap (:meth:`_filter_words`), then
        (``pending``) the appended rows, numbered after them in
        :meth:`_full_corpus` order, whose id passes every term and has no
        compacted row.  Returns (rows, cached, built)."""
        words, cached, built = self._filter_words(allow)
        n = len(self.ids)
        bits = np.unpackbits(words.astype("<u4").view(np.uint8), axis=1,
                             count=n, bitorder="little")
        rows = [np.flatnonzero(b) for b in bits]
        if pending and self.pending_count:
            pend = np.asarray([i for b in sorted(self._pend_ids)
                               for i in self._pend_ids[b]], np.int64)
            uniq, first = np.unique(pend, return_index=True)
            keep = np.sort(first[self._id_rows(uniq) < 0])
            for q, entry in enumerate(allow):
                ok = np.ones(len(keep), bool)
                for t in _terms(entry):
                    ok &= np.isin(pend[keep], _term_ids(t))
                rows[q] = np.concatenate([rows[q], n + keep[ok]])
        return rows, cached, built

    def _scan_rowmask(self, queries: np.ndarray, allow: Sequence,
                      k: int, out_v: np.ndarray, out_i: np.ndarray,
                      rerank: bool, rerank_mult: Optional[int] = None,
                      trace=None) -> int:
        """The fused scan with a per-(query, row) mask for the probe mask:
        every bucket's centroid term on the host (``knn.probe``), each
        query's row bitmap (:meth:`_filter_words`) uploaded and transposed
        into the kernel's packed layout on the device (``knn.filter``),
        ONE ``pq_scan_rowmask`` dispatch over the whole code table, then
        the exact re-rank of the candidates, which are all allowed rows."""
        qn = queries.shape[0]
        n_real = len(self.ids)
        k_eff = min(k, n_real)
        kprime = self._kprime(k_eff, n_real, rerank, rerank_mult)
        residual = self.cfg.pq_residual
        cterm = None
        if residual:
            with maybe_span(trace, "knn.probe"):
                cterm = self._cterm_np(queries, _pairwise_scores_np(
                    queries, self.centroids, self.cfg.metric))
        with maybe_span(trace, "knn.filter") as sp:
            words, cached, built = self._filter_words(allow,
                                                      padded_queries(qn))
            count_h2d("row_mask", sp, words)
            mask = row_mask_from_bitmaps(words)
            if sp is not None:
                sp.set(rows_allowed=int(np.bitwise_count(
                           words.view(np.uint64)).sum()),
                       bitmaps_cached=cached, bitmaps_built=built)
        with maybe_span(trace, "knn.luts"):
            luts = self._pq_luts(queries)                    # [Q, m, ksub]
        with maybe_span(trace, "knn.scan") as sp:
            vals, idx = pq_adc_topk(
                luts, self.codes, kprime, block_n=self.cfg.block_n,
                bias=(self.code_bias if residual else None),
                row_bucket=(self.bucket_of if residual else None),
                cscores=cterm, row_mask=mask, span=sp)
        self._rerank_table(queries, vals, idx, k_eff, rerank, out_v, out_i,
                           trace)
        return qn * n_real

    def _scan_filtered_host(self, queries: np.ndarray, allow: Sequence,
                            k: int, out_v: np.ndarray, out_i: np.ndarray,
                            trace=None) -> int:
        """Exact scores of each query's allowed rows, compacted and
        pending, in numpy; ties to the lower row."""
        METRICS.counter("knn_filter_host_queries").inc(queries.shape[0])
        with maybe_span(trace, "knn.filter") as sp:
            rows, cached, built = self._allowed_rows(allow, pending=True)
            if sp is not None:
                sp.set(rows_allowed=int(sum(len(r) for r in rows)),
                       bitmaps_cached=cached, bitmaps_built=built)
        with maybe_span(trace, "knn.host_scan"):
            corpus, ids = self._full_corpus()
            for q, r in enumerate(rows):
                if not len(r):
                    continue
                s = _exact_scores_np(queries[q:q + 1], corpus[r][None],
                                     self.cfg.metric)[0]
                top = _stable_topk_desc(s, min(k, len(r)))
                out_v[q, :len(top)] = s[top]
                out_i[q, :len(top)] = ids[r[top]]
        return int(sum(len(r) for r in rows))

    def _full_corpus(self) -> Tuple[np.ndarray, np.ndarray]:
        """(vectors, ids) over compacted + pending rows."""
        if not self.pending_count:
            return self.vectors, self.ids
        pend_v: List[np.ndarray] = []
        pend_i: List[int] = []
        for b in sorted(self._pend_vecs):
            pend_v += self._pend_vecs[b]
            pend_i += self._pend_ids[b]
        return (np.concatenate([self.vectors, np.stack(pend_v)]),
                np.concatenate([self.ids, np.asarray(pend_i, self.ids.dtype)]))

    def search_exact(self, queries: np.ndarray, k: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Brute-force ground truth (recall denominator): the batched
        *float* scan with every bucket probed, truncated to the real
        candidate count.  Always float mode -- the truth must not be
        quantized."""
        v, i = self.search_many(queries, k, nprobe=self.centroids.shape[0],
                                mode="float")
        kk = min(k, self.n_total)
        return v[:, :kk], i[:, :kk]

    def retrain_pq(self, stats=None, seed: int = 0) -> None:
        """Re-train the codebooks over the current corpus and re-encode
        every row (codebook drift after sustained dynamic inserts).  Bumps
        the statistics epoch when ``stats`` is given, so cached plans
        re-optimize against the fresh index."""
        if self.cfg.pq_m <= 0:
            return
        self.compact()
        train_rows = self.vectors
        pq_metric = "ip" if self.cfg.metric in ("ip", "cosine") else "l2"
        if self.cfg.pq_residual:
            train_rows = self.vectors - self.centroids[
                self.bucket_of.astype(np.int64)]
            pq_metric = "ip"
        self.pq = PQCodebook.train(
            train_rows, self.cfg.pq_m, bits=self.cfg.pq_bits,
            iters=self.cfg.pq_kmeans_iters, metric=pq_metric, seed=seed)
        self.codes = self.pq.encode(train_rows)
        if self.cfg.pq_residual:
            self.code_bias = _residual_bias(self.pq, self.codes,
                                            self.centroids, self.bucket_of,
                                            self.cfg.metric)
        if stats is not None:
            stats.note_index_rebuild("pq_retrain")

    def shard(self, n_shards: int, strategy: str = "hash",
              assign: Optional[np.ndarray] = None) -> List["IVFIndex"]:
        """Split bucket contents across shards (distributed layout:
        centroids + codebooks replicated, contents sharded).

        ``strategy="hash"`` (default) partitions by :func:`stable_id_hash`
        of the external id -- membership survives compaction reorders and
        rebuilds, which deterministic owner-shard routing requires.
        ``strategy="roundrobin"`` keeps the legacy positional split (row
        index mod n_shards; membership shifts whenever rows reorder --
        load-balancing only).  ``assign`` overrides both with an explicit
        per-row shard id (the cluster coordinator passes the *node* owner
        of each blob so a shard's index piece covers exactly the blobs its
        graph slice owns)."""
        self.compact()
        if assign is not None:
            assign = np.asarray(assign, np.int64)
            if assign.shape[0] != len(self.ids):
                raise ValueError(f"assign has {assign.shape[0]} entries for "
                                 f"{len(self.ids)} rows")
        elif strategy == "hash":
            assign = owner_shard(self.ids, n_shards)
        elif strategy == "roundrobin":
            assign = np.arange(len(self.ids)) % n_shards
        else:
            raise ValueError(f"unknown shard strategy {strategy!r}; "
                             f"expected hash | roundrobin")
        shards = []
        for s in range(n_shards):
            sel = assign == s
            shards.append(IVFIndex(self.cfg, self.centroids,
                                   self.bucket_of[sel], self.vectors[sel],
                                   self.ids[sel], serial=self.serial,
                                   pq=self.pq,
                                   codes=(self.codes[sel]
                                          if self.codes is not None
                                          else None),
                                   code_bias=(self.code_bias[sel]
                                              if self.code_bias is not None
                                              else None)))
        return shards

    @staticmethod
    def merge_pieces(pieces: Sequence["IVFIndex"]) -> "IVFIndex":
        """Reassemble one global index from shard pieces (the rebalance /
        dead-shard-recovery path: gather surviving pieces, merge, then
        re-deal with ``shard(assign=)`` under the new owner map).

        Pieces must share centroids + codebooks (``shard()`` slices one
        build, so they do).  Rows are re-sorted by (bucket, external id),
        which reproduces the original batch-build layout exactly -- the
        build groups blob-id-sorted input stably by bucket -- so a merged
        index re-sharded under the same assignment is bit-identical to the
        original pieces.  Rows appended by DynamicIndexing sit in insertion
        order within their bucket, so after dynamic inserts the merged
        layout can differ from the pre-merge one in tie order only."""
        pieces = list(pieces)
        if not pieces:
            raise ValueError("merge_pieces needs at least one piece")
        for p in pieces:
            p.compact()
        base = pieces[0]
        bucket = np.concatenate([p.bucket_of for p in pieces])
        vecs = np.concatenate([p.vectors for p in pieces])
        ids = np.concatenate([p.ids for p in pieces])
        codes = (np.concatenate([p.codes for p in pieces])
                 if base.codes is not None else None)
        bias = (np.concatenate([p.code_bias for p in pieces])
                if base.code_bias is not None else None)
        order = np.lexsort((ids, bucket))
        return IVFIndex(base.cfg, base.centroids, bucket[order], vecs[order],
                        ids[order], serial=base.serial, pq=base.pq,
                        codes=(codes[order] if codes is not None else None),
                        code_bias=(bias[order] if bias is not None else None))


def _exact_scores_np(queries: np.ndarray, cand: np.ndarray, metric: str
                     ) -> np.ndarray:
    """Re-rank scoring: [Q, d] x [Q, k', d] -> [Q, k'], higher is better."""
    queries = np.asarray(queries, np.float32)
    cand = np.asarray(cand, np.float32)
    if metric == "ip":
        return np.einsum("qd,qkd->qk", queries, cand, dtype=np.float32)
    if metric == "cosine":
        qn = queries / np.maximum(
            np.linalg.norm(queries, axis=-1, keepdims=True), 1e-9)
        cn = cand / np.maximum(
            np.linalg.norm(cand, axis=-1, keepdims=True), 1e-9)
        return np.einsum("qd,qkd->qk", qn, cn, dtype=np.float32)
    diff = cand - queries[:, None, :]
    return -np.sum(diff * diff, axis=-1)


def _stable_topk_desc(scores: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k largest scores, ties to the lower index (the
    ``jax.lax.top_k`` order the batched paths produce)."""
    return np.argsort(-scores, kind="stable")[:k]


def recall_at_k(index: IVFIndex, queries: np.ndarray, k: int,
                nprobe: Optional[int] = None,
                rerank: bool = True) -> float:
    _, approx = index.search_many(queries, k, nprobe, rerank=rerank)
    _, exact = index.search_exact(queries, k)
    hits = 0
    for a, e in zip(approx, exact):
        hits += len(set(a.tolist()) & set(e.tolist()) - {-1})
    return hits / (queries.shape[0] * k)
