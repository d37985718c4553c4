"""Vectorised YFCC-shaped tagged images: 192-d uint8-valued vectors in
clusters, each image a bag of tags over a Zipf vocabulary, and per-query
tag filters drawn to hit a target selectivity.

Vectors follow ``sift_gen``'s clusters (Gaussian around a center, spread
along a few directions of each cluster's own plus isotropic noise), around
the middle of the byte range, rounded and clipped to [0, 255] as the
track's quantized CLIP embeddings are; the centers and every cluster's
directions lie in one shared subspace of ``global_dim`` directions, as
CLIP embeddings keep most of their variance in a few dozen principal
components.  Tags: an image draws a count of
tags (1 + Poisson), each from its own cluster's preferred tags (a Zipf over
the cluster's short list, which is itself drawn from the global Zipf) with
``own_tag_share`` probability, else from the global Zipf over the whole
vocabulary; duplicates within an image fold.  So tags and embeddings
correlate, as CLIP vectors and Flickr tags do.  Tag ``t`` is the word
``w<t>``; rank 0 is the most frequent.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from bench.traffic.params import quantile_points, split_counts


@dataclasses.dataclass
class Corpus:
    vectors: np.ndarray      # [N, dim] float32, integer-valued in [0, 255]
    cluster: np.ndarray      # [N] the cluster each image was drawn from
    tag_ptr: np.ndarray      # [N + 1] CSR offsets into tag_ids
    tag_ids: np.ndarray      # [nnz] each image's tags, ascending

    def tags_of(self, i: int) -> np.ndarray:
        return self.tag_ids[self.tag_ptr[i]:self.tag_ptr[i + 1]]


def word(t: int) -> str:
    return f"w{int(t)}"


def _zipf_cdf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), s)
    return np.cumsum(w) / w.sum()


def _structure(cfg: Dict):
    """Cluster centers, spread directions and tag short lists, fixed by the
    configuration's ``structure_seed``."""
    g = cfg["generator"]
    rng = np.random.default_rng(int(cfg["structure_seed"]))
    dim, c, r = int(cfg["dim"]), int(g["clusters"]), int(g["intrinsic_dim"])
    # the principal subspace every embedding lies near: centers and each
    # cluster's spread directions are combinations of its directions
    sub = np.linalg.qr(rng.standard_normal((dim, int(g["global_dim"]))))[0].T
    centers = 127.5 + (rng.standard_normal((c, len(sub))) @ sub) * float(
        g["center_scale"])
    basis = (rng.standard_normal((c, r, len(sub))) @ sub) * float(
        g["spread"])
    vocab = int(cfg["tag_vocabulary"])
    prefs = np.searchsorted(_zipf_cdf(vocab, float(g["tag_zipf_s"])),
                            rng.random((c, int(g["cluster_tags"]))))
    return centers, basis, np.minimum(prefs, vocab - 1)


def _points(cfg: Dict, n: int, rng: np.random.Generator
            ) -> Tuple[np.ndarray, np.ndarray]:
    g = cfg["generator"]
    centers, basis, _ = _structure(cfg)
    which = rng.integers(0, len(centers), n)
    out = np.empty((n, centers.shape[1]), np.float32)
    for lo in range(0, n, 16384):
        hi = min(n, lo + 16384)
        w = which[lo:hi]
        z = rng.standard_normal((hi - lo, basis.shape[1]))
        x = (centers[w] + np.einsum("nr,nrd->nd", z, basis[w])
             + float(g["noise"]) * rng.standard_normal((hi - lo,
                                                        centers.shape[1])))
        out[lo:hi] = np.rint(np.clip(x, 0.0, 255.0))
    return out, which


def corpus(cfg: Dict, seed: int) -> Corpus:
    g = cfg["generator"]
    rng = np.random.default_rng([seed, 1])
    n = int(cfg["n_vectors"])
    vectors, which = _points(cfg, n, rng)
    _, _, prefs = _structure(cfg)
    vocab = int(cfg["tag_vocabulary"])
    counts = 1 + rng.poisson(float(g["tags_per_image_mean"]) - 1.0, n)
    img = np.repeat(np.arange(n), counts)
    own = rng.random(len(img)) < float(g["own_tag_share"])
    pick = np.searchsorted(_zipf_cdf(prefs.shape[1], float(g["tag_zipf_s"])),
                           rng.random(len(img)))
    glob = np.searchsorted(_zipf_cdf(vocab, float(g["tag_zipf_s"])),
                           rng.random(len(img)))
    tags = np.where(own, prefs[which[img], np.minimum(pick,
                                                      prefs.shape[1] - 1)],
                    np.minimum(glob, vocab - 1))
    keys = np.unique(img.astype(np.int64) * vocab + tags)
    img, tags = keys // vocab, keys % vocab
    ptr = np.concatenate([[0], np.cumsum(np.bincount(img, minlength=n))])
    return Corpus(vectors, which, ptr.astype(np.int64), tags.astype(np.int64))


def queries(cfg: Dict, n: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Fresh points of the corpus's distribution (not in the corpus), and
    the cluster each was drawn from."""
    return _points(cfg, n, np.random.default_rng([seed, 2]))


class TagSets:
    """Posting lists of a corpus's tags, and packed bitsets of the tags
    frequent enough to take part in a pair (count >= ``min_count``), for
    the exact size of an intersection."""

    def __init__(self, c: Corpus, vocab: int, min_count: int):
        n = len(c.vectors)
        img = np.repeat(np.arange(n), np.diff(c.tag_ptr))
        order = np.argsort(c.tag_ids, kind="stable")
        self.img = img[order]                       # images, grouped by tag
        self.count = np.bincount(c.tag_ids, minlength=vocab)
        self.start = np.concatenate([[0], np.cumsum(self.count)])
        self.n = n
        self.frequent = np.nonzero(self.count >= min_count)[0]
        self.slot = np.full(vocab, -1, np.int64)
        self.slot[self.frequent] = np.arange(len(self.frequent))
        width = -(-n // 64) * 64
        packed = []
        for lo in range(0, len(self.frequent), 256):
            chunk = self.frequent[lo:lo + 256]
            bits = np.zeros((len(chunk), width), bool)
            for j, t in enumerate(chunk):
                bits[j, self.posting(t)] = True
            packed.append(np.packbits(bits, axis=1, bitorder="little"))
        self.bits = (np.concatenate(packed) if packed
                     else np.zeros((0, width // 8), np.uint8)).view(np.uint64)
        self.by_count = np.argsort(self.count, kind="stable")
        self.sorted_count = self.count[self.by_count]

    def posting(self, t: int) -> np.ndarray:
        return self.img[self.start[t]:self.start[t + 1]]

    def pair_counts(self, t1: np.ndarray, t2: np.ndarray) -> np.ndarray:
        """|posting(t1) & posting(t2)| for frequent tags, elementwise."""
        both = self.bits[self.slot[t1]] & self.bits[self.slot[t2]]
        return np.bitwise_count(both).sum(axis=-1).astype(np.int64)


def _pick_any(ts: TagSets, lo: float, hi: float, target: float,
              rng: np.random.Generator) -> int:
    """A tag of the whole vocabulary whose count lies in [lo, hi) (a seeded
    pick among them), else the one nearest ``target`` in log count."""
    sc = ts.sorted_count
    a, b = np.searchsorted(sc, lo), np.searchsorted(sc, hi)
    if b > a:
        return int(ts.by_count[a + rng.integers(b - a)])
    near = [j for j in (a - 1, a) if 0 <= j < len(sc) and sc[j] > 0]
    j = min(near, key=lambda j: abs(np.log(sc[j]) - np.log(target)))
    return int(ts.by_count[j])


def _nearest(counts: np.ndarray, lo: float, hi: float, target: float,
             rng: np.random.Generator) -> int:
    """Index of a candidate whose count lies in [lo, hi) (a seeded pick
    among them), else of the nonempty one nearest ``target`` in log
    count."""
    inside = np.nonzero((counts >= lo) & (counts < hi))[0]
    if len(inside):
        return int(inside[rng.integers(len(inside))])
    gap = np.where(counts > 0, np.abs(np.log(np.maximum(counts, 1))
                                      - np.log(target)), np.inf)
    return int(np.argmin(gap))


def filters(cfg: Dict, spec: Dict, ts: TagSets, qcluster: np.ndarray,
            seed: int) -> Tuple[List[Tuple[int, ...]], np.ndarray,
                                np.ndarray]:
    """One filter per query: (tag ids ANDed, drawn shares, rows each
    filter allows).  The drawn
    shares of the corpus's rows are log-uniform over
    2**share_lo_log2 .. 2**share_hi_log2 and, like the kinds (one or two
    tags; from the query's own cluster's short list or the whole
    vocabulary), a fixed multiset in a seeded order.  Each query takes a
    tag or pair whose count falls in the drawn share's octave, else the
    nearest there is."""
    g = cfg["generator"]
    rng = np.random.default_rng([seed, 5])
    n = len(qcluster)
    lo2, hi2 = float(spec["share_lo_log2"]), float(spec["share_hi_log2"])
    share = 2.0 ** (lo2 + (hi2 - lo2) * quantile_points(n))[
        rng.permutation(n)]
    pair_w, own_w = float(spec["two_tag_share"]), float(spec["own_share"])
    kinds = np.repeat(np.arange(4), split_counts(n, [
        (1 - pair_w) * (1 - own_w), (1 - pair_w) * own_w,
        pair_w * (1 - own_w), pair_w * own_w]))[rng.permutation(n)]
    _, _, prefs = _structure(cfg)
    freq_w = ts.count[ts.frequent].astype(np.float64)
    freq_cdf = np.cumsum(freq_w) / freq_w.sum()
    tries = int(g["pair_tries"])
    out: List[Tuple[int, ...]] = []
    rows: List[int] = []
    for i in range(n):
        target = share[i] * ts.n
        octave = 2.0 ** np.floor(np.log2(share[i])) * ts.n
        pair, own = kinds[i] >= 2, kinds[i] % 2 == 1
        pool = prefs[qcluster[i]] if own else None
        if pair:
            if own:
                cand = pool[ts.slot[pool] >= 0]
            else:
                cand = ts.frequent[np.minimum(np.searchsorted(
                    freq_cdf, rng.random(2 * tries)), len(freq_cdf) - 1)]
            if len(cand) >= 2:
                a = cand[rng.integers(len(cand), size=tries)]
                b = cand[rng.integers(len(cand), size=tries)]
                ok = a != b
                a, b = a[ok], b[ok]
                c = ts.pair_counts(a, b) if len(a) else np.zeros(0)
                if len(c) and c.max() > 0:
                    j = _nearest(c, octave, 2 * octave, target, rng)
                    out.append(tuple(sorted((int(a[j]), int(b[j])))))
                    rows.append(int(c[j]))
                    continue
        # one tag (or a pair that found no common image)
        if own:
            t = int(pool[_nearest(ts.count[pool], octave, 2 * octave, target,
                                  rng)])
        else:
            t = _pick_any(ts, octave, 2 * octave, target, rng)
        out.append((t,))
        rows.append(int(ts.count[t]))
    return out, share, np.asarray(rows, np.int64)
