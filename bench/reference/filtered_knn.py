"""Plain filtered k-nearest-neighbour reference: the rows whose tags hold
every tag of a query's filter, from the generated tag arrays, and a brute
force over those rows in float64 (L2), in blocks; the same in a lower
precision for the control."""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def allowed_rows(tag_ptr: np.ndarray, tag_ids: np.ndarray,
                 filters: Sequence[Sequence[int]]) -> List[np.ndarray]:
    """Per filter, the ascending rows whose tag list (``tag_ids`` in CSR
    rows ``tag_ptr``) holds every one of its tags."""
    n = len(tag_ptr) - 1
    row_of = np.repeat(np.arange(n), np.diff(tag_ptr))
    out = []
    for f in filters:
        hits = np.zeros(n, np.int64)
        for t in set(int(t) for t in f):
            hits[row_of[tag_ids == t]] += 1
        out.append(np.nonzero(hits == len(set(f)))[0])
    return out


def filtered_topk(queries: np.ndarray, corpus: np.ndarray,
                  rows: Sequence[np.ndarray], k: int, dtype=None,
                  block: int = 4096) -> Tuple[np.ndarray, np.ndarray]:
    """(squared distances [Q, k], rows [Q, k]) of each query's ``k``
    nearest allowed rows, nearest first, ties to the lower row; a query
    with fewer allowed rows pads with (inf, -1).  float64, or ``dtype``
    (an ``ml_dtypes`` name such as "bfloat16": inputs, the dot products,
    norms and distances each rounded to it) for the control."""
    dist = np.full((len(queries), k), np.inf)
    pos = np.full((len(queries), k), -1, np.int64)
    for i, (q, r) in enumerate(zip(queries, rows)):
        d = np.concatenate([_l2(q, corpus[r[lo:lo + block]], dtype)
                            for lo in range(0, len(r), block)] or [[]])
        top = np.lexsort((r, d))[:k]
        dist[i, :len(top)], pos[i, :len(top)] = d[top], r[top]
    return dist, pos


def _l2(q: np.ndarray, x: np.ndarray, dtype) -> np.ndarray:
    if dtype is None:
        diff = x.astype(np.float64) - q.astype(np.float64)
        return (diff * diff).sum(1)
    import ml_dtypes

    def rnd(a):         # round to ``dtype``, compute on in float32
        return np.asarray(a, np.float32).astype(
            getattr(ml_dtypes, dtype)).astype(np.float32)
    qd, xd = rnd(q), rnd(x)
    d = rnd(rnd((qd * qd).sum()) - 2 * rnd(xd @ qd)
            + rnd((xd * xd).sum(1)))
    return d.astype(np.float64)


def l2_to(queries: np.ndarray, corpus: np.ndarray,
          pos: np.ndarray) -> np.ndarray:
    """Squared distance from each query to the corpus rows ``pos`` [Q, k],
    float64."""
    q = queries.astype(np.float64)[:, None, :]
    x = corpus[pos].astype(np.float64)
    return ((x - q) ** 2).sum(-1)
