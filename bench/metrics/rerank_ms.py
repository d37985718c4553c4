"""Exact re-rank time per kNN call, in milliseconds: the program's
``knn.rerank`` spans (the candidates' float rows fetched, scored exactly
and ordered on the host), summed over a call's shards and averaged over
the window's calls.  Reads nothing where the program has no such span."""


def read(view):
    spans = [sp for tr in view.traces for sp in tr.find("knn.rerank")]
    if not spans:
        return None
    return 1e3 * sum(sp.duration_s for sp in spans) / len(view.traces)
