"""Centroid probe time per kNN call, in milliseconds: the program's
``knn.probe`` spans (centroid scoring, top-nprobe, the copy back to the
host, the residual centroid term, the signature sort), summed over a
call's shards and averaged over the window's calls.  Reads nothing where
the program has no such span."""


def read(view):
    spans = [sp for tr in view.traces for sp in tr.find("knn.probe")]
    if not spans:
        return None
    return 1e3 * sum(sp.duration_s for sp in spans) / len(view.traces)
