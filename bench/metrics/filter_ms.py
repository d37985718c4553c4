"""Filter resolution per filtered kNN call, in milliseconds: the
``knn.filter`` spans (the coordinator's posting lookups and
intersections; each shard's row positions, packed row mask and its
upload), summed over the window's traces and averaged over the calls.
Reads nothing where no span carries the name."""


def read(view):
    spans = [sp for tr in view.traces for sp in tr.find("knn.filter")]
    if not spans:
        return None
    return 1e3 * sum(sp.duration_s for sp in spans) / len(view.traces)
