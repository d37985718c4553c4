"""Bytes the kNN path copied from the host to the device per query
answered: the ``h2d_bytes`` the program puts on its spans (the probe's
queries and centroids, each scan kernel's padded operands, the shard
merge's windows), summed over the window's traces, over the queries
answered in the window (each vector of a batch counts).  Reads nothing
where no span carries the count."""


def read(view):
    counts = [sp.attrs["h2d_bytes"] for tr in view.traces
              for sp in tr.spans() if "h2d_bytes" in sp.attrs]
    answered = sum(r.req.size for r in view.window.records
                   if r.error is None)
    if not counts or not answered:
        return None
    return sum(counts) / answered
