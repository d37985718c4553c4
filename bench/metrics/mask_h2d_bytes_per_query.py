"""Bytes of packed row mask the filtered kNN path copied to the device
per query answered: the window's growth of the program's
``h2d_bytes:row_mask`` counter (the deployment reads it before and after
the window into ``view.row_mask_h2d_bytes``), over the queries answered
in the window (each vector of a batch counts).  Reads nothing where the
program has no such counter."""


def read(view):
    sent = getattr(view, "row_mask_h2d_bytes", None)
    answered = sum(r.req.size for r in view.window.records
                   if r.error is None)
    if not sent or not answered:
        return None
    return sent / answered
