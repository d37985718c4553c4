"""Which implementation a scan/merge call takes, and a count of each.

Every kNN wrapper (``ivf_scan``, ``pq_scan``, ``topk_merge``) asks
:func:`use_pallas` once per call.  The Pallas kernel runs on a TPU, or where
a caller forces it (the interpret-mode tests), for k up to ``KERNEL_MAX_K``:
above that the L max-extract sweeps stop paying for themselves and the
jitted XLA twin runs instead.  Each decision is counted in ``METRICS`` (the
``pandadb`` namespace) as ``kernel_dispatch:<kernel>:pallas`` or ``:xla``;
a Pallas call that runs in interpret mode also counts ``:interpret``, so a
run on the chip can show which code executed and that none of it was
interpreted.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

from repro.obs.metrics import MetricsRegistry

KERNEL_MAX_K = 64

METRICS = MetricsRegistry("pandadb")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas(kernel: str, k: int, force_pallas: bool = False) -> bool:
    """Pick Pallas vs the XLA twin for one ``kernel`` call of width ``k``
    and count the choice."""
    tpu = on_tpu()
    pallas = (force_pallas or tpu) and k <= KERNEL_MAX_K
    METRICS.counter(f"kernel_dispatch:{kernel}:"
                    f"{'pallas' if pallas else 'xla'}").inc()
    if pallas and not tpu:
        METRICS.counter(f"kernel_dispatch:{kernel}:interpret").inc()
    return pallas


def padded_rows(n: int, block: int) -> int:
    """Row count to pad a ragged ``n``-row operand up to: a ``block``
    multiple on a ladder of four sizes per octave.  Callers whose sizes
    change every call (one gathered corpus per probe signature) then reuse
    a handful of compiled shapes instead of compiling one per size, for at
    most 25% padded rows."""
    units = max(1, -(-n // block))
    step = max(1, (1 << (units.bit_length() - 1)) // 4)
    return -(-units // step) * step * block


def padded_queries(qn: int) -> int:
    """Query rows to pad a batch up to: the next power of two."""
    return 1 << max(0, qn - 1).bit_length()


def pad_rows(x, rows: int, dtype=None) -> np.ndarray:
    """``x`` as a host array, zero-padded along its leading axis up to
    ``rows``.  Padding on the host keeps it out of JAX: an eager device pad
    would compile one small program per ragged input shape."""
    x = np.asarray(x, dtype)
    extra = rows - x.shape[0]
    if extra <= 0:
        return x
    return np.pad(x, ((0, extra),) + ((0, 0),) * (x.ndim - 1))


def dispatch_counts() -> Dict[str, int]:
    """``{"<kernel>:<impl>": calls}`` since process start."""
    return METRICS.counters_view("kernel_dispatch:")
