"""Which implementation a scan/merge call takes, and a count of each.

Every kNN wrapper (``ivf_scan``, ``pq_scan``, ``topk_merge``) asks
:func:`use_pallas` once per call.  The Pallas kernel runs on a TPU, or where
a caller forces it (the interpret-mode tests), for k up to its kernel's cap
in ``KERNEL_MAX_K``; above it the jitted XLA twin runs instead.  The PQ
scan ``pq_scan_ext`` takes the kernel up to k = 128, the widest at which it
measured faster than its twin (one gather pass over a [Q, N] float32 score
matrix per subspace) at every batch size of the SIFT cell on a TPU v5e
(PERF.md), and so does its row-masked sibling ``pq_scan_rowmask`` (the
same network); the other kernels keep 64, a bound set before any chip
measurement.  Each decision is counted in ``METRICS`` (the ``pandadb``
namespace) as ``kernel_dispatch:<kernel>:pallas`` or ``:xla``;
a Pallas call that runs in interpret mode also counts ``:interpret``, so a
run on the chip can show which code executed and that none of it was
interpreted.

The same registry counts what the kNN path copies to the device,
``h2d_bytes:<site>`` (:func:`count_h2d`), and the process's backend
compilations, ``jit_compiles``.  Neither name starts with
``kernel_dispatch:``, so readers of the dispatch counters never see them.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np
from jax import monitoring

from repro.obs.metrics import MetricsRegistry

KERNEL_MAX_K = {"ivf_scan": 64, "pq_scan_ext": 128, "pq_scan_rowmask": 128,
                "topk_merge": 64}

METRICS = MetricsRegistry("pandadb")


def _on_jax_event(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        METRICS.counter("jit_compiles").inc()


monitoring.register_event_duration_secs_listener(_on_jax_event)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas(kernel: str, k: int, force_pallas: bool = False) -> bool:
    """Pick Pallas vs the XLA twin for one ``kernel`` call of width ``k``
    and count the choice."""
    tpu = on_tpu()
    pallas = (force_pallas or tpu) and k <= KERNEL_MAX_K[kernel]
    METRICS.counter(f"kernel_dispatch:{kernel}:"
                    f"{'pallas' if pallas else 'xla'}").inc()
    if pallas and not tpu:
        METRICS.counter(f"kernel_dispatch:{kernel}:interpret").inc()
    return pallas


def impl_name(pallas: bool) -> str:
    """The implementation :func:`use_pallas` chose, as its counter names it:
    ``pallas``, ``interpret`` (Pallas off the chip) or ``xla``."""
    if not pallas:
        return "xla"
    return "pallas" if on_tpu() else "interpret"


def count_h2d(site: str, span, *arrays, **attrs) -> int:
    """Count the bytes of the host ``arrays`` handed to a device program at
    ``site`` as ``h2d_bytes:<site>``, and return them.  ``span`` (a live
    :class:`repro.obs.Span` or None) gets the same number as ``h2d_bytes``,
    with ``attrs``."""
    n = sum(int(a.nbytes) for a in arrays)
    METRICS.counter(f"h2d_bytes:{site}").inc(n)
    if span is not None:
        span.set(h2d_bytes=n, **attrs)
    return n


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
