"""The row-masked ADC scan's share of its roofline, in percent: the least
time the chip needs for the window's row-masked dispatches
(``rowmask_roofline.rowmask_adc_scan`` per dispatch, which the
deployment lays in ``view.rowmask_work`` where the dispatch counters show
one ``pq_scan_rowmask`` dispatch per call and no other scan) over the
device time of the row-masked programs in the trace, the Pallas kernel
or its XLA twin.  Reads nothing without that work or those programs."""
ROWMASK_PROGRAMS = {"jit_pq_adc_topk_rowmask_pallas",
                    "jit__pq_topk_xla_rowmask"}


def read(view):
    work = getattr(view, "rowmask_work", None)
    if view.device is None or not work:
        return None
    spent = sum(s for name, s in view.device.module_seconds().items()
                if name in ROWMASK_PROGRAMS)
    if spent <= 0:
        return None
    need = sum(w.min_seconds(view.device_kind) for w in work)
    return 100.0 * need / spent
