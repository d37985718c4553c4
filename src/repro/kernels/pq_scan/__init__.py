from repro.kernels.pq_scan.ops import pq_adc_topk, row_mask_from_bitmaps
from repro.kernels.pq_scan.pq_scan import (pq_adc_topk_ext_pallas,
                                           pq_adc_topk_rowmask_pallas)
from repro.kernels.pq_scan.ref import (pack_row_mask, pq_adc_topk_ref,
                                       pq_scores_ref)

__all__ = ["pack_row_mask", "pq_adc_topk", "pq_adc_topk_ext_pallas",
           "pq_adc_topk_ref", "pq_adc_topk_rowmask_pallas", "pq_scores_ref",
           "row_mask_from_bitmaps"]
