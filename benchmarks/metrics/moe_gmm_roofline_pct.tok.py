"""The grouped products' share of their roofline: ``flops_moe.gmm_work`` (the
expected rows of even routing, nothing for unoccupied rows, recomputation not
counted) at the v5e's peaks, over ``moe_gmm_ms_per_step.tok``."""

from benchmarks.metrics import kernel_families as k


def read(record):
    return k.roofline_pct(record, k.GMM, k.GMM_ALSO, "gmm_work")
