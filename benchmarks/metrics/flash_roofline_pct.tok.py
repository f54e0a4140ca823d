"""The attention kernels' share of their roofline: ``flops_moe.flash_work``
(seven products a visible pair, as the window and causal masks require,
recomputation not counted) at the v5e's peaks, over ``flash_ms_per_step.tok``."""

from benchmarks.metrics import kernel_families as k


def read(record):
    return k.roofline_pct(record, k.FLASH, (), "flash_work")
