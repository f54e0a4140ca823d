"""Device time a step of the flash-attention kernels (forward, recomputed
forward, dQ and dK/dV of every layer): the families ``hvd_flash_fwd``,
``hvd_flash_dq`` and ``hvd_flash_dkv`` of chip 0's ten largest; left out
unless all three are among them (``kernel_families.py``)."""

from benchmarks.metrics import kernel_families as k


def read(record):
    return k.ms_per_step(record, k.FLASH)
