"""Device time a step of the Mamba layers' scan kernels (every forward call,
the recomputed ones among them, and every backward call): the family
``hvd_ssd_scan`` (``horovod_tpu/ops/ssd.py`` names both kernels so) of chip
0's ten largest; left out where it is not among them (``kernel_families.py``)
and on a program without the kernels."""

from benchmarks.flops_ssm import KERNELS
from benchmarks.metrics import kernel_families as k


def read(record):
    return k.ms_per_step(record, KERNELS)
