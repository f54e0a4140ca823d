"""Device time a step of the expert layers' grouped matrix products (gate, up
and down, forward, recomputed and backward): the ``ragged-dot-none`` family of
chip 0's ten largest (``kernel_families.py``)."""

from benchmarks.metrics import kernel_families as k


def read(record):
    return k.ms_per_step(record, k.GMM, k.GMM_ALSO)
