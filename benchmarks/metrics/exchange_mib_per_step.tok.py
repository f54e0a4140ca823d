"""Payload a chip hands the gradient exchange's collectives a step, unpadded
and after compression: the gauge ``hvd.exchange.bytes`` that
``jax/fusion.py`` sets for the step handle's program where it executes the
bucket plan. Left out on one chip, where it reads 0."""

from benchmarks.metrics import program_spans


def read(record):
    nbytes = program_spans.step_gauge("hvd.exchange.bytes")
    return nbytes / 2 ** 20 if nbytes else None
