"""Collectives the gradient exchange issues a step (1 a ``psum`` bucket, 2 a
reduce-scatter + all-gather bucket, the ladder's legs for a hierarchical
one): the gauge ``hvd.exchange.calls`` of the step handle's program. Left
out on one chip, where it reads 0."""

from benchmarks.metrics import program_spans


def read(record):
    return program_spans.step_gauge("hvd.exchange.calls")
