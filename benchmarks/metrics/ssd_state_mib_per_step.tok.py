"""MiB of chunk states the scan's forward kernels write to HBM in a step:
the gauge ``hvd.ssd.state_bytes`` of the step handle's program, summed over
the Mamba layers and over the forward calls a step executes (a recomputed
block's twice) from the traced shapes. One call at 16,384 tokens and
Granite's widths writes 64 chunks x 64 heads x 128 x 64 float32: 128 MiB. A
program with no Mamba layer, or none of that gauge, has nothing to read."""

from benchmarks.metrics import program_spans


def read(record):
    written = program_spans.step_gauge("hvd.ssd.state_bytes")
    return None if written is None else written / 2 ** 20
