"""MiB of keys and values the latent layers of a step's forward pass write to
HBM for the attention kernels: the gauge ``hvd.attn.latent_expanded_bytes``
of the step handle's program, summed over its latent layers from the traced
shapes. With every head's key written whole (the rope key broadcast) a layer
of 16,384 tokens x 16 heads x (192 + 128) x 2 bytes is 160 MiB, 960 MiB over
six; with a head's own 128 columns, its value and one rope key a token 130
MiB, 780. A program with no latent layer, or none of that gauge, has nothing
to read."""

from benchmarks.metrics import program_spans


def read(record):
    written = program_spans.step_gauge("hvd.attn.latent_expanded_bytes")
    return None if written is None else written / 2 ** 20
