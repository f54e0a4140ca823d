"""Attention calls of the step whose kernels walk the diagonal blocks in row
slabs: the gauge ``hvd.attn.diagonal_slab_calls`` of the step handle's
program, which ``ops.attention.attend`` counts while the step is traced,
beside ``hvd.attn.flash_calls``. ``attention_plan`` answers slabs
(``slab_rows``) where the kernels walk the packed causal grid with square
blocks of two slabs or more and the one-kernel backward: a diagonal block is
then computed slab by slab against the keys up to each slab's last row, and
a block the causal edge does not cross applies no mask. 24 in the three
GPT-2 cells, 5 in Trinity-Mini's, 32 in Ouro's, 6 in Moonlight's, 1 in
Granite's. A program that sets no such gauge (the parent of the PR that
brought it), or whose calls all compute every block's whole square (offsets,
rectangular calls, the split backward), has nothing to read."""

from benchmarks.metrics import program_spans


def read(record):
    return program_spans.step_gauge("hvd.attn.diagonal_slab_calls")
