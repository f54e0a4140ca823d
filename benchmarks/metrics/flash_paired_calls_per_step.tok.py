"""Attention calls of the step whose kernel programs serve two heads: the
gauge ``hvd.attn.paired_calls`` of the step handle's program, which
``ops.attention.attend`` counts while the step is traced, beside
``hvd.attn.flash_calls``. ``attention_plan`` answers two heads a program
where two heads of 64 fill a 128-lane tile (an even number of heads, a KV
head a query head, no shared key, the causal square call): the kernels then
read q, k, v and dO and write o, dQ, dK and dV in the layout the projections
use, by index map, and no transpose or slice lies between a projection and a
kernel. 24 in the two GPT-2 cells. A program that sets no such gauge (the
parent of the PR that brought it), or whose calls all run one head a program
(heads of 128, a shared key), has nothing to read."""

from benchmarks.metrics import program_spans


def read(record):
    return program_spans.step_gauge("hvd.attn.paired_calls")
