"""Attention calls of the step whose backward pass is the one Pallas kernel
``hvd_flash_bwd`` (dQ, dK and dV from one walk over the score blocks): the
gauge ``hvd.attn.fused_bwd_calls`` of the step handle's program, which
``ops.attention.attend`` counts while the step is traced, beside
``hvd.attn.flash_calls``. ``attention_plan`` answers the one kernel where a
(batch, head) program's float32 dQ fits its VMEM budget and the dQ / dK+dV
split elsewhere: 24, 24, 5, 32 and 6 in the five language cells. A program
that sets no such gauge (the parent of the PR that brought it), or whose
calls all take the split, has nothing to read."""

from benchmarks.metrics import program_spans


def read(record):
    return program_spans.step_gauge("hvd.attn.fused_bwd_calls")
