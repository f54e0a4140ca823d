"""Block applications in the forward pass of a looped model's step: the gauge
``hvd.loop.applications`` of the step handle's program, which the model
counts inside its loop, one a block applied while the step is traced. The
loop is unrolled, so what is traced is what a step executes: 32 in
``ouro_seq4096_1chip`` (4 applications of 8 blocks); a model that applied
its stack fewer times reads fewer (the comparison's planted fault, a loop
left out, shows the same from outside)."""

from benchmarks.metrics import program_spans


def read(record):
    return program_spans.step_gauge("hvd.loop.applications")
