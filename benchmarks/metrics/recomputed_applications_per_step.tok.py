"""Block applications the backward pass of the step runs again: the gauge
``hvd.remat.recomputed`` of the step handle's program, which the model counts
while the step is traced, one a block application it wraps in ``nn.remat``
(``hvd.remat.applications`` counts them all). ``--remat`` recomputes what does
not fit the device's memory (``decoder.plan_recomputation``): 5 of 5 and 32 of
32 where every application is recomputed, fewer where the plan keeps some. A
program that sets no such gauge (the parent of the PR that brought it) has
nothing to read."""

from benchmarks.metrics import program_spans


def read(record):
    return program_spans.step_gauge("hvd.remat.recomputed")
