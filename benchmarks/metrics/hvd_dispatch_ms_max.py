"""The longest ``hvd.spmd.dispatch`` span of the step handle in the window:
a stall inside the runtime's enqueue shows here and not in the mean
(``hvd_dispatch_ms_per_step``)."""

from benchmarks.metrics import step_clock


def read(record):
    found = step_clock.window(record)
    return max(map(step_clock.ms, found[1])) if found else None
