"""Mean length of the step handle's ``hvd.spmd.dispatch`` spans in the
profiler-off window: the calls after the compared steps, ``compare_steps``
to ``compare_steps`` + the window's steps. ``dispatch_ms_per_step`` times the
same calls from outside, around ``lane.run_step``."""

from benchmarks.metrics import program_spans


def read(record):
    snap = program_spans.snapshot()
    if snap is None:
        return None
    first = record["cell"]["compare_steps"]
    calls = program_spans.step_dispatches(snap)
    window = [calls[i] for i in range(first, first + record["window"]["steps"])
              if i in calls]
    if len(window) != record["window"]["steps"]:
        return None             # the lane was wrapped, or the ring let go
    return 1e3 * sum(map(program_spans.seconds, window)) / len(window)
