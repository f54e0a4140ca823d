"""What the readers of the step clock's metrics share: the step handle's
dispatch records of the profiler-off window, from the program's own snapshot
(``program_spans``), in a program that has the stall detector. The window's
calls are ``compare_steps`` to ``compare_steps`` + the window's steps, as
``hvd_dispatch_ms_per_step`` takes them.

A program without the detector (the parent of the PR that brought it) has no
counter ``hvd.host.stalls``: ``window`` then returns ``None``, every reader
built on it returns ``None`` and the metric is left out of the line. With the
detector and nothing late the counter reads 0 and the readers read numbers.
"""

from benchmarks.metrics import program_spans

STALL = "hvd.host.stall"
GC = "hvd.host.gc"


def window(record):
    """``(snap, calls)``: the snapshot and the window's dispatch records in
    the order of their calls; ``None`` without the detector, or where the
    lane was wrapped or the ring let go of a call."""
    snap = program_spans.snapshot()
    if snap is None or "hvd.host.stalls" not in snap["counters"]:
        return None
    first, steps = record["cell"]["compare_steps"], record["window"]["steps"]
    calls = program_spans.step_dispatches(snap)
    found = [calls[i] for i in range(first, first + steps) if i in calls]
    return (snap, found) if len(found) == steps else None


def ms(span):
    return 1e3 * program_spans.seconds(span)
