"""What the readers of ``program_span`` and ``program_counter`` metrics share:
the program's own spans and counters, as ``horovod_tpu.utils.timeline.
snapshot()`` hands them out in the process that ran the cell (readers run in
it). A program without that module (the parent of the PR that brought it)
has nothing to read: every function here then returns ``None``, and the
metric is left out of the line.

"The step handle" is the ``hvd.spmd_fn`` handle dispatched most often in the
process: the training step, whatever the lane called it. A handle is told
from another of the same function name by the ``program`` its spans carry.
"""

import collections

DISPATCH = "hvd.spmd.dispatch"
BUILD = "hvd.lane.build"


def snapshot():
    try:
        from horovod_tpu.utils import timeline
    except ImportError:
        return None
    take = getattr(timeline, "snapshot", None)
    return take() if take else None


def step_handle(snap):
    """The ``program`` of the step handle, or ``None`` where nothing was
    dispatched through ``hvd.spmd_fn``."""
    programs = collections.Counter(
        s["args"].get("program") for s in snap["spans"]
        if s["name"] == DISPATCH)
    return programs.most_common(1)[0][0] if programs else None


def step_dispatches(snap):
    """The step handle's dispatch spans by their ``call``."""
    handle = step_handle(snap)
    return {s["args"]["call"]: s for s in snap["spans"]
            if s["name"] == DISPATCH and s["args"].get("program") == handle}


def build_span(snap):
    """The record of ``hvd.lane.build``, or ``None``."""
    return next((s for s in snap["spans"] if s["name"] == BUILD), None)


def seconds(span):
    return (span["end_ns"] - span["start_ns"]) / 1e9


def step_gauge(name):
    """The gauge ``name`` of the step handle's program; ``None`` where it is
    not set or reads 0 (one chip exchanges nothing)."""
    snap = snapshot()
    if snap is None:
        return None
    return snap["gauges"].get(name, {}).get(step_handle(snap)) or None
