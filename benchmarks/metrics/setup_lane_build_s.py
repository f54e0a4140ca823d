"""Length of the span ``hvd.lane.build``: ``bench.build_lane`` from inside
(model init, train state, placing state and batch, the audit)."""

from benchmarks.metrics import program_spans


def read(record):
    snap = program_spans.snapshot()
    built = program_spans.build_span(snap) if snap else None
    return program_spans.seconds(built) if built else None
