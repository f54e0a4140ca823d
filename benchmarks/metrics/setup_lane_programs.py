"""Programs compiled, or loaded from the persistent cache, under the span
``hvd.lane.build``, as its record says (``programs``): the lane's eager
start-up, one small program an operation."""

from benchmarks.metrics import program_spans


def read(record):
    snap = program_spans.snapshot()
    built = program_spans.build_span(snap) if snap else None
    return built["args"].get("programs") if built else None
