"""Seconds of tracing, lowering and backend compile (or load from the
persistent cache) under call 0 of the step handle, as that span's record
says (``compile_s``): what the first step of set-up spends before anything
runs."""

from benchmarks.metrics import program_spans


def read(record):
    snap = program_spans.snapshot()
    if snap is None:
        return None
    first = program_spans.step_dispatches(snap).get(0)
    return first["args"].get("compile_s") if first else None
