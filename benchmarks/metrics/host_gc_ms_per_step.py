"""Milliseconds a step that the window spent in the collections of the
Python heap that have a record ``hvd.host.gc`` (every one of generation 1 or
2, and of generation 0 from 0.2 ms): those that lie between the window's
first dispatch and its end, summed, over its steps."""

from benchmarks.metrics import step_clock


def read(record):
    found = step_clock.window(record)
    if found is None:
        return None
    snap, calls = found
    start = calls[0]["start_ns"]
    end = start + record["window"]["seconds"] * 1e9
    spent = sum(step_clock.ms(s) for s in snap["spans"]
                if s["name"] == step_clock.GC
                and start <= s["start_ns"] and s["end_ns"] <= end)
    return spent / len(calls)
