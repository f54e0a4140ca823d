"""The share of the window's seconds by which its late steps were late: 100 x
the summed ``late_ms`` of the ``hvd.host.stall`` records that two of the
window's dispatches of the step handle close (every cause but ``pause``)
over the window's seconds. 0.0 with the detector and no stall. It stands
beside the rate's bound: a window that reads over 1 lost more than 1% to
stalls."""

from benchmarks.metrics import step_clock


def read(record):
    found = step_clock.window(record)
    if found is None:
        return None
    snap, calls = found
    program = calls[0]["args"]["program"]
    closing = {s["args"]["call"] for s in calls[1:]}
    late_ms = sum(
        s["args"]["late_ms"] for s in snap["spans"]
        if s["name"] == step_clock.STALL and s["args"]["cause"] != "pause"
        and s["args"]["program"] == program and s["args"]["call"] in closing)
    return 100 * late_ms / 1e3 / record["window"]["seconds"]
