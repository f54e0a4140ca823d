"""The longest ``period_ms`` between two of the window's dispatches of the
step handle: ``step_ms_max`` from inside, by the host's clock at dispatch
where that one goes by a loss's arrival. With two steps in flight the host's
period is the device's step, so without a stall it reads ``window_step_ms``."""

from benchmarks.metrics import step_clock


def read(record):
    found = step_clock.window(record)
    if found is None:
        return None
    periods = [s["args"]["period_ms"] for s in found[1][1:]
               if "period_ms" in s["args"]]
    return max(periods) if periods else None
