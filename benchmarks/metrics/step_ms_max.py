"""The longest time between two losses arriving in the profiler-off window (the
first is counted from the window's start). Near ``window_step_ms`` the steps
were even; far over it one stall took the time."""


def read(record):
    arrivals = record["window"]["arrivals_s"]
    if not arrivals:
        return None
    return 1e3 * max(b - a for a, b in zip([0.0] + arrivals, arrivals))
