"""Host clock around each ``lane.run_step`` call until it returns, summed over
the window, over its steps."""


def read(record):
    w = record["window"]
    return 1e3 * w["dispatch_s"] / w["steps"]
