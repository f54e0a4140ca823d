"""The device's busy time (the union of its operations' intervals, a mean
over the chips used) over the steps of the traced window."""


def read(record):
    t = record["trace"]
    if t is None or not t.get("steps"):
        return None
    return 1e3 * t["busy_s"] / t["steps"]
