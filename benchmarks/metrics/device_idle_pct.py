"""1 - the union of device-operation intervals over the traced window, as a
mean over the chips used."""


def read(record):
    t = record["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
