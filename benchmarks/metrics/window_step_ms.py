"""The profiler-off window's whole time over its steps: what a step took where
the rate was measured. Over ``device_step_ms`` of the traced steps that
follow, it says how much of that window the device did not work, or worked
slower."""


def read(record):
    w = record["window"]
    return 1e3 * w["seconds"] / w["steps"]
