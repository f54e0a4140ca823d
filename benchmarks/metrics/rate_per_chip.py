"""Images or tokens of every step of the window over the window's whole time,
a chip."""


def read(record):
    w = record["window"]
    return w["steps"] * w["units_per_step_per_chip"] / w["seconds"]
