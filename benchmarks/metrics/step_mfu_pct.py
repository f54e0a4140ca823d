"""The whole step's share of the chip's peak: operations a unit requires
(``flops.py``) x units a second a chip, over the table's bf16 peak."""


def read(record):
    if record["peak"] is None:          # not a device in the table: no share
        return None
    w = record["window"]
    rate = w["steps"] * w["units_per_step_per_chip"] / w["seconds"]
    return 100.0 * record["flops_per_unit"] * rate \
        / record["peak"]["bf16_flops_per_s"]
