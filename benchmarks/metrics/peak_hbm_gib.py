"""The peak on the fullest chip after the window, before the reference runs:
the runtime's ``peak_bytes_in_use`` (live arrays) plus ``peak_bytes_reserved``
(what it set aside for running programs, the step's scratch)."""


def read(record):
    peak = record["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
