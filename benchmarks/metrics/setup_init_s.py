"""Length of the span ``hvd.init``: the program's own part of reaching the
chip (the device list, the mesh, the subsystems it starts)."""

from benchmarks.metrics import program_spans


def read(record):
    snap = program_spans.snapshot()
    init = next((s for s in snap["spans"] if s["name"] == "hvd.init"),
                None) if snap else None
    return program_spans.seconds(init) if init else None
