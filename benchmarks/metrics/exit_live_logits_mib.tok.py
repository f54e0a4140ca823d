"""The largest block of float32 logits the exit loss holds at once: the gauge
``hvd.exit.live_logits_bytes`` of the step handle's program, in MiB. Four
whole exits of 8,190 positions over 49,152 columns would be 6,142 MiB; the
chunked loss holds 512 rows of one exit, 96 MiB. The gauge is the chunk the
loss is traced with (rows x vocabulary x 4 bytes), not a reading of the
compiled program's memory, which is ``peak_hbm_gib.tok``'s."""

from benchmarks.metrics import program_spans


def read(record):
    held = program_spans.step_gauge("hvd.exit.live_logits_bytes")
    return None if held is None else held / 2 ** 20
