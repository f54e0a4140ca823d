"""The gauge ``hvd.init.process_age_s``: how old the process was when
``hvd.init`` was entered (by ``/proc``): the interpreter, the imports and
the backend's client, before the program's first line runs. With
``setup_init_s`` it is ``run.py``'s ``reach_chip_s`` from inside."""

from benchmarks.metrics import program_spans


def read(record):
    snap = program_spans.snapshot()
    if snap is None:
        return None
    return snap["gauges"].get("hvd.init.process_age_s", {}).get("")
