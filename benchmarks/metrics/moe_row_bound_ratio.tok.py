"""Rows an expert layer's passes are bound to over the rows even routing
fills: the gauges ``hvd.moe.cut_rows`` / ``hvd.moe.expected_rows`` of the step
handle's program. What never dropping a token costs in rows that are gathered,
masked and weighted though no expert reads them, while the routing stays
within the layer's first cut (2 as the program stands; 1 is a layer whose
passes follow the occupied rows). Which cut a step ran on is data that no
reader sees (PERF.md, section 7 h): the cell's draws keep every seed's
routing within the first."""

from benchmarks.metrics import program_spans


def read(record):
    rows = program_spans.step_gauge("hvd.moe.cut_rows")
    expected = program_spans.step_gauge("hvd.moe.expected_rows")
    return rows / expected if rows and expected else None
