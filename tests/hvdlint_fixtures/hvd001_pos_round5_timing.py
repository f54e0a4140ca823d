"""HISTORICAL POSITIVE (round 5): the pre-round-5 benchmark timed async
XLA dispatch, not the device — nothing in the timed region waited for
completion, and the ResNet lane read ~22x the chip's true rate.
Minimized from the pre-correction bench.py window loop / chip probe.

Fixture corpus only — never executed, only parsed by hvdlint.
"""

import time


def timed_window(run_step, state, batch, iters):
    t0 = time.perf_counter()
    for _ in range(iters):
        state, _ = run_step(state, batch)
    return iters / (time.perf_counter() - t0)  # EXPECT: HVD001
