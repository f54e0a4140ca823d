#!/usr/bin/env python
"""Where a training step's device time goes, by the program's own names.

    python tools/profile_step.py [bench.py's arguments] [--steps 12]
        [--trace-dir DIR] [--json FILE]

Builds the lane ``bench.py`` would run from the same arguments
(``bench.build_lane``: nothing of the model is written here), warms it up,
then runs ``--steps`` steps with two of them ahead of the loss that is read
(as the benchmark's cells do), under one ``jax.profiler`` session, and prints what
``horovod_tpu.utils.step_profile`` makes of the profile: device
milliseconds a step in forward, backward, recomputed, loss, exchange, update
and other (by the ``hvd_*`` scopes in each operation's ``op_name``),
collective time (an asynchronous collective from its start to its done), the
part of it during which nothing else ran on the chip and what ran beside the
rest, the idle gaps by the ``hvd.*`` host span open at their middle, and the
``hvd.attn.*`` / ``hvd.moe.*`` / ``hvd.loop.*`` / ``hvd.exit.*`` /
``hvd.remat.*`` / ``hvd.spmd.*`` / ``hvd.ssd.*`` gauges of the step's
program (attention calls by implementation, the kernels' blocks, the expert
layers' rows, a looped model's applications and the logits its exit loss
holds, the block applications recomputed and what the kept ones were
reckoned to hold, the compiler options the handle passed, the scan's forward
calls and the chunk states they write).
The platform must be ``tpu`` (``HVD_TPU_FORCE_CPU=1`` runs the same code on
a virtual CPU mesh, whose profile holds no chip: nothing is printed for it).
"""

import collections
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import bench

    ap = bench.build_parser()
    ap.description = __doc__
    ap.add_argument("--steps", type=int, default=12,
                    help="steps under the profiler")
    ap.add_argument("--trace-dir", default="",
                    help="where the profile is kept (default: a temporary "
                         "directory, removed)")
    ap.add_argument("--json", default="", help="also write the reduction")
    args = ap.parse_args()

    import jax

    import horovod_tpu.jax as hvd
    from horovod_tpu.utils import compile_cache, step_profile
    from horovod_tpu.utils.device import require_tpu

    compile_cache.enable()
    hvd.init()
    require_tpu(cpu_requested=bool(os.environ.get("HVD_TPU_FORCE_CPU")))

    def log(*a, **kw):
        kw["file"] = sys.stderr
        print(*a, **kw)

    lane = bench.build_lane(args, log)
    state, batch = lane.state, lane.batch
    lane.state = None

    def loss_of(out):
        return out["loss"] if isinstance(out, dict) else out

    def drive(state, steps):
        pending = collections.deque()
        for _ in range(steps):
            state, out = lane.run_step(state, batch)
            pending.append(loss_of(out))
            if len(pending) > 2:
                float(pending.popleft())
        for x in pending:
            float(x)
        jax.block_until_ready(state)
        return state

    state = drive(state, 3)                 # compile or load, and warm
    keep = args.trace_dir or tempfile.mkdtemp(prefix="hvd_profile_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(keep, profiler_options=options)
    try:
        state = drive(state, args.steps)
    finally:
        jax.profiler.stop_trace()
    result = step_profile.reduce_file(keep, steps=args.steps)
    if not args.trace_dir:
        shutil.rmtree(keep, ignore_errors=True)
    if result is None:
        log("the profile holds no operation of any chip")
        return 1
    print(step_profile.table(result))
    # what the step's program says of itself: the gauges keyed by it
    from horovod_tpu.utils import timeline

    snap = timeline.snapshot()
    step = next((s["args"]["program"] for s in reversed(snap["spans"])
                 if s["name"] == timeline.DISPATCH), "")
    said = {name: by_program[step]
            for name, by_program in sorted(snap["gauges"].items())
            if step in by_program and name.startswith(
                ("hvd.attn.", "hvd.moe.", "hvd.loop.", "hvd.exit.",
                 "hvd.remat.", "hvd.spmd.", "hvd.ssd."))}
    if said:
        print(f"  gauges of {step}: {said}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
