#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, on the chip, in one process.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 11,12,...
        [--control 3] [--faults 3]

For every seed: the program's first steps through the lane's own call (as
``run.py`` drives them) against the plain reference: the lower readings. For
the first ``--control`` seeds also the control, the reference computed in the
precision below the one the configuration states (``fp8`` for bfloat16),
against the reference: the upper readings. For the first ``--faults`` seeds
the faults a training cell can have, planted in the reference put in the
program's place: half of the batch left out and the mean taken over the rest,
and on a cell of several chips the exchange left out (chip 0's shard alone).
A state left unchanged reads 1 by this measure and needs no run. Every side
is also put through ``compare.decide`` with the cell's limits as they stand:
``correct`` has to read true for the program on every seed and false for the
control and for each fault. One JSON
line a reading on standard output, all of them in
``chiprun_out/calibrate_<cell>.jsonl``, and every side's norms leaf by leaf
in ``chiprun_out/calibrate_<cell>.raw.jsonl``.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
CONTROL = "fp8"         # the precision below the bfloat16 both cells state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    os.environ.setdefault("HVD_BENCH_NO_STATIC_AUDIT", "1")
    import jax

    import horovod_tpu.jax as hvd
    from horovod_tpu.utils import compile_cache
    from horovod_tpu.utils.device import require_tpu

    from benchmarks import compare, run

    _, cell, config = run.load_cell(args.workload)
    compile_cache.enable()
    require_tpu()
    used = jax.devices()[:cell["chips"]]
    hvd.init(devices=used)
    program = run.Program(config, cell)
    rows = jax.tree_util.tree_leaves(program.batch_shapes)[0].shape[0]
    os.makedirs(run.OUT, exist_ok=True)
    out = open(os.path.join(run.OUT, f"calibrate_{args.workload}.jsonl"), "a")

    raw = open(os.path.join(run.OUT, f"calibrate_{args.workload}.raw.jsonl"),
               "a")

    def emit(kind, seed, found, seconds, extra=None, readings=None):
        raw.write(json.dumps({"kind": kind, "seed": seed,
                              "readings": readings}) + "\n")
        raw.flush()
        line = {"cell": args.workload, "kind": kind, "seed": seed,
                "seconds": round(seconds, 2),
                "gaps": {k: v[0] for k, v in found.items()},
                "where": {k: v[1] for k, v in found.items()}}
        if found:
            correct, rows = compare.decide(found, cell["limits"])
            line["correct"] = correct
            line["over"] = [name for name, gap, limit, _ in rows
                            if limit is not None and not gap <= limit]
        line.update(extra or {})
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()

    for n, seed in enumerate(seeds):
        t0 = time.time()
        state, batch = program.start(seed)
        state, prog = program.first_steps(state, batch, seed)
        del state, batch
        t1 = time.time()
        ref = program.reference(seed, used[0])
        t2 = time.time()
        emit("program", seed, compare.gaps(prog, ref), t1 - t0,
             {"reference_s": round(t2 - t1, 2), "losses": prog["losses"],
              "ref_losses": ref["losses"]}, readings=prog)
        emit("reference", seed, {}, t2 - t1, readings=ref)
        if n < args.control:
            t0 = time.time()
            low = program.reference(seed, used[0], precision=CONTROL)
            emit("control_" + CONTROL, seed,
                 compare.gaps(low, ref), time.time() - t0, readings=low)
        if n < args.faults:
            plants = {"half_batch": rows // 2}
            if cell["chips"] > 1:
                plants["no_exchange"] = rows // cell["chips"]
            for name, use_rows in plants.items():
                t0 = time.time()
                bad = program.reference(seed, used[0], use_rows=use_rows)
                emit("fault_" + name, seed, compare.gaps(bad, ref),
                     time.time() - t0, readings=bad)
    out.close()


if __name__ == "__main__":
    main()
