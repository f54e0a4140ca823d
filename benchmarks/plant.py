#!/usr/bin/env python3
"""Plant a fault of the model in the plain reference and see the cell's limits
catch it, on the chip, in one process.

    python3 benchmarks/plant.py --workload <cell> --seeds 11,12
        --plant <name>:<key>=<value> [--plant ...]

``calibrate.py`` plants the faults every training cell can have (part of the
batch left out, the exchange left out). A configuration's own mechanism has
faults of its own, and its reference's ``hyper`` says what they are: for
``ouro_seq4096_1chip`` a loop step left out (``loop_left_out:total_ut_steps=3``)
and the entropy term left out (``no_entropy:beta=0``). For every seed the
reference is computed with each ``--plant`` applied to its ``hyper`` (the value
is read as JSON), put in the program's place and compared with the sound
reference through ``compare.decide`` under the cell's limits: ``correct`` has
to read false. The sound reference's readings are taken from
``chiprun_out/calibrate_<cell>.raw.jsonl`` where ``calibrate.py`` has written
them for the seed in the same call, and computed otherwise. One JSON line a
reading on standard output, all of them in ``chiprun_out/plant_<cell>.jsonl``.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def parse_plants(specs):
    """``{name: (key, value)}`` from ``name:key=value`` strings."""
    plants = {}
    for spec in specs:
        name, _, setting = spec.partition(":")
        key, _, value = setting.partition("=")
        plants[name] = (key, json.loads(value))
    return plants


def planted(program, device, seeds, plants, known=()):
    """One line (a dict) a seed and a plant: the gaps between the reference
    with the plant in its ``hyper`` and the sound one (``known[seed]`` where
    given), and what the cell's limits make of them."""
    from benchmarks import compare

    config, cell = program.config, program.cell
    hyper = config["reference"]["hyper"]
    for name, (key, _) in plants.items():
        if key not in hyper:
            raise SystemExit(f"--plant {name}: the reference's hyper has no "
                             f"{key!r}; it has {sorted(hyper)}")

    def reference(seed, **over):
        program.config = dict(config, reference=dict(
            config["reference"], hyper=dict(hyper, **over)))
        try:
            return program.reference(seed, device)
        finally:
            program.config = config

    known = dict(known)
    for seed in seeds:
        ref = known.get(seed) or reference(seed)
        for name, (key, value) in plants.items():
            t0 = time.time()
            found = compare.gaps(reference(seed, **{key: value}), ref)
            correct, rows = compare.decide(found, cell["limits"])
            yield {"cell": cell["name"], "kind": "fault_" + name,
                   "seed": seed, "planted": {key: value},
                   "seconds": round(time.time() - t0, 2),
                   "gaps": {k: v[0] for k, v in found.items()},
                   "correct": correct,
                   "over": [n for n, gap, limit, _ in rows
                            if limit is not None and not gap <= limit]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plant", action="append", required=True)
    args = ap.parse_args()

    import jax

    import horovod_tpu.jax as hvd
    from horovod_tpu.utils import compile_cache
    from horovod_tpu.utils.device import require_tpu

    from benchmarks import run

    _, cell, config = run.load_cell(args.workload)
    compile_cache.enable()
    require_tpu()
    used = jax.devices()[:cell["chips"]]
    hvd.init(devices=used)
    program = run.Program(config, cell)
    # the lane's own optimizer state (zeros) would stand beside the
    # reference's: hand it out and free it, with the draw it comes with
    for leaf in jax.tree_util.tree_leaves(program.start(0)):
        leaf.delete()
    known = {}
    raw = os.path.join(run.OUT, f"calibrate_{args.workload}.raw.jsonl")
    if os.path.isfile(raw):
        with open(raw) as f:
            for line in map(json.loads, f):
                if line["kind"] == "reference":
                    known[line["seed"]] = line["readings"]
    os.makedirs(run.OUT, exist_ok=True)
    with open(os.path.join(run.OUT, f"plant_{args.workload}.jsonl"),
              "a") as out:
        for line in planted(program, used[0],
                            [int(s) for s in args.seeds.split(",")],
                            parse_plants(args.plant), known):
            text = json.dumps(line)
            print(text, flush=True)
            out.write(text + "\n")
            out.flush()


if __name__ == "__main__":
    main()
