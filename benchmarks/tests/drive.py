"""Drive the rest of a run on the CPU, for the tests: no look for a chip, as
many virtual devices as the cell asks for, and optionally the timed path
broken underneath.

    python drive.py --root <dir holding BENCHMARK.json and benchmarks/>
        --repo <the program's checkout> --devices <n> [--fault <name>]
        -- --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Faults, planted by wrapping what ``bench.build_lane`` returns (``run.py``
calls it through the module, so the wrap is underneath the harness):

* ``state_unchanged``: the step returns the state it was given;
* ``half_batch``: the second half of the batch's rows is left out and the mean
  taken over the rest (every row of the first half stands twice);
* ``loss_altered``: the loss the step reports is 1% off where it is produced;
* ``no_exchange``: the gradient exchange between chips is left out (each chip
  updates from its own shard's gradient).
"""

import argparse
import json
import os
import sys

FAULTS = ("state_unchanged", "half_batch", "loss_altered", "no_exchange")


def plant(fault: str):
    import jax
    import jax.numpy as jnp

    import bench

    if fault == "no_exchange":
        import horovod_tpu.jax.optimizer as opt

        opt.fused_reduce = lambda tensors, *a, **kw: (
            (list(tensors), kw["residuals"]) if kw.get("residuals") is not None
            else list(tensors))
        return
    build = bench.build_lane

    def broken(args, log):
        lane = build(args, log)
        step = lane.run_step

        def scale_loss(out, by):
            if isinstance(out, dict):
                return dict(out, loss=out["loss"] * by)
            return out * by

        def run_step(state, batch):
            if fault == "state_unchanged":
                keep = jax.tree_util.tree_map(jnp.copy, state)
                _, out = step(state, batch)
                return keep, out
            if fault == "half_batch":
                def twice(x):
                    half = x.shape[0] // 2
                    return jax.device_put(
                        jnp.concatenate([x[:half], x[:half]]), x.sharding)
                return step(state, jax.tree_util.tree_map(twice, batch))
            if fault == "loss_altered":
                state, out = step(state, batch)
                return state, scale_loss(out, 1.01)
            raise ValueError(fault)

        lane.run_step = run_step
        return lane

    bench.build_lane = broken


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--repo", required=True)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}")
    sys.path[:0] = [args.root, args.repo]
    from benchmarks import run

    assert os.path.dirname(run.HERE) == os.path.realpath(args.root), run.HERE
    if args.fault:
        plant(args.fault)
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    print(json.dumps(run.run_cell(rest, look_for_chip=False)), flush=True)


if __name__ == "__main__":
    main()
