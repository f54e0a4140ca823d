#!/usr/bin/env python
"""KV-cache decode throughput (tokens/sec/chip) for the packaged LM.

The reference predates LM serving, so this lane is beyond-parity
evidence for the inference story (docs/inference.md): greedy decode of
the GPT-2-small-class model (12L/768d, vocab 32k) with the static-shape
KV cache — prefill + the whole generation loop compile as ONE program
(models/parallel_lm.py::lm_decode). Prints one JSON line in the bench
record shape, stamped with the device; fails unless the platform is
``tpu`` or ``JAX_PLATFORMS=cpu`` asked for the CPU.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:   # `python tools/decode_bench.py` puts tools/
    sys.path.insert(0, REPO)  # on sys.path, not the repo root

import jax
import jax.numpy as jnp


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    from tools.lm_common import (add_model_args, build_params,
                                 validate_model_args)

    add_model_args(ap)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    from horovod_tpu.models import parallel_lm as plm
    from horovod_tpu.utils import compile_cache
    from horovod_tpu.utils.device import require_tpu
    from horovod_tpu.utils.devsync import force_device_sync

    validate_model_args(ap, args)
    if args.steps < 1:
        ap.error(f"--steps must be >= 1, got {args.steps} (0 would "
                 "surface later as a scan/position-table shape error)")
    if args.prompt_len < 1 or args.batch < 1 or args.iters < 1:
        ap.error("--prompt-len, --batch and --iters must be >= 1")
    compile_cache.enable()
    device = require_tpu(
        cpu_requested=os.environ.get("JAX_PLATFORMS") == "cpu")
    lmax = args.prompt_len + args.steps
    params = build_params(args, lmax)
    prompt = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(0),
                                                   1),
                                (args.batch, args.prompt_len), 0,
                                args.vocab)

    fn = jax.jit(lambda p, t: plm.lm_decode(p, t, steps=args.steps))
    t0 = time.perf_counter()
    out = fn(params, prompt)
    force_device_sync(out)  # compile + warm
    compile_s = time.perf_counter() - t0

    # The reference's window discipline: N windows, each ended by one
    # device sync, mean +- 1.96*std.
    rates = []
    for x in range(args.iters):
        t0 = time.perf_counter()
        out = fn(params, prompt)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        rates.append(args.batch * args.steps / dt)
        print(f"Iter #{x}: {rates[-1]:.1f} decode tok/s",
              file=sys.stderr, flush=True)
    mean = sum(rates) / len(rates)
    var = sum((r - mean) ** 2 for r in rates) / len(rates)
    conf = 1.96 * var ** 0.5
    if conf > 0.1 * mean:
        print(f"WARNING: high variance (CI {conf:.0f} vs mean {mean:.0f})"
              " — busy host; rerun for a representative number",
              file=sys.stderr, flush=True)
    ms_gen = args.batch * args.steps / mean * 1e3
    print(f"decode: {mean:.1f} +-{conf:.1f} tok/s (batch {args.batch}, "
          f"{args.steps} steps @ {ms_gen:.1f} ms/gen, "
          f"compile+prefill first call {compile_s:.1f}s)",
          file=sys.stderr, flush=True)
    print(json.dumps({
        "metric": "transformer_lm_decode_tokens_per_sec_per_chip",
        "value": round(mean, 1), "unit": "tokens/sec/chip",
        "vs_baseline": None, "peak": round(max(rates), 1),
        "ms_per_generation": round(ms_gen, 1),
        "device": device,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
