#!/usr/bin/env python
"""On-chip A/B: flash fwd+bwd wall time, scan vs pallas backward, across
the long-context ladder. Decides `_FLASH_BWD_PALLAS_MIN_LK` (the
measured crossover in ops/attention.py) from data rather than theory.
Appends one summary line to stderr LAST so a sweep-lane record carries
it (tools/hw_sweep.py keeps the final line)."""
import os
import sys
import time

import jax
import jax.numpy as jnp


def time_fwd_bwd(fn, *args, iters=20):
    from horovod_tpu.utils.devsync import force_device_sync

    def loss(*a):
        return jnp.sum(fn(*a) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    force_device_sync(g(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = g(*args)
    jax.block_until_ready(out)
    force_device_sync(out)  # close the timed region
    return (time.perf_counter() - t0) / iters


def main():
    from horovod_tpu.ops.attention import flash_attention

    key = jax.random.PRNGKey(0)
    rows = []
    for seq, batch in ((2048, 2), (4096, 2), (8192, 2), (16384, 1)):
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (batch, seq, 8, 64), jnp.bfloat16)
                   for i in range(3))
        cell = {}
        for impl in ("scan", "pallas"):
            try:
                t = time_fwd_bwd(
                    lambda a, b, c, _i=impl: flash_attention(
                        a, b, c, causal=True, bwd_impl=_i),
                    q, k, v)
                cell[impl] = t
                print(f"seq {seq} bwd={impl}: {t * 1e3:.3f} ms",
                      file=sys.stderr, flush=True)
            except Exception as exc:  # noqa: BLE001 — record and continue
                print(f"seq {seq} bwd={impl}: failed "
                      f"{type(exc).__name__}: {exc}", file=sys.stderr,
                      flush=True)
        if len(cell) == 2:
            rows.append(f"seq {seq}: scan {cell['scan'] * 1e3:.2f} ms "
                        f"pallas {cell['pallas'] * 1e3:.2f} ms "
                        f"({cell['scan'] / cell['pallas']:.2f}x)")
    print("flash OK: bwd A/B " + "; ".join(rows), file=sys.stderr,
          flush=True)


if __name__ == "__main__":
    main()
