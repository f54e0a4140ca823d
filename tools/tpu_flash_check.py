#!/usr/bin/env python
"""Real-TPU validation of the pallas flash attention kernel + backward,
plus a flash-vs-dense micro timing ladder.

CI exercises the kernel in pallas interpret mode on the CPU mesh
(tests/test_parallel.py::TestFlashAttention); this script is the
on-hardware counterpart: compile and run the actual Mosaic kernel
(forward incl. the persisted-logsumexp output, then the custom-VJP
backward), check numerics against the dense reference in bf16 — plus
the packed-vs-full causal grid parity — then time fwd+bwd flash
(truncated AND full grid) vs dense at seq 1024/2048/4096 — so one
short chip call yields the crossover AND grid-truncation evidence
without the full transformer_lm sweep lanes (tools/hw_sweep.py seq
ladder). Every timed record carries its grid/K-V-bytes stamp
(flash_grid_info) so block-sweep records are attributable to a
concrete grid, not just a wall time.

Run on a TPU host:  python tools/tpu_flash_check.py
"""
import sys
import time

import jax
import jax.numpy as jnp

from horovod_tpu.ops.attention import (dot_product_attention,
                                       flash_attention, flash_grid_info)


def _grid_stamp(seq, heads, head_dim, batch=2, block_q=None, block_k=None,
                truncate=None):
    """One-line causal-grid accounting for a timed record: the chosen
    blocks, truncated-vs-full step counts, and estimated K/V bytes the
    grid DMAs in — so every block-sweep/ladder wall time is
    attributable to a concrete grid, not just a config name."""
    g = flash_grid_info(seq, seq, causal=True, block_q=block_q,
                        block_k=block_k, truncate=truncate,
                        head_dim=head_dim, batch_heads=batch * heads,
                        dtype_bytes=2)
    return (f"grid {g['n_qblocks']}x{g['n_kblocks']} "
            f"bq{g['block_q']}xbk{g['block_k']} "
            f"steps {g['steps']}/{g['steps_full']} "
            f"kv {g['kv_bytes'] / 1e6:.1f}/{g['kv_bytes_full'] / 1e6:.1f}MB "
            f"({g['kv_fetch_frac']:.2f}x)")


def _time_fwd_bwd(fn, q, k, v, iters=20):
    lossgrad = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    out = lossgrad(q, k, v)  # compile + warm
    from horovod_tpu.utils.devsync import force_device_sync

    force_device_sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = lossgrad(q, k, v)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    print("devices:", jax.devices(), file=sys.stderr)
    key = jax.random.PRNGKey(0)
    B, L, H, D = 2, 512, 4, 128
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D),
                                 jnp.bfloat16) for i in range(3))

    out = flash_attention(q, k, v, causal=True)  # interpret=False on TPU
    ref = dot_product_attention(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    print(f"forward max err: {err:.2e}", file=sys.stderr)
    assert err < 2e-2, err

    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, causal=True).astype(jnp.float32)))(q)
    gr = jax.grad(lambda q: jnp.sum(
        dot_product_attention(q, k, v, causal=True).astype(jnp.float32)))(q)
    gerr = float(jnp.max(jnp.abs(g.astype(jnp.float32) -
                                 gr.astype(jnp.float32))))
    print(f"backward max err: {gerr:.2e}", file=sys.stderr)
    assert gerr < 5e-2, gerr
    # Truncated-vs-full parity ON HARDWARE: the causal square default
    # runs the packed at-or-below-diagonal grid; pin it bit-exact
    # against the full grid's compute-skip path (interpret-mode CI pins
    # the same equality, but only the chip runs real Mosaic).
    out_full = flash_attention(q, k, v, causal=True, truncate=False)
    terr = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                 out_full.astype(jnp.float32))))
    print(f"truncated-vs-full grid max err: {terr:.2e} "
          f"[{_grid_stamp(L, H, D)}]", file=sys.stderr)
    assert terr == 0.0, terr
    # Sentinel BEFORE the timing ladder: the kernel validation above is
    # the scarce evidence — a dense-path OOM in the secondary
    # benchmark below must not make it read as a failure.
    print("TPU-FLASH: OK", flush=True)

    if "--block-sweep" in sys.argv:
        # Sweep mode: keep the cheap numerics canary above, skip the
        # flash-vs-dense ladder (the separate flash_check lane owns it
        # — re-paying its 6 timed compiles here would eat the sweep
        # lane's budget).
        block_sweep(key)
        return

    # Micro A/B: fwd+bwd wall time per step, GPT-2-small-ish head shape,
    # with the causal-grid truncation priced in-line. The flash/dense
    # columns keep the historical auto-backward protocol (crossover
    # continuity); trunc_gain comes from a SEPARATE pair pinned to the
    # pallas backward — below Lk 8192 the auto backward is the scan,
    # which is diagonal-truncated by construction on both sides, so an
    # unpinned pair would price the forward grid only. Each rung
    # degrades independently (a seq-4096 dense OOM is itself a useful
    # record, not a script failure).
    for seq in (1024, 2048, 4096):
        qs, ks, vs = (jax.random.normal(jax.random.fold_in(key, 10 + i),
                                        (2, seq, 8, 64), jnp.bfloat16)
                      for i in range(3))
        try:
            tf_ = _time_fwd_bwd(
                lambda a, b, c: flash_attention(a, b, c, causal=True),
                qs, ks, vs)
            td = _time_fwd_bwd(
                lambda a, b, c: dot_product_attention(a, b, c, causal=True),
                qs, ks, vs)
            tp = _time_fwd_bwd(
                lambda a, b, c: flash_attention(a, b, c, causal=True,
                                                bwd_impl="pallas"),
                qs, ks, vs)
            tpf = _time_fwd_bwd(
                lambda a, b, c: flash_attention(a, b, c, causal=True,
                                                bwd_impl="pallas",
                                                truncate=False),
                qs, ks, vs)
            print(f"seq {seq}: flash {tf_ * 1e3:.3f} ms  "
                  f"dense {td * 1e3:.3f} ms  ratio {td / tf_:.2f}x  | "
                  f"pallas-bwd trunc {tp * 1e3:.3f} ms  "
                  f"full {tpf * 1e3:.3f} ms  "
                  f"trunc_gain {tpf / tp:.2f}x  "
                  f"[{_grid_stamp(seq, 8, 64)}]",
                  file=sys.stderr, flush=True)
        except Exception as exc:  # noqa: BLE001 — record and continue
            print(f"seq {seq}: ladder rung failed: "
                  f"{type(exc).__name__}: {exc}", file=sys.stderr,
                  flush=True)


def block_sweep(key):
    """Time flash fwd+bwd across (block_q, block_k) tilings at the
    dense/flash crossover lengths. The kernel default is 128x128; the
    round-4 A/B showed dense beating flash by ~5% at seq 2048, so if a
    bigger tile wins there, flash wins at every length and the default
    should follow the measurement (larger k-blocks amortize the online
    softmax rescale; larger q-blocks raise MXU tile occupancy at the
    cost of VMEM).  Prints one summary line LAST so a sweep-lane record
    (tools/hw_sweep.py keeps the final line) carries the best config.
    """
    results = {}
    for seq in (2048, 4096):
        qs, ks, vs = (jax.random.normal(jax.random.fold_in(key, 20 + i),
                                        (2, seq, 8, 64), jnp.bfloat16)
                      for i in range(3))
        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                if bq > seq or bk > seq:
                    continue
                try:
                    t = _time_fwd_bwd(
                        lambda a, b, c: flash_attention(
                            a, b, c, causal=True, block_q=bq, block_k=bk),
                        qs, ks, vs)
                    results[(seq, bq, bk)] = t
                    print(f"seq {seq} bq {bq} bk {bk}: {t * 1e3:.3f} ms "
                          f"[{_grid_stamp(seq, 8, 64, block_q=bq, block_k=bk)}]",
                          file=sys.stderr, flush=True)
                except Exception as exc:  # noqa: BLE001
                    print(f"seq {seq} bq {bq} bk {bk}: failed "
                          f"{type(exc).__name__}: {exc}",
                          file=sys.stderr, flush=True)
    summary = []
    for seq in (2048, 4096):
        per = [(t, bq, bk) for (s, bq, bk), t in results.items()
               if s == seq]
        if per:
            t, bq, bk = min(per)
            base = results.get((seq, 128, 128))
            gain = f" ({base / t:.2f}x vs 128x128)" if base else ""
            summary.append(f"seq {seq}: best {bq}x{bk} "
                           f"{t * 1e3:.3f} ms{gain} "
                           f"[{_grid_stamp(seq, 8, 64, block_q=bq, block_k=bk)}]")
    if not summary:
        # No measurement = no record: exit nonzero so the sweep lane
        # (and the watcher's done-check) retries rather than filing a
        # "flash OK" line with no data in it.
        print("block sweep: no rung completed", file=sys.stderr,
              flush=True)
        sys.exit(4)
    line = "block sweep: " + "; ".join(summary)
    # Last stderr line = the sweep-lane record (hw_sweep.py keeps it);
    # stdout carries it too for direct runs.
    print(line, file=sys.stderr, flush=True)
    print(line, flush=True)


if __name__ == "__main__":
    main()
