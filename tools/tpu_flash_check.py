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
short chip call yields the grid-truncation evidence without a whole
training lane. Every ladder
record carries its grid/K-V-bytes stamp (flash_grid_info) so it is
attributable to a concrete grid, not just a wall time.

``--block-sweep [shape ...]`` is the measurement the attention policy's
constants come from (``ops.attention.attention_plan``; PERF.md, PR 29 and
PR 35): dense against the kernels over blocks and the three backwards (one
kernel, the dQ / dK+dV split, the scan) at :data:`SWEEP_SHAPES`, each
kernel's own device time beside the wall times; where the plan answers
two heads a program (heads of 64), a layer through ``attend`` from the fused
projection and back, the copies around the kernels included, one head a
program against two (PR 37); and at the plan's blocks and backward the rows
of a diagonal block's slabs (:data:`SWEEP_SLABS`, ``FLASH_SLAB``), each
row with its largest difference from the kernels without slabs (PR 39).
``--slabs-only`` keeps the slab rows alone.

Run on a TPU host:  python tools/tpu_flash_check.py
"""
import functools
import sys
import time

import jax
import jax.numpy as jnp

from horovod_tpu.ops.attention import (FLASH_BLOCK, attention_plan,
                                       dot_product_attention,
                                       flash_attention, flash_grid_info)

F32 = jnp.float32


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


def _time(fn, *args, iters=10, repeats=3):
    """Median over ``repeats`` of the mean wall time of ``iters`` calls."""
    from horovod_tpu.utils.devsync import force_device_sync

    force_device_sync(fn(*args))  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters)
    return sorted(times)[len(times) // 2]


def _kernel_ms(fn, *args, calls=4):
    """Device ms a call of each ``hvd_flash_*`` kernel, from a profile of
    ``calls`` calls of ``fn`` (compiled already): what the wall times hold
    beside the kernels (the transposes around them, the rows' ``dO . O``)
    is not in it."""
    import collections
    import tempfile

    from horovod_tpu.utils import step_profile

    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        planes = step_profile.read_xspace(
            step_profile.newest_xplane(trace_dir))
    ns = collections.Counter()
    for e in (e for plane in planes
              if step_profile.DEVICE_PLANE.match(plane.name)
              for line in plane.lines if line.name == step_profile.OPS_LINE
              for e in line.events):
        # ``%hvd_flash_bwd.3 = ...``: an instruction is named after its kernel
        name = e.name.partition(" = ")[0].lstrip("%").rstrip(".0123456789")
        if name.startswith("hvd_flash_"):
            ns[name] += e.end_ns - e.start_ns
    return {k: round(v / calls / 1e6, 4) for k, v in sorted(ns.items())}


def _grad_of(attend, argnums=(0, 1, 2)):
    """The jitted gradients of ``sum(attend(q, k, v))`` by q, k and v."""
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(attend(*a).astype(jnp.float32)),
        argnums=argnums))


def _max_err(got, want):
    """The largest absolute difference between two lists of arrays."""
    return max(float(jnp.max(jnp.abs(a.astype(F32) - b.astype(F32))))
               for a, b in zip(got, want))


def _one_head_a_program(qkv, heads, **kw):
    """What ``attend`` does with a fused projection where the plan answers
    one head a program: split it, give every head its ``[L, D]`` slab (the
    transposes inside ``flash_attention``), and lay the result back."""
    q, k, v = (t.reshape(*t.shape[:2], heads, -1)
               for t in jnp.split(qkv, 3, axis=-1))
    out = flash_attention(q, k, v, causal=True, **kw)
    return out.reshape(*out.shape[:2], -1)


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
    # runs the packed at-or-below-diagonal grid; pin it, without row slabs,
    # bit-exact against the full grid's compute-skip path (interpret-mode
    # CI pins the same equality, but only the chip runs real Mosaic).
    out_full = flash_attention(q, k, v, causal=True, truncate=False)
    out_whole = flash_attention(q, k, v, causal=True, slab=0)
    terr = _max_err([out_whole], [out_full])
    print(f"truncated-vs-full grid max err: {terr:.2e} "
          f"[{_grid_stamp(L, H, D)}]", file=sys.stderr)
    assert terr == 0.0, terr
    # The diagonal block in row slabs (the plan's, two of 256 in a block of
    # 512) against its whole masked square, outputs and gradients.
    # Other sums, so bf16 roundings apart: largest error over largest value.
    serr = max(_max_err([a], [b]) / float(jnp.max(jnp.abs(b.astype(F32))))
               for a, b in zip(
                   (out,) + _grad_of(functools.partial(
                       flash_attention, causal=True))(q, k, v),
                   (out_whole,) + _grad_of(functools.partial(
                       flash_attention, causal=True, slab=0))(q, k, v)))
    print(f"slabs-vs-whole-diagonal max relative err: {serr:.2e}",
          file=sys.stderr)
    assert serr < 2e-2, serr
    # The one-kernel backward against the split ON HARDWARE: the same
    # products summed in the same order (the diagonal blocks whole, as the
    # split computes them), at four blocks a side so that the resident dQ
    # rows are revisited.
    qkv4 = [jax.random.normal(jax.random.fold_in(key, 5 + i),
                              (1, 2048, 2, D), jnp.bfloat16) for i in range(3)]
    fused, split = (_grad_of(functools.partial(
        flash_attention, causal=True, block_q=512, block_k=512,
        bwd_impl=bwd, **pin))(*qkv4)
        for bwd, pin in (("fused", dict(slab=0)), ("pallas", {})))
    ferr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                     b.astype(jnp.float32))))
               for a, b in zip(fused, split))
    print(f"fused-vs-split backward max err: {ferr:.2e}", file=sys.stderr)
    assert ferr < 1e-2, ferr
    # Two heads a program against one ON HARDWARE, from a fused projection
    # of 4 heads of 64 at four blocks a side: zeros added to the same sums
    # (the diagonal blocks whole; the sweep holds the slabs to them).
    fused_qkv = jax.random.normal(jax.random.fold_in(key, 9),
                                  (2, 2048, 3 * 4 * 64), jnp.bfloat16)
    lanes = dict(block_q=512, block_k=512, slab=0)
    perr = 0.0
    for one, two in (
            (_one_head_a_program(fused_qkv, 4, **lanes),
             flash_attention(fused_qkv, causal=True, heads=4, **lanes)),
            (_grad_of(functools.partial(_one_head_a_program, heads=4,
                                        **lanes), 0)(fused_qkv),
             _grad_of(functools.partial(flash_attention, causal=True,
                                        heads=4, **lanes), 0)(fused_qkv))):
        perr = max(perr, float(jnp.max(jnp.abs(
            one.astype(jnp.float32) - two.astype(jnp.float32)))))
    print(f"two-heads-a-program vs one max err: {perr:.2e}", file=sys.stderr)
    assert perr < 1e-2, perr
    # Sentinel BEFORE the timing ladder: the kernel validation above is
    # the scarce evidence — a dense-path OOM in the secondary
    # benchmark below must not make it read as a failure.
    print("TPU-FLASH: OK", flush=True)

    if "--block-sweep" in sys.argv:
        # Sweep mode: keep the cheap numerics canary above, skip the
        # flash-vs-dense ladder (the separate flash_check lane owns it
        # — re-paying its 6 timed compiles here would eat the sweep
        # lane's budget). Names after the flag keep those shapes only.
        names = [a for a in sys.argv[sys.argv.index("--block-sweep") + 1:]
                 if not a.startswith("--")]
        block_sweep(key, names, slabs_only="--slabs-only" in sys.argv)
        return

    # Micro A/B: fwd+bwd wall time of one layer, GPT-2-small-ish head
    # shape, the kernels at the policy's blocks and backward against dense,
    # with the causal-grid truncation priced in-line (the packed grid
    # against the full one). Each rung degrades independently (a seq-4096
    # dense OOM is itself a useful record, not a script failure).
    for seq in (1024, 2048, 4096):
        qkv = [jax.random.normal(jax.random.fold_in(key, 10 + i),
                                 (2, seq, 8, 64), jnp.bfloat16)
               for i in range(3)]
        try:
            tf_ = _time(_grad_of(functools.partial(
                flash_attention, causal=True)), *qkv)
            td = _time(_grad_of(functools.partial(
                dot_product_attention, causal=True)), *qkv)
            tpf = _time(_grad_of(functools.partial(
                flash_attention, causal=True, truncate=False)), *qkv)
            print(f"seq {seq}: flash {tf_ * 1e3:.3f} ms  "
                  f"dense {td * 1e3:.3f} ms  ratio {td / tf_:.2f}x  | "
                  f"full grid {tpf * 1e3:.3f} ms  "
                  f"trunc_gain {tpf / tf_:.2f}x  "
                  f"[{_grid_stamp(seq, 8, 64)}]",
                  file=sys.stderr, flush=True)
        except Exception as exc:  # noqa: BLE001 — record and continue
            print(f"seq {seq}: ladder rung failed: "
                  f"{type(exc).__name__}: {exc}", file=sys.stderr,
                  flush=True)


# The shapes of the sweep the attention policy's constants come from
# (``ops/attention.py:attention_plan``; PERF.md has its table): name, q
# shape, KV heads, window, blocks and, where keys and values differ, the
# values' width and the width of the key all heads share. The first is
# GPT-2-medium's cell, then the same 8,192 tokens at longer sequences, then
# Trinity-Mini's two layer kinds, then Moonlight's latent layer (keys of 192,
# the last 64 of them one rope key a token, values of 128).
SWEEP_SHAPES = (
    ("gpt2m_1024", (8, 1024, 16, 64), 16, None, (256, 512, 1024)),
    # the same layer with no block swept: dense, and the layer through
    # ``attend`` one head a program against two (PR 37)
    ("gpt2m_1024_layer", (8, 1024, 16, 64), 16, None, ()),
    ("h64_2048_layer", (4, 2048, 16, 64), 16, None, ()),
    ("h64_4096_layer", (2, 4096, 16, 64), 16, None, ()),
    ("h64_2048", (4, 2048, 16, 64), 16, None, (256, 512, 1024, 2048)),
    ("h64_4096", (2, 4096, 16, 64), 16, None, (256, 512, 1024, 2048)),
    # 256 x 256 at heads of 128 is PR 28's reading (PERF.md): 7.64 / 21.24
    ("trinity_window", (2, 4096, 32, 128), 4, 2048, (512, 1024, 2048)),
    ("trinity_full", (2, 4096, 32, 128), 4, None, (512, 1024, 2048)),
    ("latent_8192", (2, 8192, 16, 192), 16, None, (512, 1024, 2048),
     (128, 64)),
    # Ouro's layer (16 heads of 128, no grouping), the plan's blocks alone
    ("ouro_4096", (2, 4096, 16, 128), 16, None, (1024,)),
    # Long query sides at heads of 128 (a Ulysses shard's), the plan's blocks
    # alone: where the one-kernel backward's resident dQ stops fitting or
    # stops winning (``ops.attention.FLASH_FUSED_VMEM_BUDGET``).
    ("h128_16384", (1, 16384, 4, 128), 4, None, (1024,)),
    ("h128_32768", (1, 32768, 2, 128), 2, None, (1024,)),
    ("h128_65536", (1, 65536, 1, 128), 1, None, (1024,)),
)
# f32 scores of one block the kernels are tried at: 1,024 x 1,024
SWEEP_MAX_SCORES = 1024 * 1024
# Rows of a diagonal block's slabs, at the plan's blocks: 0 is none (every
# block's whole square under the mask)
SWEEP_SLABS = (0, 128, 256, 512)


def block_sweep(key, only=None, slabs_only=False):
    """Forward and forward + backward ms of ONE attention layer, dense
    against the flash kernels over blocks and the three backwards, at
    :data:`SWEEP_SHAPES` (``only``: names to keep), and at the plan's blocks
    and backward over the rows of a diagonal block's slabs
    (:data:`SWEEP_SLABS`; ``slabs_only`` keeps those rows alone); for the
    kernels also each one's device ms a call (``kernels_ms``), for a slab
    row the largest difference of its output and gradients from the row
    without slabs (``max_err_vs_no_slab``). One JSON line a measurement on
    standard output and in ``chiprun_out/flash_sweep.jsonl``; the last line
    names the best of every shape."""
    import json
    import os

    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []

    def measure(shape_name, label, attend, qkv, no_slab=None, **stamp):
        """One row; a slab row returns its output and gradients, and
        compares them with ``no_slab``'s where given."""
        row = dict(shape=shape_name, impl=label, **stamp)
        got = None
        try:
            fwd = jax.jit(attend)
            row["fwd_ms"] = 1e3 * _time(fwd, *qkv)
            grad = _grad_of(attend, tuple(range(min(len(qkv), 3))))
            row["fwd_bwd_ms"] = 1e3 * _time(grad, *qkv)
            if label != "dense":
                row["kernels_ms"] = _kernel_ms(grad, *qkv)
            if "slab" in stamp:
                got = (fwd(*qkv),) + tuple(grad(*qkv))
                if no_slab is not None:
                    row["max_err_vs_no_slab"] = _max_err(got, no_slab)
        except Exception as exc:  # noqa: BLE001: a refusal is a record too
            row["failed"] = f"{type(exc).__name__}: {str(exc)[:160]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
        return got

    for name, (b, length, h, d), g, window, blocks, *widths in SWEEP_SHAPES:
        if only and name not in only:
            continue
        value, shared = widths[0] if widths else (d, 0)
        shapes = ((b, length, h, d), (b, length, g, d - shared),
                  (b, length, g, value)) + ((b, length, shared),) * bool(shared)
        qkv = [jax.random.normal(jax.random.fold_in(key, 20 + i), s,
                                 jnp.bfloat16) for i, s in enumerate(shapes)]

        def shared_key(fn, **kw):
            """``fn(q, k, v)``, or ``fn(q, k, v, k_shared=...)``."""
            return lambda q, k, v, *rest: fn(
                q, k, v, causal=True, window=window,
                **(dict(k_shared=rest[0]) if rest else {}), **kw)

        plan = attention_plan(length, length, h, g, (d, value), window,
                              backend="tpu", shared_key=bool(shared))
        if plan.heads_per_program == 2:     # the block's fused projection
            fused_qkv = [jnp.concatenate(
                [t.reshape(b, length, -1) for t in qkv], -1)]
        no_slab = None
        for slab in SWEEP_SLABS:
            # The plan's kernels (two heads a program from the fused
            # projection where it pairs them) with the diagonal blocks in
            # slabs of ``slab`` rows; 0 first, the reference of the others.
            if slab and plan.block_q < 2 * slab:
                continue
            if plan.heads_per_program == 2:
                attend, args = functools.partial(
                    flash_attention, causal=True, heads=h, window=window,
                    slab=slab), fused_qkv
            else:
                attend, args = shared_key(flash_attention, slab=slab), qkv
            got = measure(name, "slabs", attend, args, no_slab,
                          block_q=plan.block_q, block_k=plan.block_k,
                          bwd=plan.bwd, slab=slab,
                          heads_per_program=plan.heads_per_program)
            if not slab:
                no_slab = got
        if slabs_only:
            continue
        if length <= 4096:      # the scores of 8,192 keys do not fit
            measure(name, "dense", shared_key(dot_product_attention), qkv)
        for bq in blocks:
            for bk in blocks:
                if max(bq, bk) > length or bq * bk > SWEEP_MAX_SCORES:
                    continue
                for bwd in ("fused", "pallas", "scan"):
                    if bwd == "scan" and (bq != bk or length > 8192):
                        continue        # the scan only reads block_k
                    measure(name, "flash", shared_key(
                        flash_attention, block_q=bq, block_k=bk,
                        bwd_impl=bwd), qkv, block_q=bq, block_k=bk, bwd=bwd)
        if plan.heads_per_program == 2:
            # A layer THROUGH ``attend``, from the fused projection to what
            # the output projection reads and back to the projection's
            # gradient, the copies between them included: the same kernels,
            # one head a program (split, transposed) against two (read where
            # the projection wrote them).
            for per, fn in ((1, functools.partial(_one_head_a_program,
                                                  heads=h, window=window)),
                            (2, functools.partial(flash_attention,
                                                  causal=True, heads=h,
                                                  window=window))):
                measure(name, "attend", fn, fused_qkv, block_q=FLASH_BLOCK,
                        block_k=FLASH_BLOCK, bwd="fused",
                        heads_per_program=per)
    with open(os.path.join(out_dir, "flash_sweep.jsonl"), "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    done = [r for r in rows if "fwd_bwd_ms" in r]
    if not done:
        # No measurement = no record: exit nonzero so the sweep lane
        # retries rather than filing a "flash OK" line with no data in it.
        print("block sweep: no rung completed", file=sys.stderr, flush=True)
        sys.exit(4)
    summary = []
    for name in dict.fromkeys(r["shape"] for r in done):
        per = [r for r in done if r["shape"] == name]
        best = min((r for r in per if r["impl"] == "flash"),
                   key=lambda r: r["fwd_bwd_ms"], default=None)
        dense = next((r for r in per if r["impl"] == "dense"), None)
        if best:
            summary.append(
                f"{name}: best {best['block_q']}x{best['block_k']} "
                f"{best['bwd']} {best['fwd_bwd_ms']:.3f} ms"
                + (f" (dense {dense['fwd_bwd_ms']:.3f})" if dense else ""))
        slabs = [r for r in per if r["impl"] == "slabs"]
        if slabs:
            fastest = min(slabs, key=lambda r: r["fwd_bwd_ms"])
            summary.append(
                f"{name}: slabs " + ", ".join(
                    f"{r['slab']} {r['fwd_bwd_ms']:.3f} ms"
                    + (f" (err {r['max_err_vs_no_slab']:.1e})"
                       if "max_err_vs_no_slab" in r else "")
                    for r in slabs) + f"; best {fastest['slab']}")
    line = "block sweep: " + "; ".join(summary)
    # The summary is the last line of both streams.
    print(line, file=sys.stderr, flush=True)
    print(line, flush=True)


if __name__ == "__main__":
    main()
