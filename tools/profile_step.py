#!/usr/bin/env python
"""Device-time breakdown of one training step, by XLA op family.

Runs a few steps of the bench model under ``jax.profiler.trace`` and
aggregates device-side event durations by fusion family (the thunk-name
prefix before trailing digits), printing the share table that PERF.md's
profiles were built from — so a fused-BN / fused-CE / flash A/B takes
one command per variant:

    python tools/profile_step.py --model resnet50
    python tools/profile_step.py --model resnet50 --fused-bn

The SHARES are the signal. Output: one line per family,
``share%  total_us  count  family``, plus the step wall time measured
WITHOUT the profiler for scale.
"""

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_step(args):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd
    from horovod_tpu import models

    hvd.init()
    rng = jax.random.PRNGKey(0)
    if args.model == "transformer_lm":
        model = models.TransformerLM(
            vocab_size=32000, num_layers=12, num_heads=12, embed_dim=768,
            max_len=2048, dtype=jnp.bfloat16,
            scan_layers=args.scan_layers, remat=args.remat)
        sample = jnp.zeros((1, args.seq_len), jnp.int32)
        opt = optax.adam(1e-4)
        state, optimizer = models.create_train_state(rng, model, opt, sample)
        batch = jax.random.randint(
            rng, (args.batch_size or 8, args.seq_len), 0, 32000)

        if args.fused_ce:
            from horovod_tpu.ops.xent import fused_cross_entropy

            def loss_fn(params, tokens):
                hidden = model.apply({"params": params}, tokens,
                                     train=False, return_hidden=True)
                e = hidden.shape[-1]
                h = hidden[:, :-1].reshape(-1, e).astype(jnp.float32)
                wv = params["lm_head"]["kernel"].astype(jnp.float32)
                return fused_cross_entropy(h, wv,
                                           tokens[:, 1:].reshape(-1))
        else:
            def loss_fn(params, tokens):
                logits = model.apply({"params": params}, tokens,
                                     train=False)
                logp = jax.nn.log_softmax(
                    logits[:, :-1].astype(jnp.float32))
                return -jnp.mean(jnp.take_along_axis(
                    logp, tokens[:, 1:, None], -1))

        def step_fn(state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, tokens))(state["params"])
            state, loss = models.read_before_update(state, loss)
            return models.apply_gradients(optimizer, state, grads), loss
    else:
        kwargs = {"fused_bn": True} if args.fused_bn else {}
        model = models.build(args.model, num_classes=1000,
                             dtype=jnp.bfloat16, **kwargs)
        sample = jnp.zeros((1, 224, 224, 3), jnp.float32)
        state, optimizer = models.create_train_state(
            rng, model, optax.sgd(0.01, momentum=0.9), sample)
        step_fn = models.make_train_step(model, optimizer,
                                         average_loss=False)
        bs = args.batch_size or 64
        batch = {
            "image": jax.random.normal(rng, (bs, 224, 224, 3),
                                       jnp.float32),
            "label": jax.random.randint(rng, (bs,), 0, 1000),
        }

    # Shared window stager: the profile attributes host vs device time
    # under the SAME dispatch shape bench.py --steps-per-dispatch runs.
    from horovod_tpu.jax.window import stage_synthetic_window

    step_fn, batch, batch_spec = stage_synthetic_window(
        step_fn, batch, args.steps_per_dispatch)
    run = hvd.spmd_fn(step_fn, in_specs=(P(), batch_spec),
                      out_specs=(P(), P()), donate_argnums=(0,))
    return run, state, batch


FAMILY_RE = re.compile(r"[._]?\d+$")


def family(name: str) -> str:
    """fusion.123 -> fusion; convert_reduce_fusion_5 -> convert_reduce_fusion"""
    return FAMILY_RE.sub("", name.split("/")[-1])


def device_events(trace_dir):
    """Yield (name, dur_us) for device-track complete events from the
    TensorBoard trace.json.gz this jax writes."""
    paths = glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True)
    if not paths:
        raise SystemExit(f"no trace.json.gz under {trace_dir}")
    with gzip.open(sorted(paths)[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # Device tracks: process names contain "TPU"/"Device" (host python
    # threads are excluded so python dispatch doesn't pollute shares).
    device_pids = {e.get("pid") for e in events
                   if e.get("ph") == "M" and e.get("name") == "process_name"
                   and any(k in str(e.get("args", {}).get("name", ""))
                           for k in ("TPU", "Device", "device"))}
    if device_pids:
        for e in events:
            if e.get("ph") == "X" and e.get("pid") in device_pids:
                yield e.get("name", "?"), float(e.get("dur", 0.0))
        return
    # CPU-backend fallback (hermetic smoke): XLA ops execute on
    # tf_XLAEigen/* threads of the single /host:CPU process.
    xla_tids = {(e.get("pid"), e.get("tid")) for e in events
                if e.get("ph") == "M" and e.get("name") == "thread_name"
                and str(e.get("args", {}).get("name", "")
                        ).startswith("tf_XLAEigen")}
    for e in events:
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in xla_tids:
            yield e.get("name", "?"), float(e.get("dur", 0.0))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--fused-bn", action="store_true")
    ap.add_argument("--fused-ce", action="store_true")
    ap.add_argument("--scan-layers", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--steps-per-dispatch", type=int, default=1,
                    help="K training steps per dispatch (lax.scan "
                         "window) — profile the window lane's host/"
                         "device split; --steps counts DISPATCHES")
    ap.add_argument("--trace-dir", default="")
    args = ap.parse_args()

    import jax

    run, state, batch = build_step(args)

    for _ in range(3):  # compile + warm
        state, _ = run(state, batch)
    jax.block_until_ready(state)
    from horovod_tpu.utils.devsync import force_device_sync

    force_device_sync(state)

    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, _ = run(state, batch)
    jax.block_until_ready(state)
    clean = ((time.perf_counter() - t0)
             / (args.steps * args.steps_per_dispatch))
    print(f"step wall time (no profiler): {clean * 1e3:.3f} ms"
          + (f" ({args.steps} dispatches x "
             f"{args.steps_per_dispatch}-step windows)"
             if args.steps_per_dispatch > 1 else ""),
          file=sys.stderr)

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="hvd_prof_")
    with jax.profiler.trace(trace_dir):
        for _ in range(args.steps):
            state, _ = run(state, batch)
        jax.block_until_ready(state)

    agg = collections.defaultdict(lambda: [0.0, 0])
    for name, dur in device_events(trace_dir):
        agg[family(name)][0] += dur
        agg[family(name)][1] += 1
    total = sum(v[0] for v in agg.values()) or 1.0
    print(f"device-side op families over {args.steps} steps "
          f"(trace: {trace_dir}):")
    for fam, (dur, cnt) in sorted(agg.items(), key=lambda kv: -kv[1][0])[:20]:
        print(f"{100 * dur / total:5.1f}%  {dur:12.0f}us  {cnt:6d}  {fam}")


if __name__ == "__main__":
    main()
