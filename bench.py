#!/usr/bin/env python
"""Canonical scaling benchmark: ResNet-50 synthetic data, Horovod protocol.

Mirrors the reference's benchmark protocol exactly
(reference examples/pytorch_synthetic_benchmark.py:79-110): warmup
iterations, then ``num_iters`` timed groups of ``num_batches_per_iter``
training steps; report images/sec ± CI. TPU-native execution: the whole
step (fwd + bwd + fused gradient allreduce + update) is one XLA program
run over a 1-D "hvd" mesh of every visible chip.

Prints ONE JSON line:
    {"metric": "resnet50_img_per_sec_per_chip", "value": N,
     "unit": "img/sec/chip", "vs_baseline": N, "peak": N,
     "probe_tflops": N}

``peak`` is the best timed window's rate, ``value`` (the mean) is the
protocol's headline number, ``probe_tflops`` is a bf16 matmul rate taken
right after the timed windows (see ``probe_chip``), and ``device`` is
what JAX reports (platform, device_kind, count).

``vs_baseline`` compares against the reference's published per-GPU
absolute throughput: 1656.82 img/s over 16 Pascal GPUs = 103.55 img/s/GPU
(reference docs/benchmarks.md:22-38) — the only absolute number the
reference publishes.

``--model transformer_lm`` switches to the long-context lane the
reference never had: causal-LM training, tokens/sec/chip (vs_baseline
null — the reference published no LM number).

The measurement runs in the process that was started: one process holds
the chip, any failure is a traceback and a non-zero exit code, and no
record is printed for a run that did not measure. The platform must be
``tpu``; ``HVD_TPU_FORCE_CPU=1`` is the test switch that runs the same
code on an 8-device virtual CPU mesh instead (its numbers are not device
metrics).
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Any, Callable

if os.environ.get("HVD_TPU_FORCE_CPU"):
    # Test switch: an 8-device virtual CPU mesh, set before jax loads.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()

# The reference publishes exactly one absolute throughput: ResNet-101 at
# 1656.82 img/s over 16 Pascal GPUs (reference docs/benchmarks.md:22-38).
# BASELINE.md calibrates the ResNet-50 north star against the same number
# (ResNet-class, bs=64/device). Other models have no published reference
# throughput, so their JSON carries vs_baseline=null rather than an
# apples-to-oranges ratio.
LM_MODELS = ("transformer_lm", "moe_lm")
_REF_PER_DEVICE = 1656.82 / 16.0
REFERENCE_BASELINES = {"resnet50": _REF_PER_DEVICE, "resnet101": _REF_PER_DEVICE}


def probe_chip(log):
    """~20 ms bf16 matmul probe: sustained TFLOP/s stamped into the JSON
    record as ``probe_tflops`` — what a large matmul achieves on this
    chip right after the timed windows, beside the headline number.
    Chained matmuls (each feeding the next) so the device, not the
    dispatch path, is what's timed."""
    import jax
    import jax.numpy as jnp

    # Accelerator sizing. The forced-CPU test mesh gets a token probe:
    # 3.4 TFLOP of matmuls is ~30 s of host CPU, and the stamp only
    # means something on the chip.
    if jax.devices()[0].platform == "cpu":
        n, n1, n2 = 512, 2, 6
    else:
        n, n1, n2 = 4096, 25, 100
    x = (jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
         / jnp.sqrt(n)).astype(jnp.bfloat16)
    f = jax.jit(lambda a: a @ a)
    _force_sync(f(x))  # compile + warm

    def chain(iters):
        t0 = time.perf_counter()
        y = x
        for _ in range(iters):
            y = f(y)
        jax.block_until_ready(y)
        return time.perf_counter() - t0

    # MARGINAL rate over two chain lengths: each synced chain carries a
    # fixed dispatch + sync overhead that a single short chain folds
    # into the average; the difference quotient cancels it.
    t1, t2 = chain(n1), chain(n2)
    if t2 <= t1:
        # Timer noise on a loaded host can invert short CPU chains; a
        # null stamp reads as "probe unreliable", never as a fast chip.
        log(f"Chip probe UNRELIABLE: chain({n2})={t2:.4f}s <= "
            f"chain({n1})={t1:.4f}s", file=sys.stderr)
        return None
    tflops = 2 * n**3 * (n2 - n1) / (t2 - t1) / 1e12
    log(f"Chip probe: {tflops:.1f} TFLOP/s sustained "
        f"(bf16 {n}^3 matmul, marginal over {n1}->{n2} chained)",
        file=sys.stderr)
    return round(tflops, 1)


def _force_sync(tree) -> None:
    """Wait for ``tree`` by pulling one scalar off-device
    (horovod_tpu/utils/devsync.py)."""
    from horovod_tpu.utils.devsync import force_device_sync

    force_device_sync(tree)


def run_timed(run_step, state, batch, args, units_per_iter, unit, log):
    """The reference's measurement discipline: warmup (compile included),
    then ``num_iters`` timed windows of ``num_batches_per_iter`` steps,
    ONE device sync per window."""
    import jax
    import numpy as np

    if getattr(args, "compile_only", False):
        # Warm-cache lane: pay the first compile (writing the persistent
        # cache entry) and exit — so a big model's MEASURED lane reruns
        # against a warm cache (tools/hw_sweep.py runs this lane first).
        t0 = time.perf_counter()
        state, _ = run_step(state, batch)
        _force_sync(state)
        secs = time.perf_counter() - t0
        log(f"compile-only: first step (compile included) {secs:.1f}s",
            file=sys.stderr)
        return round(secs, 2), 0.0, round(secs, 2)

    for _ in range(args.num_warmup_batches):
        state, _ = run_step(state, batch)
    _force_sync(state)

    rates = []
    for x in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            state, _ = run_step(state, batch)
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
        rate = units_per_iter / elapsed
        log(f"Iter #{x}: {rate:.1f} {unit} per chip", file=sys.stderr)
        rates.append(rate)

    mean = float(np.mean(rates))
    conf = float(1.96 * np.std(rates))
    log(f"{unit} per chip: {mean:.1f} +-{conf:.1f}", file=sys.stderr)
    if conf > 0.1 * mean:
        log(f"WARNING: high variance (CI {conf:.0f} vs mean {mean:.0f}) — "
            "the host was busy during the windows; rerun for a "
            "representative number", file=sys.stderr)
    return mean, conf, float(np.max(rates))


def measure_snapshot_ms(state, log, samples: int = 3):
    """Measured cost of ONE elastic host-RAM snapshot of ``state``
    (synchronous d2h through horovod_tpu.elastic.Snapshotter), in ms.

    Min over ``samples`` takes: the steady-state cost is what the
    cadence amortizes — a one-off allocator warmup in the mean would
    overstate the overhead. Runs BEFORE the timed windows (the state is
    donated inside them); gradients share the state's shapes so the d2h
    cost is the same one training would pay."""
    import jax

    from horovod_tpu.elastic.snapshot import Snapshotter

    jax.block_until_ready(state)
    snap = Snapshotter(every=1)
    times = []
    for i in range(samples):
        t0 = time.perf_counter()
        snap.take(i + 1, state, sync=True)
        times.append((time.perf_counter() - t0) * 1e3)
    ms = min(times)
    log(f"Snapshot probe: {ms:.2f} ms per sync host-RAM snapshot "
        f"(min of {samples})", file=sys.stderr)
    return ms


def snapshot_field(args, snap_ms, mean, units_per_step):
    """The ``"snapshot"`` JSON stamp: cadence, ms/snapshot and measured
    overhead %% of step time — the elastic acceptance evidence (budget:
    <= 2%% at the default cadence; docs/elastic.md cadence math).
    ``mean`` is the measured rate in units/sec; ``units_per_step``
    converts it to a per-training-step time."""
    if snap_ms is None:
        return {"snapshot": None}
    field = {"every": args.snapshot_every,
             "ms_per_snapshot": round(snap_ms, 3)}
    if mean and mean > 0:
        step_secs = units_per_step / mean
        overhead = (100.0 * (snap_ms / 1e3)
                    / (args.snapshot_every * step_secs))
        # 3 significant digits at ANY magnitude: fixed-decimal rounding
        # would floor a tiny-but-real overhead (fast steps on a quiet
        # host) to exactly 0.0, misreporting the measured cost the
        # stamp exists to evidence.
        field["overhead_pct"] = float(f"{overhead:.3g}")
    else:
        field["overhead_pct"] = None
    return {"snapshot": field}


@dataclasses.dataclass
class Lane:
    """One built training lane: the compiled-on-first-call step handle
    (train state donated), the state and the one reusable synthetic
    batch — both already placed over the mesh — and what a step is worth.
    ``build_image_lane`` / ``build_lm_lane`` make it; ``measure_lane``
    times it; ``chip_smoke.py`` steps it a few times by hand."""

    model: Any              # the flax module the step applies
    run_step: Callable      # (state, batch) -> (state, loss | metrics)
    state: Any
    batch: Any
    units_per_step: int     # images or tokens per chip per training step
    unit: str               # "img/sec" | "tokens/sec"
    stamp: dict             # lane evidence fields for the JSON record


def place(tree, specs):
    """Commit ``tree`` to the hvd mesh under ``specs`` (a pytree prefix of
    PartitionSpecs) once, before the loop. Left on the default device, a
    global batch is re-scattered from device 0 inside every timed step
    and the first donation of the state is refused. Multi-process jobs
    pass host-local shards that ``spmd_fn`` assembles itself."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.common.state import global_state

    if jax.process_count() > 1:
        return tree
    mesh = global_state().mesh
    return jax.tree_util.tree_map(
        lambda spec, sub: jax.device_put(sub, NamedSharding(mesh, spec)),
        specs, tree, is_leaf=lambda x: isinstance(x, P))


def apply_window(step_fn, batch, steps_per_dispatch):
    """Window-lane wiring (--steps-per-dispatch K): one-call delegate to
    the shared synthetic-window stager so the bench and the profiler
    (tools/profile_step.py) always dispatch the same window shape."""
    from horovod_tpu.jax.window import stage_synthetic_window

    return stage_synthetic_window(step_fn, batch, steps_per_dispatch)


def build_image_lane(args, log) -> Lane:
    """ResNet/VGG/Inception/ViT lane: img/sec/chip."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd
    from horovod_tpu import models
    from horovod_tpu.utils.timeline import span

    n = hvd.size()
    batch_size = args.batch_size if args.batch_size is not None else 64
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    for flag in ("fused_ce", "scan_layers", "remat", "flash_attention",
                 "flash_full_grid"):
        if getattr(args, flag):
            raise ValueError(
                f"--{flag.replace('_', '-')} applies to transformer_lm "
                f"only (got --model {args.model})")
    if args.attention is not None:
        raise ValueError(
            f"--attention applies to transformer_lm only "
            f"(got --model {args.model})")
    if args.flash_bwd is not None:
        raise ValueError(
            f"--flash-bwd applies to transformer_lm only "
            f"(got --model {args.model})")
    build_kwargs = {}
    if args.fused_bn:
        name = args.model.lower()
        if not (name.startswith("resnet") or name.startswith("inception")):
            raise ValueError(
                "--fused-bn applies to the ResNet and Inception families")
        build_kwargs["fused_bn"] = True
    model = models.build(args.model, num_classes=1000, dtype=dtype,
                         **build_kwargs)
    k = args.steps_per_dispatch
    rng = jax.random.PRNGKey(42)
    sample = jnp.zeros((1, args.image_size, args.image_size, 3), jnp.float32)
    sgd = optax.sgd(
        0.01, momentum=0.9,
        accumulator_dtype=jnp.bfloat16 if args.bf16_momentum else None)
    state, optimizer = models.create_train_state(
        rng, model, sgd, sample, zero=args.zero, overlap=args.overlap,
        compression=resolve_compression(args),
        hierarchical=args.hierarchical)
    step_fn = models.make_train_step(model, optimizer, average_loss=False)
    # state_partition_specs owns the sharded-vs-replicated knowledge
    # (ZeRO flats, EF residuals -> P("hvd"); everything else P()).
    state_spec = models.state_partition_specs(state)

    global_batch = batch_size * n
    with span("hvd.lane.place"):
        batch = {
            "image": jax.random.normal(
                rng, (global_batch, args.image_size, args.image_size, 3),
                jnp.float32),
            "label": jax.random.randint(rng, (global_batch,), 0, 1000),
        }

    # One prebuilt compiled handle — no per-step cache lookup/hashing — with
    # the train state donated so XLA updates weights/momenta in place
    # instead of reallocating ~100 MB every step. With
    # --steps-per-dispatch K > 1 the handle is a lax.scan window of K
    # steps over a device-staged K-batch stack: one dispatch and one
    # sync per window (horovod_tpu/jax/window.py).
    step_fn, batch, batch_spec = apply_window(step_fn, batch, k)
    run_step = hvd.spmd_fn(
        step_fn,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, P()),
        donate_argnums=(0,),
    )
    with span("hvd.lane.place"):
        state, batch = place(state, state_spec), place(batch, batch_spec)
    log(f"Model: {args.model}, batch size {batch_size}/chip, {n} chips "
        f"({jax.devices()[0].platform})"
        + (f", {k}-step dispatch windows" if k > 1 else ""),
        file=sys.stderr)
    stamp = audit_stamps(args, run_step, state, batch, log)
    return Lane(model, run_step, state, batch, batch_size, "img/sec", stamp)


def build_lm_lane(args, log) -> Lane:
    """Long-context causal-LM lane: tokens/sec/chip (beyond the
    reference, which scaled batch only — SURVEY §2.9/§5)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd
    from horovod_tpu import models
    from horovod_tpu.ops.attention import FLASH_BWD, attend, flash_grid_info
    from horovod_tpu.utils.timeline import FORWARD, LOSS, UPDATE, span

    if args.fused_bn:
        raise ValueError(
            "--fused-bn applies to the ResNet and Inception families "
            "(got --model transformer_lm)")
    n = hvd.size()
    # sequences per chip
    batch_size = args.batch_size if args.batch_size is not None else 8
    L = args.seq_len
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    attn_fn = None
    attention = resolve_attention(args)
    flash_grid = None
    if args.model == "moe_lm":
        # models/decoder.py calls the kernels itself: they take the layer's
        # window and its KV heads; the grid flags are the other LM's
        if args.flash_full_grid or args.flash_bwd is not None:
            raise ValueError("--flash-full-grid and --flash-bwd apply to "
                             "transformer_lm only (got --model moe_lm)")
    elif attention == "flash":
        # Pallas flash attention (ops/attention.py): the O(L)-memory
        # kernel lane, A/B-able against the dense reference at the same
        # protocol (--attention dense | flash).
        # --flash-full-grid pins the causal grid to full size (compute-
        # skip only) for the truncated-vs-full A/B lanes; the default
        # (None) runs the packed at-or-below-diagonal grid. --flash-bwd
        # pins the backward implementation; unset, the kernels run the
        # policy's own (ops.attention.FLASH_BWD).
        truncate = False if args.flash_full_grid else None
        attn_fn = functools.partial(attend, impl="flash", truncate=truncate,
                                    bwd_impl=args.flash_bwd)
        # Grid + K/V-DMA accounting stamped into the JSON record so the
        # wall time is attributable to a concrete grid (blocks, step
        # count, bytes) and a named backward, not just a lane name.
        # PER-CHIP numbers (batch_size is per chip), mirroring each
        # device's actual pallas grid — like the tokens/sec/chip metric
        # the record headlines.
        flash_grid = flash_grid_info(
            L, L, causal=True, truncate=truncate,
            head_dim=args.lm_dim // args.lm_heads,
            batch_heads=batch_size * args.lm_heads,
            dtype_bytes=4 if args.fp32 else 2)
        flash_grid["bwd"] = (FLASH_BWD if args.flash_bwd in (None, "auto")
                             else args.flash_bwd)
    elif args.flash_full_grid or args.flash_bwd is not None:
        raise ValueError("--flash-full-grid and --flash-bwd require the "
                         "flash attention path (--attention flash, or "
                         "where auto picks it)")
    else:
        attn_fn = functools.partial(attend, impl="dense")
    if args.model == "moe_lm":
        model = build_sparse_lm(args, attention, dtype)
    else:
        model = models.TransformerLM(
            vocab_size=args.vocab, num_layers=args.lm_layers,
            num_heads=args.lm_heads, embed_dim=args.lm_dim,
            max_len=max(L, 2048), dtype=dtype, attn_fn=attn_fn,
            scan_layers=args.scan_layers, remat=args.remat)
    rng = jax.random.PRNGKey(42)
    sample = jnp.zeros((1, L), jnp.int32)
    # --bf16-momentum maps to adam's first-moment dtype on this lane (the
    # second moment stays fp32 for stability).
    opt = optax.adam(
        1e-4, mu_dtype=jnp.bfloat16 if args.bf16_momentum else None)
    state, optimizer = models.create_train_state(
        rng, model, opt, sample, zero=args.zero, overlap=args.overlap,
        compression=resolve_compression(args),
        hierarchical=args.hierarchical)
    state_spec = models.state_partition_specs(state)

    def step_fn(state, batch):
        tokens = batch["tokens"]
        # A sparse layer's state (its selection bias): read by the forward
        # pass, which writes the step's expert counts beside it.
        buffers = state.get("buffers")

        def apply(params, **kw):
            if buffers is None:
                return model.apply({"params": params}, tokens, train=False,
                                   **kw), None
            out, wrote = model.apply(
                {"params": params, "buffers": buffers}, tokens, train=False,
                mutable=["buffers"], **kw)
            return out, wrote["buffers"]

        if args.fused_ce:
            # Chunked fused loss (ops/xent.py): the [B, L, vocab] fp32
            # logits tensor — the step's largest single HBM sink —
            # never materializes; the vocab projection's gradient comes
            # out of the same scan.
            from horovod_tpu.ops.xent import fused_cross_entropy

            def loss_fn(params):
                with jax.named_scope(FORWARD):
                    hidden, wrote = apply(params, return_hidden=True)
                with jax.named_scope(LOSS):
                    e = hidden.shape[-1]
                    h = hidden[:, :-1].reshape(-1, e).astype(jnp.float32)
                    wv = params["lm_head"]["kernel"].astype(jnp.float32)
                    return fused_cross_entropy(
                        h, wv, tokens[:, 1:].reshape(-1)), wrote
        else:
            def loss_fn(params):
                with jax.named_scope(FORWARD):
                    logits, wrote = apply(params)
                with jax.named_scope(LOSS):
                    logp = jax.nn.log_softmax(
                        logits[:, :-1].astype(jnp.float32))
                    tgt = tokens[:, 1:]
                    nll = -jnp.take_along_axis(logp, tgt[..., None], -1)
                    return jnp.mean(nll), wrote

        (loss, wrote), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        if wrote is not None:
            from horovod_tpu.common.state import current_spmd_axis
            from horovod_tpu.models import decoder

            with jax.named_scope(UPDATE):
                wrote = decoder.update_buffers(
                    wrote, args.moe_bias_coeff,
                    current_spmd_axis() if n > 1 else None)
        state, loss = models.read_before_update(state, loss)
        return models.apply_gradients(optimizer, state, grads,
                                      buffers=wrote), loss

    with span("hvd.lane.place"):
        batch = {"tokens": jax.random.randint(
            rng, (batch_size * n, L), 0, args.vocab)}
    k = args.steps_per_dispatch
    step_fn, batch, batch_spec = apply_window(step_fn, batch, k)
    run_step = hvd.spmd_fn(
        step_fn,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, P()),
        donate_argnums=(0,),
    )
    with span("hvd.lane.place"):
        state, batch = place(state, state_spec), place(batch, batch_spec)
    grid_note = ""
    if flash_grid is not None:
        grid_note = (f", grid {flash_grid['steps']}/"
                     f"{flash_grid['steps_full']} steps "
                     f"({'truncated' if flash_grid['truncated'] else 'full'}"
                     f", {flash_grid['block_q']}x{flash_grid['block_k']})")
    log(f"Model: {args.model} ({args.lm_layers}L/{args.lm_dim}d), "
        f"seq {L}, batch {batch_size} seqs/chip, {n} chips "
        f"({jax.devices()[0].platform}), {attention} attention{grid_note}"
        + (f", {k}-step dispatch windows" if k > 1 else ""),
        file=sys.stderr)
    stamp = audit_stamps(args, run_step, state, batch, log)
    return Lane(model, run_step, state, batch, batch_size * L,
                "tokens/sec", {"attention": attention,
                               "flash_grid": flash_grid, **stamp})


def build_sparse_lm(args, attention: str, dtype):
    """``--model moe_lm``: the sparse decoder LM (models/decoder.py) from
    the command line. The arguments say which experts this chip holds and
    how large its slice of the vocabulary is; the router keeps every
    expert's output and its top ``k``."""
    from horovod_tpu.models import decoder

    kinds = {"sliding": decoder.SLIDING, "full": decoder.FULL}
    names = (args.lm_layer_types.split(",") if args.lm_layer_types
             else ["full"] * args.lm_layers)
    if len(names) != args.lm_layers or set(names) - set(kinds):
        raise ValueError(
            f"--lm-layer-types needs {args.lm_layers} of "
            f"{sorted(kinds)}, comma-separated; got {args.lm_layer_types!r}")
    held = args.moe_experts_held or args.moe_experts
    if not 0 <= args.moe_first_expert <= args.moe_experts - held:
        raise ValueError(
            f"experts {args.moe_first_expert} to "
            f"{args.moe_first_expert + held - 1} are not among the "
            f"{args.moe_experts} the router scores")
    if args.scan_layers:
        raise ValueError("--scan-layers applies to transformer_lm only "
                         "(got --model moe_lm)")
    return decoder.SparseDecoderLM(
        vocab_size=args.vocab, embed_dim=args.lm_dim,
        layer_types=tuple(kinds[k] for k in names), heads=args.lm_heads,
        kv_heads=args.lm_kv_heads or args.lm_heads,
        head_dim=args.lm_head_dim or args.lm_dim // args.lm_heads,
        window=args.lm_window, dense_layers=args.lm_dense_layers,
        dense_width=args.lm_ffn or 4 * args.lm_dim,
        experts=args.moe_experts, experts_held=held,
        first_expert=args.moe_first_expert, top_k=args.moe_top_k,
        expert_width=args.moe_width, shared_experts=args.moe_shared,
        route_scale=args.moe_route_scale, attention=attention, dtype=dtype,
        remat=args.remat)


def audit_stamps(args, run_step, state, batch, log) -> dict:
    """The lane's evidence fields (span ``hvd.lane.audit``)."""
    from horovod_tpu.utils.timeline import span

    with span("hvd.lane.audit"):
        stamp = overlap_stamp(args, state, log)
        stamp.update(wire_stamp(args, state, log))
        stamp.update(collectives_stamp(run_step, state, batch, log))
    return stamp


def build_lane(args, log) -> Lane:
    """Span ``hvd.lane.build``; its children are ``hvd.lane.model_init`` and
    ``hvd.lane.train_state`` (``models.create_train_state``),
    ``hvd.lane.place`` and ``hvd.lane.audit``."""
    from horovod_tpu.utils.timeline import span

    with span("hvd.lane.build", model=args.model):
        if args.model in LM_MODELS:
            return build_lm_lane(args, log)
        return build_image_lane(args, log)


def measure_lane(lane: Lane, args, log):
    """Time a built lane under the reference protocol; returns
    ``(mean, peak, unit, metric, stamp)`` for the JSON record."""
    import horovod_tpu.jax as hvd

    n = hvd.size()
    snap_ms = (measure_snapshot_ms(lane.state, log)
               if args.snapshot_every > 0 and not args.compile_only
               else None)
    units_per_iter = (lane.units_per_step * args.steps_per_dispatch
                      * args.num_batches_per_iter)
    mean, conf, peak = run_timed(lane.run_step, lane.state, lane.batch,
                                 args, units_per_iter, lane.unit, log)
    if not args.compile_only:
        log(f"Total {lane.unit} on {n} chip(s): {mean * n:.1f} "
            f"+-{conf * n:.1f}", file=sys.stderr)
    metric, unit = metric_contract(args)
    stamp = {**lane.stamp,
             **snapshot_field(args, snap_ms, mean, lane.units_per_step)}
    return mean, peak, unit, metric, stamp


def resolve_compression(args):
    """The Compression class the lane runs (and stamps)."""
    from horovod_tpu.jax.compression import Compression

    return getattr(Compression, args.compression or "none")


def wire_leaves(leaves, compression):
    """The leaves ``fused_reduce`` actually buckets: the compressor's
    own ``plan_dtype`` rule (cast compressors halve floating leaves
    BEFORE planning; none/int8/fp8 plan the raw tree), so the stamp's
    plan can never drift from the executing one."""
    import jax

    out = []
    changed = False
    for l in leaves:
        pd = compression.plan_dtype(l.dtype)
        if pd == l.dtype:
            out.append(l)
        else:
            out.append(jax.ShapeDtypeStruct(l.shape, pd))
            changed = True
    return out if changed else leaves


def wire_stamp(args, state, log):
    """The ``"hierarchical"``/``"wire"`` evidence fields: the resolved
    ladder knob (mode + inner) and the per-leg static byte split
    (fusion.hier_wire_summary — ICI vs DCN operand bytes, DCN wire
    dtype, compression ratio), so a multi-slice A/B row carries the
    bytes its prediction (tools/scaling_model.py) is priced on. Null
    wire when the ladder is not engaged (single-slice default)."""
    import jax

    import horovod_tpu.jax as hvd
    from horovod_tpu.common.state import global_state
    from horovod_tpu.jax.fusion import (
        hier_wire_summary,
        plan_buckets,
        resolve_hierarchical,
    )

    mode = args.hierarchical or global_state().config.hierarchical
    if args.zero:
        return {"hierarchical": None, "wire": None}
    inner = resolve_hierarchical(args.hierarchical, hvd.size())
    if not inner:
        return {"hierarchical": {"mode": mode, "inner": 0}, "wire": None}
    comp = resolve_compression(args)
    leaves = wire_leaves(jax.tree_util.tree_leaves(state["params"]), comp)
    plan = plan_buckets(leaves, global_state().config.fusion_threshold)
    wire = hier_wire_summary(plan, hvd.size(), inner, comp)
    log(f"Hierarchical wire split: inner {inner}, ICI {wire['ici_mb']} "
        f"MB, DCN {wire['dcn_mb']} MB @ {wire['dtype']} "
        f"(x{wire['ratio']} vs uncompressed)", file=sys.stderr)
    return {"hierarchical": {"mode": mode, "inner": inner}, "wire": wire}


def overlap_stamp(args, state, log):
    """The overlap/bucket evidence fields for the JSON record: the
    resolved overlap knob plus the fused-bucket plan the gradient
    exchange will execute (count / MB / oversize singletons — the same
    accounting tools/scaling_model.py consumes), so an overlap A/B row
    carries its dispatch-shape evidence like the flash rows carry their
    grid. Uses param shapes only (gradients share them), so it runs
    before the timed windows touch (and donate) the state."""
    import jax

    from horovod_tpu.common.state import global_state
    from horovod_tpu.jax.fusion import plan_buckets, plan_summary

    # Resolve exactly the way fused_reduce will (flag > HOROVOD_OVERLAP
    # config default): the stamp must record what the run executed.
    mode = args.overlap or global_state().config.overlap
    if args.zero:
        # ZeRO's exchange is already reduce-scatter shaped; the overlap
        # knob applies to the fused-psum DP lane only.
        return {"overlap": None, "buckets": None}
    leaves = jax.tree_util.tree_leaves(state["params"])
    summary = plan_summary(plan_buckets(
        leaves, global_state().config.fusion_threshold))
    log(f"Gradient bucket plan: {summary['count']} bucket(s), "
        f"{summary['total_mb']} MB total, "
        f"{summary['oversize_singletons']} oversize singleton(s), "
        f"overlap={mode}", file=sys.stderr)
    return {"overlap": mode, "buckets": summary}


def collectives_stamp(run_step, state, batch, log):
    """The ``"collectives"`` static-audit field: count + bytes of every
    collective in THIS lane's compiled step program, from the hvdverify
    schedule walker (tools/hvdverify — the HVV105 accounting surface,
    cross-checked against the dynamic jaxpr accounting in
    tests/test_wire_bytes.py). Traced on abstract twins of the real
    state/batch BEFORE the timed windows donate the state; pure
    tracing, so it costs seconds of host time and zero device work.
    HVD_BENCH_NO_STATIC_AUDIT=1 skips it (stamps null)."""
    if os.environ.get("HVD_BENCH_NO_STATIC_AUDIT"):
        return {"collectives": None}
    from tools.hvdverify import abstractify, audit_collectives

    audit = audit_collectives(lambda s, b: run_step(s, b),
                              abstractify(state), abstractify(batch))
    field = {"count": audit["count"], "bytes": audit["bytes"],
             "mb": audit["mb"], "by_kind": audit["by_kind"]}
    log(f"Static collective audit: {field['count']} collective(s), "
        f"{field['mb']} MB per step program "
        f"({', '.join(f'{k}:{v}' for k, v in field['by_kind'].items())})",
        file=sys.stderr)
    return {"collectives": field}


def resolve_attention(args) -> str:
    """Resolve the LM lane's attention implementation to "dense"|"flash".

    ``--attention auto``, and an unset ``--attention``, ask
    ``ops.attention.attention_plan`` with the lane's shapes: the flash
    kernels where the v5e sweep found them faster (PERF.md, PR 29), the
    dense reference elsewhere and on the CPU. ``dense`` and ``flash`` pin
    one side for an A/B. ``--flash-attention`` remains the back-compat
    spelling of ``--attention flash``.
    """
    mode = args.attention
    if args.flash_attention:
        if mode not in (None, "flash"):
            raise ValueError(
                f"--flash-attention conflicts with --attention {mode}")
        mode = "flash"
    if mode in (None, "auto"):
        import jax.numpy as jnp

        from horovod_tpu.ops.attention import attention_plan

        heads = args.lm_heads
        kv_heads, head_dim = heads, args.lm_dim // heads
        if args.model == "moe_lm":
            kv_heads = args.lm_kv_heads or heads
            head_dim = args.lm_head_dim or head_dim
        mode = attention_plan(
            args.seq_len, args.seq_len, heads, kv_heads, head_dim,
            dtype=jnp.float32 if args.fp32 else jnp.bfloat16).impl
    return mode


def metric_contract(args):
    """(metric, unit) the JSON line will carry. Window lanes (--steps-per-dispatch K > 1) get a _winK
    metric suffix: a different dispatch protocol than the reference's
    per-step headline, recorded alongside it, never over it."""
    if getattr(args, "probe_only", False):
        return "chip_probe_tflops", "TFLOP/s"
    k = getattr(args, "steps_per_dispatch", 1)
    suffix = f"_win{k}" if k > 1 else ""
    if getattr(args, "compile_only", False):
        # Suffixed too: a K-step window's first step compiles a
        # different (scanned) program than the historical 1-step
        # records — same-name rows would compare apples to oranges.
        return f"{args.model}_first_step_secs{suffix}", "secs"
    if args.model in LM_MODELS:
        return (f"{args.model}_tokens_per_sec_per_chip{suffix}",
                "tokens/sec/chip")
    return f"{args.model}_img_per_sec_per_chip{suffix}", "img/sec/chip"


def _mesh_config(text):
    """argparse type for --mesh: parse + canonicalize through the
    logical-axis vocabulary (horovod_tpu.parallel.logical), so the
    record always carries the canonical spelling ('tp=4,dp=8' and
    'dp=8,tp=4' stamp identically) and an invalid config is a usage
    error, not a mid-run crash."""
    from horovod_tpu.parallel.logical import (
        format_mesh_config,
        parse_mesh_config,
    )

    try:
        return format_mesh_config(parse_mesh_config(text))
    except Exception as e:
        raise argparse.ArgumentTypeError(str(e))


def build_parser():
    """The bench CLI (exposed so tests/test_sweep_lanes.py can statically
    validate every tools/hw_sweep.py lane's arg wiring — a round-3
    hardware window died to a wiring bug no CPU test had covered)."""
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="resnet50")
    parser.add_argument("--mesh", default=None, type=_mesh_config,
                        help="logical mesh config this lane ran under, "
                             "e.g. 'dp=8,tp=4,sp=2' — canonicalized and "
                             "stamped as the record's \"mesh\" field "
                             "(null when unconfigured)")
    parser.add_argument("--batch-size", type=int, default=None,
                        help="per-chip batch (default: 64 images, or 8 "
                             "sequences for transformer_lm)")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--seq-len", type=int, default=2048,
                        help="context length (transformer_lm)")
    parser.add_argument("--vocab", type=int, default=32000)
    parser.add_argument("--lm-layers", type=int, default=12)
    parser.add_argument("--lm-dim", type=int, default=768)
    # Alias for --lm-dim (VERDICT r5 ask #4's spelling): the GPT-2-medium
    # MFU lane is `--model transformer_lm --d-model 1024` (+ --lm-layers
    # 24 --lm-heads 16 in tools/hw_sweep.py's transformer_lm_medium
    # lanes). SUPPRESS keeps --lm-dim's default authoritative.
    parser.add_argument("--d-model", dest="lm_dim", type=int,
                        default=argparse.SUPPRESS,
                        help="alias for --lm-dim (transformer_lm model "
                             "width; --d-model 1024 + --lm-layers 24 + "
                             "--lm-heads 16 is the GPT-2-medium config)")
    parser.add_argument("--lm-heads", type=int, default=12)
    # --model moe_lm (models/decoder.py): grouped-query attention with a
    # type a layer, gated feed-forwards, and this chip's share of the
    # experts. Widths are the model's; what is held here may be a share.
    parser.add_argument("--lm-kv-heads", type=int, default=None,
                        help="moe_lm: KV heads (default: --lm-heads)")
    parser.add_argument("--lm-head-dim", type=int, default=None,
                        help="moe_lm: size of a head (default: "
                             "--lm-dim / --lm-heads)")
    parser.add_argument("--lm-window", type=int, default=2048,
                        help="moe_lm: window of a sliding layer")
    parser.add_argument("--lm-layer-types", default=None,
                        help="moe_lm: 'sliding' (window, rotary positions) "
                             "or 'full' (no positional encoding) a layer, "
                             "comma-separated (default: all full)")
    parser.add_argument("--lm-ffn", type=int, default=None,
                        help="moe_lm: width of a dense layer's gated "
                             "feed-forward (default: 4 x --lm-dim)")
    parser.add_argument("--lm-dense-layers", type=int, default=1,
                        help="moe_lm: leading layers with a dense "
                             "feed-forward; the others have experts")
    parser.add_argument("--moe-experts", type=int, default=8,
                        help="moe_lm: experts the router scores")
    parser.add_argument("--moe-experts-held", type=int, default=None,
                        help="moe_lm: experts this chip holds (default: "
                             "all); tokens routed elsewhere add nothing "
                             "here, and none is dropped")
    parser.add_argument("--moe-first-expert", type=int, default=0,
                        help="moe_lm: the first expert held here")
    parser.add_argument("--moe-top-k", type=int, default=2)
    parser.add_argument("--moe-width", type=int, default=1024,
                        help="moe_lm: width of one expert")
    parser.add_argument("--moe-shared", type=int, default=1,
                        help="moe_lm: shared experts every token passes")
    parser.add_argument("--moe-route-scale", type=float, default=1.0)
    parser.add_argument("--moe-bias-coeff", type=float, default=0.001,
                        help="moe_lm: step of the selection bias's "
                             "balancing rule after every optimizer step")
    parser.add_argument("--steps-per-dispatch", type=int, default=1,
                        help="compile K training steps into ONE XLA "
                             "program (lax.scan window over a device-"
                             "staged K-batch stack): one host dispatch "
                             "and one sync per window amortizes the "
                             "measured 27-32%% per-step host gap on "
                             "short-step models (PERF.md, pre-round profiles). "
                             "Default 1 preserves the reference "
                             "protocol; window records carry a _winK "
                             "metric suffix and vs_baseline=null")
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--fp32", action="store_true",
                        help="disable bfloat16 compute")
    parser.add_argument("--zero", action="store_true",
                        help="ZeRO-1 optimizer-state sharding over the mesh")
    parser.add_argument("--overlap", default=None,
                        choices=("auto", "on", "off"),
                        help="backward-overlapped bucketed gradient "
                             "collectives (horovod_tpu/jax/fusion.py): "
                             "per-bucket reductions issued in reverse "
                             "bucket order, start-all/unpack-later — "
                             "dispatch shape only, numerics "
                             "bit-identical. "
                             "Default: the HOROVOD_OVERLAP env knob "
                             "(auto). The record stamps the mode plus "
                             "the bucket plan (count/MB/oversize)")
    parser.add_argument("--hierarchical", default=None,
                        choices=("auto", "on", "off"),
                        help="hierarchical bucket collectives "
                             "(horovod_tpu/jax/fusion.py): each fused "
                             "bucket runs intra-slice reduce-scatter -> "
                             "inter-slice DCN exchange of the 1/inner "
                             "shard -> intra-slice all-gather. Default: "
                             "the HOROVOD_HIERARCHICAL env knob (auto = "
                             "engage only on a multi-slice/DCN mesh; "
                             "pin the slice size with HOROVOD_"
                             "HIERARCHICAL_INNER_SIZE). The record "
                             "stamps the resolved mode/inner plus the "
                             "per-leg 'wire' byte split")
    parser.add_argument("--compression", default=None,
                        choices=("none", "fp16", "bf16", "int8", "fp8"),
                        help="gradient wire compression "
                             "(horovod_tpu/jax/compression.py): fp16/"
                             "bf16 cast every leg; int8/fp8 quantize "
                             "ONLY the hierarchical DCN leg (per-bucket "
                             "absmax scale + error-feedback residuals "
                             "in optimizer state) and degrade to "
                             "lossless without --hierarchical. The "
                             "record's 'wire' stamp carries the "
                             "ici/dcn byte split and compression ratio")
    parser.add_argument("--snapshot-every", type=int, default=0,
                        help="measure the elastic snapshot overhead at "
                             "this cadence (steps between host-RAM "
                             "snapshots; horovod_tpu.elastic) and stamp "
                             "{'every', 'ms_per_snapshot', "
                             "'overhead_pct'} into the record as "
                             "'snapshot'. 0 (default) = off. The "
                             "elastic default cadence is 100 "
                             "(HOROVOD_SNAPSHOT_EVERY); acceptance "
                             "budget: overhead <= 2%% of step time at "
                             "the default cadence")
    parser.add_argument("--flash-attention", action="store_true",
                        help="transformer_lm: run the Pallas flash "
                             "attention kernel instead of dense "
                             "attention (A/B at the same protocol); "
                             "back-compat spelling of --attention flash")
    parser.add_argument("--attention", default=None,
                        choices=("auto", "dense", "flash"),
                        help="LM lanes' attention: auto (the default) "
                             "asks ops.attention.attention_plan with the "
                             "lane's shapes (the flash kernels where the "
                             "v5e sweep found them faster, the dense "
                             "reference elsewhere and on the CPU; "
                             "PERF.md, PR 29); dense | flash pin one "
                             "side for an A/B")
    parser.add_argument("--flash-full-grid", action="store_true",
                        help="transformer_lm + flash: force the FULL "
                             "causal (q-block, k-block) grid (compute-"
                             "skip only) instead of the packed at-or-"
                             "below-diagonal grid — the truncated-vs-"
                             "full A/B lane in tools/hw_sweep.py")
    parser.add_argument("--flash-bwd", default=None,
                        choices=("auto", "scan", "pallas"),
                        help="transformer_lm + flash: pin the backward "
                             "implementation for an A/B (unset or auto: "
                             "the one ops.attention.attention_plan "
                             "names)")
    parser.add_argument("--compile-only", action="store_true",
                        help="build + compile the train step (one first "
                             "step, metric <model>_first_step_secs) and "
                             "exit: warms the persistent compile cache "
                             "so a big model's measured lane reruns "
                             "against it (tools/hw_sweep.py *_warm lanes)")
    parser.add_argument("--probe-only", action="store_true",
                        help="emit only the chip-condition probe "
                             "(metric chip_probe_tflops) and exit — a "
                             "~30s structured health check for deciding "
                             "whether a measurement window is worth "
                             "spending")
    parser.add_argument("--fused-ce", action="store_true",
                        help="transformer_lm: chunked fused cross-"
                             "entropy (ops/xent.py) — the [B,L,vocab] "
                             "fp32 logits tensor never materializes")
    parser.add_argument("--scan-layers", action="store_true",
                        help="transformer_lm: compile the layer stack as "
                             "one lax.scan step over weight-stacked params "
                             "— ~flat compile time in depth (the unrolled "
                             "default grows linearly). Measured cost: -11%% "
                             "step rate vs unrolled (lost cross-layer "
                             "fusion), and at the default LM shape it "
                             "needs --remat (scan stacks every layer's "
                             "attention residuals — 19.3 GB on a 16 GB "
                             "chip without it; PERF.md pre-round)")
    parser.add_argument("--remat", action="store_true",
                        help="transformer_lm: rematerialize each block on "
                             "the backward pass (activation memory O(1) "
                             "in depth — the long-context default)")
    parser.add_argument("--fused-bn", action="store_true",
                        help="ResNet family: compute BN statistics in the "
                             "1x1-conv matmul epilogue (Pallas kernel, "
                             "ops/conv_bn.py) instead of a separate "
                             "reduction pass — attacks the convert_reduce "
                             "step-time share identified in PERF.md")
    parser.add_argument("--bf16-momentum", action="store_true",
                        help="keep SGD momentum in bfloat16: halves the "
                             "optimizer-state HBM traffic of the update "
                             "(PERF.md), off by default for reference-"
                             "protocol parity")
    return parser


def main():
    args = build_parser().parse_args()

    import horovod_tpu.jax as hvd
    from horovod_tpu.utils import compile_cache
    from horovod_tpu.utils.device import require_tpu

    compile_cache.enable()
    hvd.init()
    device = require_tpu(
        cpu_requested=bool(os.environ.get("HVD_TPU_FORCE_CPU")))
    log = print if hvd.rank() == 0 else (lambda *a, **k: None)
    log(f"Device: {device['platform']} / {device['device_kind']} x "
        f"{device['count']}", file=sys.stderr)

    if args.probe_only:
        probe = probe_chip(log)
        log(json.dumps({
            "metric": "chip_probe_tflops", "value": probe,
            "unit": "TFLOP/s", "vs_baseline": None,
            "peak": None, "probe_tflops": probe, "device": device,
        }))
        return

    mean, peak, unit, metric, extra = measure_lane(
        build_lane(args, log), args, log)
    # Probe AFTER the timed windows: adjacent to the measurement it
    # sits beside in the record.
    probe = probe_chip(log)

    # vs_baseline is a REFERENCE-PROTOCOL ratio: window lanes
    # (K > 1) change the dispatch protocol, so they carry null
    # rather than an apples-to-oranges comparison.
    base = (None if args.compile_only or args.steps_per_dispatch > 1
            else REFERENCE_BASELINES.get(args.model))
    log(json.dumps({
        "metric": metric,
        "value": round(mean, 2),
        "unit": unit,
        "vs_baseline": round(mean / base, 3) if base else None,
        "peak": round(peak, 2),
        "probe_tflops": probe,
        "device": device,
        "window": args.steps_per_dispatch,
        "mesh": args.mesh,
        # LM lanes append the resolved attention implementation and
        # (flash only) the grid/K-V-bytes accounting — the evidence
        # chain for the truncated-vs-full A/B records.
        **extra,
    }))


if __name__ == "__main__":
    main()
