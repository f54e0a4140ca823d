#!/usr/bin/env python
"""Measured scaling-efficiency model: bucket bytes vs ICI/DCN bandwidth.

The reference's entire public claim is its scaling table — 90% (Inception
V3 / ResNet-101) and 68% (VGG-16) efficiency at 512 GPUs over 25GbE
(reference docs/benchmarks.md) — while this rebuild shipped zero analysis
of what its fused-bucket gradient exchange costs against TPU interconnect.
This tool closes that gap with three measured ingredients and one model:

1. **Per-model fused-bucket bytes** — the exact plan
   `horovod_tpu.jax.fusion.plan_buckets` executes (same code path the
   DistributedOptimizer traces), derived from `jax.eval_shape` over each
   model's parameter tree: zero FLOPs, runs anywhere, and the numbers are
   pinned by tests/test_scaling_model.py.
2. **Single-chip collective dispatch overhead** — `--microbench` times a
   compiled psum dispatch under the sync-honest `_force_sync` discipline
   (PERF.md pre-round: one d2h pull before any clock read), feeding the
   per-bucket fixed cost. Without hardware the documented default stands.
3. **Measured single-chip step times** — the round-5 honest benchmarks
   (docs/benchmarks.md; PERF_RUNS.tsv).

Model: weak scaling (per-chip batch fixed). A bucket's ring allreduce
costs ``2(n-1)/n * bytes / bw + 2(n-1) * hop_latency + dispatch``; the
overlap schedule (HOROVOD_OVERLAP, horovod_tpu/jax/fusion.py) can hide
communication under backward compute up to ``overlap_fraction *
backward_time``, where the plan-derived default fraction is
``(buckets - 1) / buckets`` — the first-layer bucket is issued last, with
no backward left to hide under. Efficiency(n) = step / (step + exposed).

    python tools/scaling_model.py                 # the docs table
    python tools/scaling_model.py --microbench    # measure dispatch cost
    python tools/scaling_model.py --fusion-threshold 1048576
"""

import argparse
import sys
import time

# --------------------------------------------------------------------------
# Interconnect figures (documented assumptions, not measurements).
#
# TPU v5e: 1,600 Gbps inter-chip interconnect per chip (Google Cloud v5e
# spec sheet) = 200 GB/s; a v5e slice is ICI end-to-end up to 256 chips,
# so the 1->64 ladder below is all-ICI. The DCN variant models multi-slice
# data parallelism: 8-chip ICI domains joined over the data-center network
# at ~25 GB/s per host (200 Gbps NIC) = ~3.125 GB/s per chip, with the
# hierarchical ladder (HOROVOD_HIERARCHICAL_ALLREDUCE: reduce-scatter in
# the ICI domain, cross-reduce 1/inner of the bytes over DCN, all-gather).
ICI_GBPS = 200.0
DCN_GBPS_PER_CHIP = 3.125
ICI_HOP_LATENCY_US = 1.0
DCN_HOP_LATENCY_US = 10.0
# Per-collective host+launch overhead. Default = the pre-round profile's
# per-op dispatch share (PERF.md); --microbench replaces it with a fresh
# measurement.
DEFAULT_DISPATCH_US = 5.0

# Fraction of a training step that is backward compute (fwd:bwd ~ 1:2 for
# these architectures) — the window overlap can hide communication under.
BACKWARD_FRACTION = 2.0 / 3.0

# --------------------------------------------------------------------------
# Measured single-chip step times (round-5 HONEST protocol; one v5e-class
# chip, docs/benchmarks.md "Measured" table, 2026-08-01). transformer_lm
# is the 12L/768d bench default at seq 2048, batch 8 (16,384 tok/step).
# transformer_lm_medium (24L/1024d/16h — VERDICT r5 ask #4's GPT-2-medium
# lane, queued in tools/hw_sweep.py) has no measured row yet: its step
# time is ESTIMATED as 6*P*T FLOPs at the base LM's measured 26% MFU of
# the ~180 TF/s probe rate, and the table says so.
MEASURED = {
    "resnet50": {"step_ms": 64 / 1906 * 1e3, "source": "1,906 img/s bs64"},
    "vgg16": {"step_ms": 64 / 783 * 1e3, "source": "783 img/s bs64"},
    "transformer_lm": {"step_ms": 16384 / 61078 * 1e3,
                       "source": "61,078 tok/s seq2048 bs8"},
    "transformer_lm_medium": {"step_ms": None,
                              "source": "est. 6PT @ 26% MFU of 180 TF"},
}

PROBE_TFLOPS = 180.0
LM_MEASURED_MFU = 0.26


def model_param_leaves(name):
    """Parameter-leaf ShapeDtypeStructs of a zoo model via jax.eval_shape
    — the exact tree the DistributedOptimizer's fused exchange reduces,
    with zero parameter FLOPs or memory."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    if name == "transformer_lm":
        # The bench.py lane defaults: 12L / 768d / 12 heads, vocab 32000.
        model = models.TransformerLM(num_layers=12, num_heads=12,
                                     embed_dim=768)
        sample = jnp.zeros((1, 2048), jnp.int32)
    elif name == "transformer_lm_medium":
        model = models.TransformerLM(num_layers=24, num_heads=16,
                                     embed_dim=1024)
        sample = jnp.zeros((1, 2048), jnp.int32)
    else:
        model = models.build(name, num_classes=1000)
        sample = jnp.zeros((1, 224, 224, 3), jnp.float32)
    variables = jax.eval_shape(
        functools.partial(model.init, train=False),
        jax.random.PRNGKey(0), sample)
    return jax.tree_util.tree_leaves(variables["params"])


def bucket_stats(name, fusion_threshold):
    """(plan, summary) of the model's fused gradient buckets — the
    numbers the efficiency model (and bench.py's JSON stamp) consume."""
    from horovod_tpu.jax.fusion import plan_buckets, plan_summary

    plan = plan_buckets(model_param_leaves(name), fusion_threshold)
    return plan, plan_summary(plan)


def step_time_ms(name, summary):
    rec = MEASURED[name]
    if rec["step_ms"] is not None:
        return rec["step_ms"]
    # Estimated lane (transformer_lm_medium): 6 * params * tokens at the
    # measured base-LM MFU — replaced by the hw_sweep lane's record
    # once it has run.
    params = summary["total_bytes"] / 4  # fp32 leaves
    tokens = 4 * 2048  # the lane's batch 4 seqs/chip x seq 2048
    flops = 6.0 * params * tokens
    return flops / (PROBE_TFLOPS * 1e12 * LM_MEASURED_MFU) * 1e3


def ring_allreduce_us(nbytes, n, bw_gbps, hop_latency_us, dispatch_us,
                      split_collectives=1):
    """One bucket's ring-allreduce wall time on an n-chip ring:
    2(n-1)/n of the bytes over the per-chip bandwidth, 2(n-1) hop
    latencies, plus the fixed per-collective dispatch cost
    (``split_collectives=2`` for the ladder's intra-slice rs+ag pair)."""
    if n <= 1:
        return 0.0
    wire_bytes = 2.0 * (n - 1) / n * nbytes
    return (wire_bytes / (bw_gbps * 1e3)
            + 2.0 * (n - 1) * hop_latency_us
            + dispatch_us * split_collectives)


# DCN wire options (bench.py --compression; horovod_tpu/jax/compression):
# fp16/bf16 cast EVERY leg to 2 bytes/elem; int8/fp8 quantize ONLY the
# DCN leg to 1 byte/elem (+ scalar scales, negligible) and leave ICI at
# fp32 — the fusion.py hierarchical contract this model prices.
DCN_WIRE_MODES = ("none", "fp16", "bf16", "int8", "fp8")


def hierarchical_allreduce_us(nbytes, n, inner, dispatch_us,
                              dcn_wire="none"):
    """Multi-slice ladder: reduce-scatter inside the inner-chip ICI
    domain, exchange 1/inner of the bytes over DCN between the n/inner
    slices, all-gather back (fusion.py -> mesh.py ladder).

    ``dcn_wire`` prices the compression of the inter-slice leg.
    int8/fp8 use the shapes fusion.py actually traces: at 2 slices an
    all-gather of the quantized shards ((m-1) x q bytes per chip); at
    >2 slices the two-stage quantized ring decomposition (all-to-all +
    all-gather, 2(m-1)/m x q bytes, two collective launches) — per-chip
    DCN wire stays ~2q instead of growing with the slice count."""
    cast = dcn_wire in ("fp16", "bf16")
    quant = dcn_wire in ("int8", "fp8")
    if n <= inner:
        # Single slice, no DCN leg: cast compressors still halve the
        # (only) leg — the table must stay comparable across the
        # c == inner boundary; the DCN-only codecs do nothing here.
        return ring_allreduce_us(nbytes / 2 if cast else nbytes, n,
                                 ICI_GBPS, ICI_HOP_LATENCY_US,
                                 dispatch_us)
    m = n // inner
    ici_bytes = nbytes / 2 if cast else nbytes
    ici = ring_allreduce_us(ici_bytes, inner, ICI_GBPS, ICI_HOP_LATENCY_US,
                            dispatch_us, split_collectives=2)
    if quant:
        q = (nbytes / 4) / inner  # fp32 elements -> 1-byte payloads
        if m == 2:
            wire_bytes, colls = (m - 1) * q, 1
        else:
            wire_bytes, colls = 2.0 * (m - 1) / m * q, 2
        dcn = (wire_bytes / (DCN_GBPS_PER_CHIP * 1e3)
               + colls * (m - 1) * DCN_HOP_LATENCY_US
               + dispatch_us * colls)
    else:
        dcn = ring_allreduce_us(ici_bytes / inner, m, DCN_GBPS_PER_CHIP,
                                DCN_HOP_LATENCY_US, dispatch_us)
    return ici + dcn


def predict_efficiency(name, n, fusion_threshold, overlap="auto",
                       dispatch_us=DEFAULT_DISPATCH_US, dcn_inner=0,
                       dcn_wire="none", _stats=None):
    """Predicted weak-scaling efficiency of the DP step at n chips.

    ``overlap``: "off" = the legacy post-backward block (no hiding);
    "on"/"auto" = the overlap schedule hides up to
    ``(buckets-1)/buckets * backward`` of the communication (the
    plan-derived fraction; see module docstring). ``dcn_inner`` > 0
    switches to the multi-slice ladder with that ICI domain size;
    ``dcn_wire`` prices the wire compression of the hierarchical DCN
    leg (int8/fp8 compress the DCN leg only, fp16/bf16 every leg).
    """
    plan, summary = _stats if _stats is not None else bucket_stats(
        name, fusion_threshold)
    step_us = step_time_ms(name, summary) * 1e3
    if n <= 1:
        return {"efficiency": 1.0, "comm_ms": 0.0, "exposed_ms": 0.0,
                "step_ms": step_us / 1e3, "buckets": summary["count"]}
    overlapped = overlap in ("on", "auto") and summary["count"] >= (
        1 if overlap == "on" else 2)
    if dcn_inner:
        comm_us = sum(hierarchical_allreduce_us(b.nbytes, n, dcn_inner,
                                                dispatch_us,
                                                dcn_wire=dcn_wire)
                      for b in plan)
    else:
        comm_us = sum(ring_allreduce_us(b.nbytes, n, ICI_GBPS,
                                        ICI_HOP_LATENCY_US, dispatch_us)
                      for b in plan)
    backward_us = BACKWARD_FRACTION * step_us
    frac = ((summary["count"] - 1) / summary["count"]) if overlapped else 0.0
    hidden = min(frac * comm_us, backward_us)
    exposed_us = comm_us - hidden
    return {
        "efficiency": step_us / (step_us + exposed_us),
        "comm_ms": comm_us / 1e3,
        "exposed_ms": exposed_us / 1e3,
        "step_ms": step_us / 1e3,
        "buckets": summary["count"],
    }


CHIP_LADDER = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def efficiency_table(fusion_threshold, overlap="auto",
                     dispatch_us=DEFAULT_DISPATCH_US, dcn_inner=0,
                     dcn_wire="none", models=None, chips=None):
    """Markdown rows: per model, predicted efficiency across the chip
    ladder (or the ``chips`` override, e.g. a mesh config's device
    product) plus the bucket accounting that produced it."""
    ladder = tuple(chips) if chips else CHIP_LADDER
    lines = ["| model | buckets | grad MB | step ms | "
             + " | ".join(f"{c}c" for c in ladder) + " |",
             "|---|---|---|---|" + "---|" * len(ladder)]
    for name in models or list(MEASURED):
        stats = bucket_stats(name, fusion_threshold)
        _, summary = stats
        cells = []
        for c in ladder:
            p = predict_efficiency(name, c, fusion_threshold,
                                   overlap=overlap, dispatch_us=dispatch_us,
                                   dcn_inner=dcn_inner, dcn_wire=dcn_wire,
                                   _stats=stats)
            cells.append(f"{p['efficiency'] * 100:.1f}%")
        step_ms = step_time_ms(name, summary)
        est = "" if MEASURED[name]["step_ms"] is not None else "~"
        lines.append(
            f"| {name} | {summary['count']} "
            f"({summary['oversize_singletons']} oversize) "
            f"| {summary['total_mb']} | {est}{step_ms:.1f} | "
            + " | ".join(cells) + " |")
    return "\n".join(lines)


def microbench_dispatch(iters=200):
    """Single-chip collective dispatch overhead, sync-honest: a compiled
    psum program dispatched ``iters`` times; the clock reads only bracket
    regions that end in a forced d2h pull (the round-5 discipline —
    without it this times async dispatch enqueue, not the op)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel.logical import DATA_AXIS
    from horovod_tpu.utils.devsync import force_device_sync

    mesh = Mesh(np.array(jax.devices()[:1]), (DATA_AXIS,))
    f = jax.jit(jax.shard_map(
        lambda x: lax.psum(x, DATA_AXIS), mesh=mesh, in_specs=P(),
        out_specs=P(), check_vma=False))
    x = jnp.ones((1024,), jnp.float32)
    out = f(x)
    force_device_sync(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(out)
    force_device_sync(out)
    us = (time.perf_counter() - t0) / iters * 1e6
    print(f"[microbench] per-collective dispatch: {us:.1f} us "
          f"({iters} chained psum dispatches, sync-honest)",
          file=sys.stderr)
    return us


def main():
    from horovod_tpu.common.config import DEFAULT_FUSION_THRESHOLD

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fusion-threshold", type=int,
                    default=DEFAULT_FUSION_THRESHOLD,
                    help="bucket threshold in bytes (HOROVOD_FUSION_"
                         "THRESHOLD; default 64 MiB)")
    ap.add_argument("--overlap", default="auto",
                    choices=("auto", "on", "off"),
                    help="overlap schedule assumed by the prediction")
    ap.add_argument("--dcn-inner", type=int, default=0,
                    help="model multi-slice DP: ICI domain size joined "
                         "over DCN via the hierarchical ladder (0 = "
                         "all-ICI, the single-slice default)")
    ap.add_argument("--dcn-compression", default="none",
                    choices=DCN_WIRE_MODES,
                    help="price the wire compression of the "
                         "hierarchical DCN leg (int8/fp8: quantized "
                         "payloads, fusion.py's exchange shapes; "
                         "fp16/bf16: every leg cast). Needs --dcn-inner")
    ap.add_argument("--microbench", action="store_true",
                    help="measure the per-collective dispatch overhead "
                         "on this chip instead of the documented default")
    ap.add_argument("--models", default="",
                    help="comma list (default: all of "
                         f"{','.join(MEASURED)})")
    ap.add_argument("--mesh", default=None,
                    help="logical mesh config, e.g. 'dp=8,tp=4,sp=2' "
                         "(horovod_tpu.parallel.logical vocabulary): "
                         "restricts the table to that device product "
                         "and stamps the canonical config in the "
                         "header")
    args = ap.parse_args()

    mesh_cfg, mesh_chips = None, None
    if args.mesh:
        from horovod_tpu.parallel.logical import (
            format_mesh_config,
            parse_mesh_config,
        )

        try:
            axes = parse_mesh_config(args.mesh)
        except Exception as e:
            ap.error(f"--mesh: {e}")
        mesh_cfg = format_mesh_config(axes)
        mesh_chips = [1]
        for size in axes.values():
            mesh_chips[0] *= size

    dispatch_us = DEFAULT_DISPATCH_US
    if args.microbench:
        dispatch_us = microbench_dispatch()
    models = [m for m in args.models.split(",") if m] or None
    for m in models or MEASURED:
        if m not in MEASURED:
            ap.error(f"unknown model {m!r}; have {sorted(MEASURED)}")

    if args.dcn_compression != "none" and not args.dcn_inner:
        ap.error("--dcn-compression prices the hierarchical DCN leg; "
                 "pass --dcn-inner as well")
    print(f"# Predicted weak-scaling efficiency "
          f"(fusion threshold {args.fusion_threshold} B, "
          f"overlap={args.overlap}, dispatch {dispatch_us:.1f} us, "
          + (f"multi-slice DCN inner={args.dcn_inner}, "
             f"wire={args.dcn_compression}"
             if args.dcn_inner else "all-ICI")
          + (f", mesh={mesh_cfg}" if mesh_cfg else "") + ")")
    print()
    print(efficiency_table(args.fusion_threshold, overlap=args.overlap,
                           dispatch_us=dispatch_us,
                           dcn_inner=args.dcn_inner,
                           dcn_wire=args.dcn_compression, models=models,
                           chips=mesh_chips))
    return 0


if __name__ == "__main__":
    sys.exit(main())
