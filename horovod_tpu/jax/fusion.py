"""Tensor fusion: the bucketed gradient exchange, overlap-scheduled.

TPU-native rebuild of the reference's fusion machinery — the 64 MB fusion
buffer (horovod/common/fusion_buffer_manager.h:50-55), the response-merging
look-ahead that packs same-dtype tensors into one collective
(operations.cc:2160-2264), and the MEMCPY_IN/OUT_FUSION_BUFFER data plane
(operations.cc:1491-1586).

Mapping onto XLA:

* the bucket PLAN stays (same-dtype tensors, greedily packed up to
  HOROVOD_FUSION_THRESHOLD): it names each bucket's
  ``hvd_allreduce_<name>_<dtype>_b<i>`` scope, orders the issue, feeds the
  ``hvd.exchange.*`` gauges and the HVV105 byte accounting;
* "memcpy into the fusion buffer" has NO counterpart on a single slice:
  a bucket reduces its members in their own shapes, one ``lax.psum`` a
  member under the bucket's scope. A ``f32[1024,4096]`` leaf lives in
  (8,128) tiles and a flat 1-D buffer does not, so ``ravel`` +
  ``concatenate`` in and slice + ``reshape`` out are relayouts of every
  gradient byte in HBM (measured on the v5e: PERF.md, PR 27); grouping the
  all-reduces on the wire is XLA's all-reduce combiner's job;
* only the hierarchical ladder (several slices, below) still packs a
  bucket into one flat, padded buffer: its intra-slice reduce-scatter
  needs one. ``hvd.exchange.packed_bytes`` counts the bytes a step copies
  that way (0 on a single slice).

Overlap scheduling (HOROVOD_OVERLAP=auto|on|off): the reference hid the
gradient exchange behind backward compute by firing an allreduce from each
gradient hook as autograd produced it (Sergeev & Del Balso 2018; PyTorch
DDP's reverse-order buckets, Li et al. VLDB 2020). Under XLA the step is
one program, and what runs beside what is the compiler's scheduler's: with
overlap on, per-bucket collectives are issued in REVERSE bucket order as a
start-all/unpack-later sequence, so each leaf's collective depends on that
leaf alone and nothing here stands in the scheduler's way. The emission by
itself hides nothing: under XLA:TPU's defaults an all-reduce is a
synchronous operation (on the v5e all 28.3 ms a step of the data-parallel
GPT-2-medium cell stood alone on the core; PERF.md, PR 27 to 31). What
makes them asynchronous is how the program is COMPILED:
``hvd.spmd_fn`` passes :data:`horovod_tpu.parallel.spmd.
ASYNC_ALL_REDUCE_OPTIONS` to ``jax.jit`` for a TPU mesh of several chips
(asynchronous all-reduce, asynchronous collective fusion of it, also into
loop fusions, and the all-reduce combiner held under 1 MiB, because the
fusion pass takes an all-reduce of one operand only). XLA's
latency-hiding scheduler then moves every all-reduce BEHIND the backward
pass, next to the weight-gradient product of the following leaf and to the
optimizer's passes, not under the backward pass's other work: nothing but
the optimizer waits for a reduced gradient, and the scheduler places an
all-reduce as late as its consumer allows (PERF.md, PR 31, has the
schedule and the times). Overlap NEVER changes results: the emission order
changes, the math does not (pinned bit-exactly in tests/test_overlap.py).

Same-dtype-only fusion matches the reference (it fused only responses with
identical dtype/device signatures, operations.cc:2175-2230).

Hierarchical bucket execution (HOROVOD_HIERARCHICAL=auto|on|off): on a
multi-slice mesh the flat psum would push every gradient byte across
DCN (~3 GB/s/chip) when 200 GB/s ICI sits inside each slice. With the
ladder engaged, each bucket runs intra-slice reduce-scatter -> inter-
slice exchange of the 1/``inner`` shard -> intra-slice all-gather (the
reference's NCCL-within/MPI-across hierarchical allreduce,
operations.cc:1284-1436, as explicit XLA collectives over
``axis_index_groups`` — shared rung: parallel/mesh.py
``hierarchical_ladder_in_axis``; two-level mesh factory:
``hybrid_mesh``). "auto" engages only when the device set spans a DCN
boundary (``parallel.mesh.dcn_present``). Composes with the overlap
schedule (reverse-order issue applies per bucket regardless of its
collective shape).

Low-bit DCN wire (``Compression.int8`` / ``Compression.fp8``): the DCN
leg optionally quantizes the shard with a per-bucket absmax scale (the
scale rides beside the payload as a scalar all-gather) and an optional
error-feedback residual carried in optimizer state
(:func:`ef_residual_specs`; Seide et al. 2014 / DGC lineage), so
quantization error is re-injected the next step instead of compounding.
Two exchange shapes: at 2 slices, an all-gather of the quantized shards
with local dequant-sum; at >2 slices, the quantized ring decomposition
— all-to-all of quantized sub-shards, local dequant-sum, re-quantize
(second residual), all-gather — keeping per-chip DCN wire at
``~2(m-1)/m`` of the QUANTIZED shard instead of growing with the slice
count. ICI legs always stay at the bucket's own dtype.

Dtype ladder (where bytes live and where the Average divide happens —
the no-double-scaling contract pinned by tests/test_hierarchical.py):

    compression   ICI wire      DCN wire        accumulate  1/n divide
    ------------  ------------  --------------  ----------  -------------
    none          input dtype   input dtype     input       shard, pre-ag
    fp16 / bf16   wire dtype    wire dtype      wire        tail, fp32*
    int8 / fp8    input dtype   int8/fp8+scale  fp32        shard, pre-ag

    (*) cast compressors divide once, at the decompressed tail — the
    historical flat-path behavior, kept so hierarchical-off and -on
    share one reduction + division sequence exactly. The quantized
    codecs divide the dequantized fp32 shard BEFORE the all-gather
    (elementwise divide commutes with gather — bit-identical to a tail
    divide, 1/inner of the work) and never at the tail, so Average is
    applied exactly once; the error-feedback residual lives in the
    pre-divide SUM domain, so feedback composes with Average without
    double-scaling.
"""

from __future__ import annotations

import collections
import math
from typing import List, NamedTuple, Optional, Sequence

import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.config import HIERARCHICAL_MODES, OVERLAP_MODES
from horovod_tpu.common.exceptions import InvalidArgumentError
from horovod_tpu.common.state import current_spmd_axis, global_state
from horovod_tpu.jax.compression import Compression, is_dcn_wire


def _plan_buckets(sizes_bytes: Sequence[int], threshold: int) -> List[List[int]]:
    """Greedy contiguous bucketing: consecutive tensors pack into a bucket
    until adding the next would exceed ``threshold`` (an oversize tensor
    gets its own bucket, like an oversize response in the reference)."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, nb in enumerate(sizes_bytes):
        if cur and cur_bytes + nb > threshold:
            buckets.append(cur)
            cur = []
            cur_bytes = 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        buckets.append(cur)
    return buckets


class Bucket(NamedTuple):
    """One fused-collective bucket of the plan (public accounting record:
    the exchange's gauges, hvdverify's HVV105 and the bucket-byte tests
    consume these)."""

    dtype: str        # wire dtype name, e.g. "float32"
    index: int        # position within this dtype's bucket sequence
    members: tuple    # indices into the input tensor list, input order
    nbytes: int       # payload bytes (sum of member bytes, unpadded)
    oversize: bool    # single tensor alone exceeding the fusion threshold


def _leaf_size(leaf) -> int:
    size = getattr(leaf, "size", None)
    if size is None:  # ShapeDtypeStruct on older jax: derive from shape
        size = int(math.prod(leaf.shape))
    return int(size)


def plan_buckets(leaves, threshold: int) -> List[Bucket]:
    """The full bucket plan for ``leaves`` (arrays or ShapeDtypeStructs):
    grouped by dtype (first-appearance order), greedily packed to
    ``threshold`` bytes within each group, forward (input) order.

    This is exactly the plan :func:`fused_reduce` executes, exposed so the
    scaling model and tests can account bucket bytes without tracing."""
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    plan: List[Bucket] = []
    for dtype, idxs in by_dtype.items():
        sizes = [_leaf_size(leaves[i]) * dtype.itemsize for i in idxs]
        for b, bucket in enumerate(_plan_buckets(sizes, threshold)):
            nbytes = sum(sizes[j] for j in bucket)
            plan.append(Bucket(
                dtype=dtype.name,
                index=b,
                members=tuple(idxs[j] for j in bucket),
                nbytes=nbytes,
                oversize=len(bucket) == 1 and nbytes > threshold,
            ))
    return plan


def plan_summary(plan: Sequence[Bucket]) -> dict:
    """Compact accounting of a bucket plan: count, bytes and the oversize
    singletons."""
    total = sum(b.nbytes for b in plan)
    return {
        "count": len(plan),
        "total_bytes": total,
        "total_mb": round(total / (1024 * 1024), 2),
        "oversize_singletons": sum(1 for b in plan if b.oversize),
        "largest_bytes": max((b.nbytes for b in plan), default=0),
    }


def resolve_overlap(mode: Optional[str], n_buckets: int) -> bool:
    """Resolve the overlap knob to a concrete decision for one plan.

    ``auto`` engages overlap emission whenever the plan has >= 2 buckets
    (with a single bucket there is nothing to interleave — the legacy
    single-pass emission is kept so historical wire shapes stay
    byte-identical); ``on`` forces the overlap shape even for one bucket;
    ``off`` is the legacy post-backward block. ``None`` reads the
    HOROVOD_OVERLAP config default.
    """
    if mode is None:
        mode = global_state().config.overlap
    if mode is True:
        mode = "on"
    elif mode is False:
        mode = "off"
    if mode not in OVERLAP_MODES:
        raise InvalidArgumentError(
            f"overlap must be one of {OVERLAP_MODES} (got {mode!r})")
    if mode == "off":
        return False
    if mode == "on":
        return True
    return n_buckets >= 2


def _hierarchical_inner(st, axis_size: int, enabled: bool) -> int:
    """Fast-domain size for the two-level ladder, or 0 when the flat
    collective should be used. Auto mode uses chips-per-process (the
    reference's local/cross comm split, operations.cc:1760-1797).
    (Legacy helper kept for the allgather lane — the allreduce path now
    resolves through :func:`resolve_hierarchical`.)"""
    if not enabled:
        return 0
    inner = st.config.hierarchical_inner_size or st.local_device_count
    if 1 < inner < axis_size and axis_size % inner == 0:
        return inner
    return 0


def resolve_hierarchical(mode: Optional[str], axis_size: int) -> int:
    """Resolve the HOROVOD_HIERARCHICAL knob to a fast-domain (ICI)
    size for this axis, or 0 for the flat collective.

    ``auto`` (default) engages only when the device set spans a DCN
    boundary (multiple slices or processes — ``parallel.mesh.
    dcn_present``), with the detected chips-per-slice as the inner
    size; ``on`` forces the ladder with HOROVOD_HIERARCHICAL_INNER_SIZE
    (falling back to chips-per-slice, then chips-per-process); ``off``
    is the flat collective. The legacy HOROVOD_HIERARCHICAL_ALLREDUCE=1
    boolean reads as ``on``. An inner size that does not strictly
    divide the axis (1 < inner < axis_size) degrades to flat, the
    reference's is_homogeneous degradation (operations.cc:1303-1315).
    """
    st = global_state()
    if mode is None:
        mode = st.config.hierarchical
        # The legacy boolean is an EXPLICIT opt-in (env var or the
        # autotuner's categorical knob): when set it forces the ladder
        # regardless of the tri-state default.
        if st.config.hierarchical_allreduce:
            mode = "on"
    if mode is True:
        mode = "on"
    elif mode is False:
        mode = "off"
    if mode not in HIERARCHICAL_MODES:
        raise InvalidArgumentError(
            f"hierarchical must be one of {HIERARCHICAL_MODES} "
            f"(got {mode!r})")
    if mode == "off":
        return 0
    from horovod_tpu.parallel.mesh import dcn_present, slice_topology

    devices = st.devices or None
    inner = st.config.hierarchical_inner_size
    if mode == "auto":
        # auto = engage only on a REAL multi-slice/DCN mesh, explicit
        # inner size or not — single-slice jobs stay flat (force the
        # ladder there with "on").
        if not dcn_present(devices):
            return 0
        if not inner:
            try:
                _, inner = slice_topology(devices)
            except InvalidArgumentError:
                # Heterogeneous chips-per-domain with no explicit inner:
                # no valid ladder tiling exists — degrade to flat, the
                # reference's is_homogeneous rule.
                return 0
    elif not inner:  # "on" without an explicit inner size
        try:
            domains, per = slice_topology(devices)
            inner = per if domains > 1 else st.local_device_count
        except InvalidArgumentError:
            inner = st.local_device_count
    if inner and 1 < inner < axis_size and axis_size % inner == 0:
        return inner
    return 0


def _pad_up_elems(elems: int, quantum: int) -> int:
    return (elems + quantum - 1) // quantum * quantum


def hier_bucket_layout(elems: int, axis_size: int, inner: int,
                       quantized: bool) -> dict:
    """Static element-count layout of one hierarchical bucket: how the
    flat buffer pads and shards on the ladder. ``m`` is the slice
    (outer/DCN) count; quantized buckets at m > 2 take the two-stage
    exchange, whose all-to-all needs the shard divisible by m as well.
    Shared by the executing path, :func:`ef_residual_specs`,
    :func:`hier_wire_summary` and the HVV105 reconciliation — one
    layout, four consumers, no drift."""
    m = axis_size // inner
    two_stage = quantized and m > 2
    quantum = inner * m if two_stage else inner
    padded = _pad_up_elems(elems, quantum)
    shard = padded // inner
    return {
        "m": m,
        "two_stage": two_stage,
        "padded_elems": padded,
        "shard_elems": shard,
        "sub_elems": shard // m if two_stage else 0,
    }


def _ef_eligible(bucket: "Bucket") -> bool:
    """Buckets the low-bit DCN codec (and so the error-feedback
    residual) applies to: floating dtypes only — integer gradients take
    the plain psum DCN leg."""
    return jnp.issubdtype(jnp.dtype(bucket.dtype), jnp.floating)


def ef_residual_specs(leaves, threshold: int, axis_size: int, inner: int):
    """GLOBAL-shaped ShapeDtypeStructs of the error-feedback residuals
    for a quantized hierarchical exchange over ``leaves`` — one fp32
    vector per quantized stage per floating bucket, in plan order.

    Each residual is rank-LOCAL state: chip ``r`` owns rows
    ``[r*shard : (r+1)*shard)`` of the global vector. Feed these leaves
    through the training step with ``P("hvd")`` partition specs
    (``models.state_partition_specs`` derives them) so shard_map hands
    every chip exactly its own slice; the leaves are created zero by
    ``allreduce_gradients_transform``'s init and updated in place of
    the optimizer state each step. Buckets at 2 slices carry one
    residual (the all-gather exchange quantizes once); buckets at >2
    slices carry two (the two-stage exchange re-quantizes the summed
    sub-shard)."""
    import jax

    specs = []
    for bucket in plan_buckets(leaves, threshold):
        if not _ef_eligible(bucket):
            continue
        itemsize = jnp.dtype(bucket.dtype).itemsize
        layout = hier_bucket_layout(bucket.nbytes // itemsize, axis_size,
                                    inner, quantized=True)
        specs.append(jax.ShapeDtypeStruct(
            (axis_size * layout["shard_elems"],), jnp.float32))
        if layout["two_stage"]:
            specs.append(jax.ShapeDtypeStruct(
                (axis_size * layout["sub_elems"],), jnp.float32))
    return specs


def hier_wire_summary(plan: Sequence[Bucket], axis_size: int, inner: int,
                      compression=Compression.none) -> dict:
    """Per-leg STATIC operand-byte split of a hierarchical bucket plan,
    derived from the same :func:`hier_bucket_layout` the executing path
    uses (so it is checkable against the HVV105-reconciled schedule).

    ``ici_bytes`` = intra-slice reduce-scatter + all-gather operands;
    ``dcn_bytes`` = inter-slice exchange operands (quantized payloads +
    their scale scalars under int8/fp8); ``ratio`` = what the DCN leg
    would have carried at the input dtype over what it carries now
    (1.0 uncompressed, ~4x under int8/fp8 from fp32)."""
    quantizer = compression if is_dcn_wire(compression) else None
    ici = dcn = flat_dcn = 0
    dcn_dtype = None
    for b in plan:
        dt = jnp.dtype(b.dtype)
        elems = b.nbytes // dt.itemsize
        q = quantizer is not None and _ef_eligible(b)
        layout = hier_bucket_layout(elems, axis_size, inner, quantized=q)
        shard = layout["shard_elems"]
        # Quantized buckets dequant-sum in fp32, so the final intra-
        # slice all-gather carries fp32 regardless of the input dtype.
        ag_itemsize = 4 if q else dt.itemsize
        ici += layout["padded_elems"] * dt.itemsize + shard * ag_itemsize
        if q:
            wire = jnp.dtype(quantizer.wire_dtype)
            dcn_dtype = wire.name
            if layout["two_stage"]:
                dcn += (shard + layout["sub_elems"]) * wire.itemsize + 8
            else:
                dcn += shard * wire.itemsize + 4
        else:
            dcn += shard * dt.itemsize
            if dcn_dtype is None:
                dcn_dtype = dt.name
        flat_dcn += shard * dt.itemsize
    return {
        "ici_bytes": int(ici),
        "dcn_bytes": int(dcn),
        "ici_mb": round(ici / (1024 * 1024), 3),
        "dcn_mb": round(dcn / (1024 * 1024), 3),
        "dtype": dcn_dtype,
        "ratio": round(flat_dcn / dcn, 2) if dcn else None,
    }


def _quantized_outer_exchange(shard_v, axis, outer_groups, quantizer,
                              layout, r_in, act):
    """The compressed inter-slice (DCN) leg of one bucket's ladder.

    ``shard_v`` is this chip's intra-slice-reduced 1/inner shard. Two
    shapes (see module docstring): at m == 2 slices, all-gather the
    quantized shards + scales and dequant-sum locally; at m > 2, the
    quantized ring decomposition — all-to-all quantized sub-shards,
    dequant-sum, re-quantize, all-gather — so per-chip DCN wire stays
    ~2(m-1)/m of the QUANTIZED shard instead of growing with m.
    ``r_in`` is the bucket's error-feedback residual tuple (or None for
    feedback-free quantization); returns ``(fp32 summed shard,
    [new residuals])`` with residuals in the pre-divide SUM domain.
    """
    from jax import lax as _lax

    from horovod_tpu.utils import timeline as _tl_names

    new_res = []
    v = shard_v.astype(jnp.float32)
    if r_in is not None:
        v = v + r_in[0]
    q, scale = quantizer.quantize(v)
    if r_in is not None:
        new_res.append(v - quantizer.dequantize(q, scale))
    if not layout["two_stage"]:
        with act(_tl_names.ALLGATHER):
            qs = _lax.all_gather(q, axis, axis=0,
                                 axis_index_groups=outer_groups)
            ss = _lax.all_gather(scale.reshape(1), axis, axis=0,
                                 axis_index_groups=outer_groups)
        out = (qs.astype(jnp.float32) * ss).sum(axis=0)
        return out, new_res
    m = layout["m"]
    with act(_tl_names.ALLTOALL):
        recv = _lax.all_to_all(q.reshape(m, -1), axis, split_axis=0,
                               concat_axis=0,
                               axis_index_groups=outer_groups, tiled=True)
        ss = _lax.all_gather(scale.reshape(1), axis, axis=0,
                             axis_index_groups=outer_groups)
    u = (recv.astype(jnp.float32) * ss).sum(axis=0)
    if r_in is not None:
        u = u + r_in[1]
    q2, scale2 = quantizer.quantize(u)
    if r_in is not None:
        new_res.append(u - quantizer.dequantize(q2, scale2))
    with act(_tl_names.ALLGATHER):
        qg = _lax.all_gather(q2, axis, axis=0,
                             axis_index_groups=outer_groups)
        sg = _lax.all_gather(scale2.reshape(1), axis, axis=0,
                             axis_index_groups=outer_groups)
    out = (qg.astype(jnp.float32) * sg).reshape(-1)
    return out, new_res


# The exchange's plan, as gauges keyed by the enclosing program (the
# ``program`` of the ``hvd.spmd.dispatch`` span whose call traced it):
# program -> (id of that span, totals). A second ``fused_reduce`` in the
# same trace adds to the program's totals; a re-trace starts them anew.
_plans: dict = {}


def _record_plan(issued, n: int) -> None:
    """``issued``: ``(collectives, bytes, tensors, packed bytes)`` a bucket,
    as ``_issue`` executed the plan. Sets ``hvd.exchange.calls`` / ``.bytes``
    / ``.tensors`` / ``.buckets`` / ``.packed_bytes``: collectives issued a
    step (one a member on a single slice, the ladder's legs across slices),
    payload bytes a chip hands them (unpadded, after compression), gradient
    tensors, buckets, and the bytes a step copies into flat buffers before a
    collective (0 where every bucket reduces its members in place, equal to
    ``.bytes`` where every bucket takes the ladder). All 0 on one chip, where
    nothing is exchanged. Which path each bucket took is in its ``ALLREDUCE``
    event of the Chrome timeline."""
    from horovod_tpu.utils import timeline

    program, totals = timeline.program_tally(_plans, collections.Counter)
    if n > 1:
        for calls, nbytes, tensors, packed in issued:
            totals.update(calls=calls, bytes=nbytes, tensors=tensors,
                          buckets=1, packed_bytes=packed)
    for what in ("calls", "bytes", "tensors", "buckets", "packed_bytes"):
        timeline.gauge("hvd.exchange." + what, totals[what], key=program)


def fused_reduce(
    tensors,
    average: bool = True,
    compression=Compression.none,
    op=None,
    fusion_threshold: Optional[int] = None,
    name: Optional[str] = None,
    overlap: Optional[str] = None,
    hierarchical: Optional[str] = None,
    residuals=None,
):
    """Allreduce a sequence of tensors, bucket by bucket.

    Returns a list of reduced tensors in input order. Works inside an SPMD
    region (on a single slice a bucket reduces each member in its own shape
    under the bucket's scope; the hierarchical ladder packs the bucket into
    one flat buffer) and eagerly (size()==1 identity semantics).
    ``name`` labels the per-tensor collectives on the eager process-level
    path (where names drive the native negotiation and the timeline); the
    SPMD path has no per-tensor identity inside the compiled program.

    ``overlap`` (auto|on|off, default HOROVOD_OVERLAP) selects the
    emission: reverse bucket order, start-all/unpack-later. Changes
    dispatch shape only — results are bit-identical to ``off``; what an
    all-reduce runs beside is decided where the program is compiled
    (module docstring).

    ``hierarchical`` (auto|on|off, default HOROVOD_HIERARCHICAL) runs
    each Sum/Average bucket as the two-level intra-slice reduce-scatter
    -> inter-slice exchange -> intra-slice all-gather ladder (module
    docstring); with ``Compression.int8``/``.fp8`` the inter-slice leg
    is absmax-quantized, optionally error-corrected by ``residuals``
    (the per-chip state from :func:`ef_residual_specs` — when passed,
    the return value becomes ``(outputs, new_residuals)``).
    """
    from horovod_tpu.jax import mpi_ops

    if op is None:
        op = mpi_ops.Average if average else mpi_ops.Sum

    st = global_state()
    st.require_init()
    if fusion_threshold is None:
        fusion_threshold = st.config.fusion_threshold

    tensors = [jnp.asarray(t) for t in tensors]
    axis = current_spmd_axis()
    if axis is None:
        nproc = st.process_count
        if nproc > 1 and residuals and is_dcn_wire(compression):
            # Same config-drift class as the flat-resolution raise
            # below: EF state exists (init saw an engageable ladder)
            # but the eager lane has no hierarchical path — full-
            # precision bytes would cross the wire while the user
            # believes int8/fp8 EF is active. (Single-process identity
            # passes through: no bytes move at all.)
            raise InvalidArgumentError(
                "error-feedback residuals are present but the multi-"
                "process eager lane has no hierarchical/quantized "
                "exchange — int8/fp8 wire compression requires the "
                "SPMD lane (hvd.spmd_run/spmd_fn); use Compression."
                "fp16/bf16 or none here")
        if nproc == 1:
            out = list(tensors)
        else:
            # Multi-process eager: reduce each via the process-level
            # path (the native core fuses on its own side, so this
            # per-tensor loop is not the per-tensor anti-pattern HVD006
            # flags in user code).
            out = [
                mpi_ops.allreduce(  # hvdlint: disable=HVD006
                    t, average=(op is mpi_ops.Average), op=op,
                    name=f"{name}.{i}" if name else None)
                for i, t in enumerate(tensors)
            ]
        if residuals is not None:  # no DCN leg here: residuals untouched
            return out, tuple(residuals)
        return out

    n = mpi_ops._axis_size(axis)
    # Min/Max/Product bucket just as Sum does: any elementwise cross-rank
    # reduction applies member by member.
    plain_sum = op is mpi_ops.Average or op is mpi_ops.Sum
    if plain_sum:
        reduce_fn = lax.psum
        # HOROVOD_HIERARCHICAL: run each bucket as the explicit
        # two-level ladder (reference operations.cc:1284-1436) —
        # reduce-scatter in the fast (ICI) domain, exchange 1/inner of
        # the bytes across DCN, all-gather back.
        hier = resolve_hierarchical(hierarchical, n)
    else:
        hier = 0
        try:
            reduce_fn = mpi_ops._REDUCE_FNS[op]
        except KeyError:
            raise InvalidArgumentError(f"Unsupported reduction op: {op}")
    quantizer = compression if (hier and is_dcn_wire(compression)) else None
    if residuals and is_dcn_wire(compression) and quantizer is None:
        # The caller initialized error-feedback state for an engaged
        # ladder (ef_residual_specs at init world size), but on THIS
        # axis the ladder resolves to flat — silently skipping the
        # quantized exchange would let the user believe int8/fp8 EF is
        # active while fp32 flows. Config drift, not a degrade case.
        raise InvalidArgumentError(
            "error-feedback residuals are present but the hierarchical "
            f"ladder resolves to FLAT on this {n}-way axis "
            "(HOROVOD_HIERARCHICAL_INNER_SIZE must satisfy 1 < inner "
            f"< {n} and divide it): the optimizer state was initialized "
            "against a different world/axis size — re-init the "
            "optimizer (fusion.ef_residual_specs) for this axis")
    compressed = []
    ctxs = []
    for t in tensors:
        c, ctx = compression.compress(t)
        compressed.append(c)
        ctxs.append(ctx)

    plan = plan_buckets(compressed, fusion_threshold)
    use_overlap = resolve_overlap(overlap, len(plan))

    # Error-feedback residual slots: plan index -> (offset, count) into
    # the ``residuals`` tuple, in plan order (the structure
    # ef_residual_specs promises). Updated residuals land in
    # ``new_residuals`` at the same offsets.
    ef_map = {}
    if hier and quantizer is not None:
        off = 0
        for pi, b in enumerate(plan):
            if not _ef_eligible(b):
                continue
            layout = hier_bucket_layout(
                b.nbytes // jnp.dtype(b.dtype).itemsize, n, hier,
                quantized=True)
            count = 2 if layout["two_stage"] else 1
            ef_map[pi] = (off, count)
            off += count
        if residuals is not None and len(residuals) != off:
            raise InvalidArgumentError(
                f"error-feedback residuals carry {len(residuals)} "
                f"leaves but this plan needs {off} (one per quantized "
                "stage per floating bucket, plan order — rebuild them "
                "with fusion.ef_residual_specs after changing the "
                "fusion threshold, world size or inner size)")
    new_residuals = list(residuals) if residuals is not None else None

    # Per-bucket observability (the SPMD half of the reference's
    # per-tensor activity taxonomy, operations.h:29-50): each bucket's
    # collective is built under a jax.named_scope — the name lands in
    # the HLO metadata, so device profiles (jax.profiler /
    # tools/profile_step.py) attribute its time by name — and, when
    # HOROVOD_TIMELINE is active, emits ALLREDUCE (on the ladder with
    # MEMCPY_IN_FUSION_BUFFER / REDUCESCATTER / the DCN leg's ALLGATHER or
    # ALLTOALL / MEMCPY_OUT_FUSION_BUFFER inside it) spans on a per-bucket
    # track at TRACE time (this code runs once per compile; the spans
    # record the bucket PLAN — members/bytes/dtype/issue order — not
    # per-step device time,
    # which is stated in the span args; per-step device time is the
    # profiler's job, per-step host dispatch is XLA_EXECUTE's). Under
    # overlap the B span opens at ISSUE and closes at UNPACK, so the
    # trace shows every in-flight bucket between its collective start
    # and its unpack.
    import contextlib

    import jax as _jax

    from horovod_tpu.utils import timeline as _tl_names
    from horovod_tpu.utils.timeline import activity as _activity

    tl = getattr(st, "timeline", None)
    emit = tl is not None and tl.enabled

    def _act(track, act_name):
        return (_activity(tl, track, act_name) if emit
                else contextlib.nullcontext())

    results: List = [None] * len(tensors)
    issued: List = []   # (collectives, bytes, tensors, packed bytes) a bucket
    # Members whose averaging division already happened on the ladder's
    # 1/inner shard (before the all-gather) — the tail must not divide
    # them again.
    averaged = [False] * len(tensors)

    def _pack_flat(members, bucket_name):
        """Memcpy-in: ravel+concatenate the bucket members into the
        ladder's flat fusion buffer."""
        with _act(bucket_name, _tl_names.MEMCPY_IN_FUSION_BUFFER):
            return (jnp.concatenate(
                [compressed[i].ravel() for i in members])
                if len(members) > 1
                else compressed[members[0]].ravel())

    def _issue(k, pi, bucket: Bucket):
        """Emit bucket ``bucket``'s collective (k-th in issue order,
        ``pi``-th in the plan); return the unpack closure that splits
        results back out."""
        dtype = jnp.dtype(bucket.dtype)
        bucket_name = f"{name or 'fused'}.{dtype.name}.b{bucket.index}"
        scope = f"hvd_allreduce_{bucket_name}".replace(".", "_")
        members = list(bucket.members)
        hier_q = hier and quantizer is not None and _ef_eligible(bucket)
        if hier:
            path = (f"hier_{jnp.dtype(quantizer.wire_dtype).name}"
                    if hier_q else "hier")
        else:
            path = "psum"
        if emit:
            tl.start(bucket_name, _tl_names.ALLREDUCE,
                     args={"span": "trace", "tensors": len(members),
                           "bytes": int(bucket.nbytes),
                           "overlap": bool(use_overlap), "issue": k,
                           # Sequential emission unpacks each bucket
                           # before issuing the next: never >1 in flight.
                           "in_flight": k + 1 if use_overlap else 1,
                           "path": path,
                           **({"inner": int(hier)} if hier else {})})
        try:
            with _jax.named_scope(scope):
                if hier:
                    flat = _pack_flat(members, bucket_name)
                    size = flat.size
                    layout = hier_bucket_layout(size, n, hier,
                                                quantized=hier_q)
                    pad = layout["padded_elems"] - size
                    if pad:
                        flat = jnp.pad(flat, (0, pad))
                    # The ladder's legs: reduce-scatter, the outer exchange
                    # (one psum; quantized, values and scales gathered, with
                    # an all-to-all and a second gather of both when there
                    # are more than two slices), all-gather.
                    legs = (3 if not hier_q
                            else 6 if layout["two_stage"] else 4)
                    issued.append((legs, int(bucket.nbytes), len(members),
                                   int(bucket.nbytes)))
                    # Average: divide the dequantized/summed 1/inner
                    # shard BEFORE the gather (commutes elementwise —
                    # bit-identical to a tail divide, 1/inner the work);
                    # cast compressors keep the historical tail divide
                    # so hier-off/on share one division sequence.
                    div_on_shard = op is mpi_ops.Average and (
                        hier_q or compression is Compression.none)
                    r_in = None
                    if hier_q and residuals is not None:
                        offr, cnt = ef_map[pi]
                        r_in = tuple(residuals[offr:offr + cnt])
                        want = (layout["shard_elems"],)
                        if tuple(r_in[0].shape) != want:
                            raise InvalidArgumentError(
                                f"error-feedback residual for bucket "
                                f"{bucket_name} arrives with shape "
                                f"{tuple(r_in[0].shape)}, expected the "
                                f"per-chip shard {want}: residual "
                                "leaves are rank-local state and must "
                                "enter the step sharded P(axis) — pass "
                                "the train state through models."
                                "state_partition_specs")

                    def _outer(shard_v, ax, og, _layout=layout,
                               _r=r_in, _div=div_on_shard, _pi=pi,
                               _bn=bucket_name, _hq=hier_q):
                        if _hq:
                            out_s, res_new = _quantized_outer_exchange(
                                shard_v, ax, og, quantizer, _layout, _r,
                                lambda a: _act(_bn, a))
                            if _r is not None:
                                offr, cnt = ef_map[_pi]
                                new_residuals[offr:offr + cnt] = res_new
                        else:
                            out_s = lax.psum(shard_v, ax,
                                             axis_index_groups=og)
                        if _div:
                            out_s = out_s / n
                        return out_s

                    from horovod_tpu.parallel.mesh import (
                        hierarchical_ladder_in_axis,
                    )

                    with _act(bucket_name, _tl_names.REDUCESCATTER):
                        reduced = hierarchical_ladder_in_axis(
                            flat, axis, hier, outer_exchange=_outer)
                    if div_on_shard:
                        for i in members:
                            averaged[i] = True
                    if pad:
                        reduced = reduced[:size]
                else:
                    # The flat path (no ladder): every member reduced
                    # in its own shape, one collective a member (grouping
                    # them on the wire is XLA's all-reduce combiner's) —
                    # no gradient byte is copied into a flat buffer.
                    issued.append((len(members), int(bucket.nbytes),
                                   len(members), 0))
                    reduced = reduce_fn(
                        tuple(compressed[i] for i in members), axis)
        except Exception:
            if emit:
                tl.end(bucket_name, _tl_names.ALLREDUCE)
            raise

        def _unpack():
            try:
                if not hier:
                    for i, r in zip(members, reduced):
                        results[i] = r
                    return
                with _jax.named_scope(scope), _act(
                        bucket_name, _tl_names.MEMCPY_OUT_FUSION_BUFFER):
                    offset = 0
                    for i in members:
                        sz = compressed[i].size
                        results[i] = reduced[offset:offset + sz].reshape(
                            compressed[i].shape)
                        offset += sz
            finally:
                if emit:
                    tl.end(bucket_name, _tl_names.ALLREDUCE)

        return _unpack

    if use_overlap:
        # Reverse bucket order (autodiff produces the LAST layers'
        # gradients first; the plan follows the pytree's order of leaves,
        # which is the layers' only up to nine of a name): issue every
        # collective, unpack afterwards in forward order, so that no
        # collective waits for another's unpack. Where each then runs is
        # the compiler's scheduler's, under the options hvd.spmd_fn
        # compiles the program with (module docstring).
        unpacks = [None] * len(plan)
        for k, bi in enumerate(reversed(range(len(plan)))):
            unpacks[bi] = _issue(k, bi, plan[bi])
        for unpack in unpacks:
            unpack()
    else:
        for k, bucket in enumerate(plan):
            _issue(k, k, bucket)()
    _record_plan(issued, n)

    out = []
    for i, t in enumerate(tensors):
        r = compression.decompress(results[i], ctxs[i])
        if op is mpi_ops.Average and not averaged[i]:
            r = r / n
        out.append(r.astype(t.dtype) if r.dtype != t.dtype else r)
    if residuals is not None:
        return out, tuple(new_residuals)
    return out
