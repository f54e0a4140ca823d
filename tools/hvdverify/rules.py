"""The hvdverify rule catalogue: IR-level checks over a traced program's
collective schedule.

hvdlint (tools/hvdlint) catches these bug classes SYNTACTICALLY; the
repo's riskiest programs are *traced* — ``lax.cond`` branches, scanned
windows, overlap's reverse-order bucket schedules — where AST rules are
blind. hvdverify re-decides the native coordinator's runtime mismatch
checks (csrc/coordinator.cc: op/dtype/root/shape/ragged) at trace time,
over the jaxpr.

Rules HVV101-HVV104 are emitted during the schedule walk
(tools/hvdverify/schedule.py); HVV105 runs after, reconciling the
schedule's byte accounting against the bucket plan
(:func:`horovod_tpu.jax.fusion.plan_buckets`) the program claims to
execute. ``RULES`` maps rule id -> one-line doc (the --list-rules
catalogue; the long-form catalogue lives in docs/static_analysis.md).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence

from horovod_tpu.parallel.logical import DATA_AXIS
from tools.hvdverify.schedule import CollectiveOp, RawFinding

RULES: Dict[str, str] = {
    "HVV101": "collective present in only some branches of rank-divergent "
              "control flow (cond/while on axis_index) -> deadlock; the "
              "IR-level generalization of HVD002",
    "HVV102": "collective over an axis name not bound by the enclosing "
              "mesh/shard_map (caught at trace or in the walked IR)",
    "HVV103": "rank-divergent branches submit collective schedules that "
              "disagree in op/order/shape/dtype/params — the "
              "coordinator's five runtime mismatch checks, decided "
              "statically",
    "HVV104": "donated buffer referenced after the donating call "
              "(IR-level HVD003), or donation where a program forbids it "
              "(the elastic no-donation-while-snapshot-in-flight "
              "invariant)",
    "HVV105": "static wire-byte accounting does not reconcile with the "
              "declared fusion bucket plan "
              "(horovod_tpu.jax.fusion.plan_buckets; a psum a member "
              "under the bucket's scope, or the hierarchical "
              "rs->exchange->ag ladder incl. quantized DCN legs)",
    "HVV201": "declared in/out/param partition specs do not reconcile "
              "with the LogicalMesh axis-rules table — the sharding "
              "analogue of HVV105's byte reconciliation",
    "HVV202": "collective or with_sharding_constraint references a "
              "physical mesh axis the bound LogicalMesh does not "
              "define (vocabulary drift past the rules table)",
    "HVV203": "composed-stack collective schedule is not op-identical "
              "to the per-module reference trace (kind/axes/shape/"
              "dtype/params, in issue order)",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One verified-program finding (the hvdverify analogue of
    hvdlint's Finding; programs are keyed by registry name, not file)."""

    program: str
    rule: str
    message: str
    path: str = ""
    source: str = ""
    suppressed: bool = False
    suppress_reason: str = ""

    def format(self) -> str:
        tag = (f" (suppressed: {self.suppress_reason})"
               if self.suppressed else "")
        src = f" [{self.source}]" if self.source else ""
        return (f"{self.program}: {self.rule} {self.message}"
                f" @ {self.path}{src}{tag}")


def from_raw(program: str, raw: RawFinding) -> Finding:
    return Finding(program=program, rule=raw.rule, message=raw.message,
                   path=raw.path, source=raw.source)


# ------------------------------------------------------------------ HVV105


@dataclasses.dataclass
class ReconcileSpec:
    """What a program claims its fused gradient exchange moves.

    ``leaves``: the gradient leaves (arrays or ShapeDtypeStructs) the
    bucketed exchange reduces; ``threshold``: the fusion threshold the
    plan was built with; ``axis_size``: the collective axis size (the
    ladder pads its flat buckets by it and ``hier_inner``).

    ``hier_inner`` declares the hierarchical ladder (HOROVOD_
    HIERARCHICAL, fusion.py): each bucket must decompose into
    intra-slice reduce-scatter -> inter-slice exchange of the
    1/inner shard -> intra-slice all-gather. ``dcn_dtype`` (e.g.
    "int8"/"float8_e4m3fn") additionally declares the low-bit DCN
    wire: floating buckets' inter-slice leg must be the quantized
    exchange (payload + scalar scale all-gathers; the two-stage
    all-to-all shape at >2 slices) instead of a shard psum.
    """

    leaves: Sequence
    threshold: int
    axis_size: int
    axis: str = DATA_AXIS
    hier_inner: int = 0
    dcn_dtype: Optional[str] = None


def check_reconciliation(program: str, schedule: Sequence[CollectiveOp],
                         spec: ReconcileSpec) -> List[Finding]:
    """HVV105: the traced schedule's gradient-exchange collectives must
    carry EXACTLY the bytes of the bucket plan the program claims.

    Matching contract (per bucket of ``plan_buckets(leaves, threshold)``):

    * the ``psum`` entries under that bucket's
      ``hvd_allreduce_<name>_<dtype>_b<i>`` scope, whose payloads must sum
      to the bucket's bytes (the flat path: every member reduced in its
      own shape, one entry a member); where the program tags nothing, one
      ``psum`` entry of the bucket's bytes (a hand-rolled flat bucket), or
    * when ``spec.hier_inner`` is set, the hierarchical rs->exchange->ag
      decomposition (fusion.hier_bucket_layout — the SAME layout the
      executing path computes): a ``psum_scatter`` of the
      inner-padded bucket, the inter-slice leg (a shard ``psum``, or
      under ``spec.dcn_dtype`` the quantized payload + scale
      all-gathers / two-stage all-to-all), and the intra-slice
      ``all_gather`` of the shard — any missing or mis-sized leg is a
      finding, and a bucket traced as one FLAT full-bytes psum under a
      declared ladder is a finding too (a ladder that silently never
      engaged must not keep the sweep green).

    Entries are pre-filtered to the fusion data plane: collectives whose
    jax name_stack carries the ``hvd_allreduce`` scope fusion.py wraps
    every bucket in. When no tagged entry exists (a hand-rolled
    exchange), every reduce-type collective over the spec's axis is
    considered instead — so a per-tensor exchange that bypasses fusion
    reconciles only if it happens to move the same flat buckets.
    Leftover entries or unmatched buckets are findings.
    """
    import numpy as np

    from horovod_tpu.jax.fusion import plan_buckets

    plan = plan_buckets(list(spec.leaves), spec.threshold)
    exchange_kinds = ("psum", "psum2", "reduce_scatter", "psum_scatter",
                      "all_gather", "all_to_all")
    tagged = [op for op in schedule if "hvd_allreduce" in op.name_stack
              and spec.axis in op.axes]
    used_tag_filter = bool(tagged)
    if not tagged:
        tagged = [op for op in schedule
                  if op.kind in exchange_kinds and spec.axis in op.axes]
    findings: List[Finding] = []
    # The tag filter keeps metric psums (loss means etc.) out of the
    # reconciliation — but a HAND-ROLLED collective on the gradient
    # axis moving a gradient-sized payload is exactly the per-tensor
    # bypass this rule exists to catch, tagged exchange present or not.
    if used_tag_filter:
        pooled = {id(op) for op in tagged}
        grad_sizes = {b.nbytes for b in plan}
        for leaf in spec.leaves:
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = getattr(leaf, "dtype", None)
            if dtype is not None:
                grad_sizes.add(
                    int(np.prod(shape, dtype=np.int64))
                    * np.dtype(dtype).itemsize)
        for op in schedule:
            if (id(op) not in pooled and op.kind in exchange_kinds
                    and spec.axis in op.axes
                    and op.payload_bytes in grad_sizes):
                findings.append(Finding(
                    program, "HVV105",
                    f"schedule entry {op.describe()} moves a "
                    "gradient-sized payload on the gradient axis "
                    "OUTSIDE the tagged fused exchange: a hand-rolled "
                    "per-tensor collective bypassing the bucket plan",
                    op.path, op.source))
    # Pool entries by kind; match buckets greedily by exact byte size.
    reduces = [op for op in tagged
               if op.kind in ("psum", "psum2")]
    scatters = [op for op in tagged
                if op.kind in ("reduce_scatter", "psum_scatter")]
    gathers = [op for op in tagged if op.kind == "all_gather"]
    a2as = [op for op in tagged if op.kind == "all_to_all"]

    def _take(pool, nbytes):
        for i, op in enumerate(pool):
            if op.payload_bytes == nbytes:
                return pool.pop(i)
        return None

    def _take_flat(bucket) -> bool:
        """The flat path's form of ``bucket``: the psum entries under its
        own scope, summing to its bytes (untagged: one entry of them)."""
        if not used_tag_filter:
            return _take(reduces, bucket.nbytes) is not None
        scope = re.compile(
            rf"hvd_allreduce_\w*_{bucket.dtype}_b{bucket.index}(/|$)")
        mine = [op for op in reduces if scope.search(op.name_stack)]
        if not mine or sum(op.payload_bytes for op in mine) != bucket.nbytes:
            return False
        for op in mine:
            reduces.remove(op)
        return True

    def _match_hier(bucket, itemsize) -> Optional[List[str]]:
        """Try the hierarchical decomposition for ``bucket``: returns
        None when the intra-slice reduce-scatter itself is absent (the
        bucket may match another form), else the list of missing/
        mis-sized legs (empty = fully reconciled)."""
        import jax.numpy as jnp

        from horovod_tpu.jax.fusion import hier_bucket_layout

        quantized = (spec.dcn_dtype is not None
                     and np.issubdtype(np.dtype(bucket.dtype),
                                       np.floating))
        layout = hier_bucket_layout(
            bucket.nbytes // itemsize, spec.axis_size, spec.hier_inner,
            quantized=quantized)
        if _take(scatters, layout["padded_elems"] * itemsize) is None:
            return None
        shard_e = layout["shard_elems"]
        missing: List[str] = []
        if quantized:
            wire_isz = jnp.dtype(spec.dcn_dtype).itemsize
            if layout["two_stage"]:
                if _take(a2as, shard_e * wire_isz) is None:
                    missing.append(
                        f"{shard_e * wire_isz} B quantized "
                        f"({spec.dcn_dtype}) inter-slice all-to-all")
                if _take(gathers,
                         layout["sub_elems"] * wire_isz) is None:
                    missing.append(
                        f"{layout['sub_elems'] * wire_isz} B quantized "
                        "sub-shard all-gather")
                scale_count = 2
            else:
                if _take(gathers, shard_e * wire_isz) is None:
                    missing.append(
                        f"{shard_e * wire_isz} B quantized "
                        f"({spec.dcn_dtype}) shard all-gather")
                scale_count = 1
            for _ in range(scale_count):
                if _take(gathers, 4) is None:
                    missing.append("4 B scale all-gather")
            ag_bytes = shard_e * 4  # dequant-summed in fp32
        else:
            if _take(reduces, shard_e * itemsize) is None:
                missing.append(
                    f"{shard_e * itemsize} B inter-slice (DCN) shard "
                    "psum")
            ag_bytes = shard_e * itemsize
        if _take(gathers, ag_bytes) is None:
            missing.append(
                f"{ag_bytes} B intra-slice all-gather of the shard")
        return missing

    for bucket in plan:
        itemsize = np.dtype(bucket.dtype).itemsize
        if spec.hier_inner:
            # Declared ladder: try the three-leg decomposition FIRST —
            # and refuse to let a flat full-bytes psum reconcile
            # quietly, or a regression that stops the ladder engaging
            # (config drift, a lost inner-size pin) would keep the
            # sweep green while the 1/inner DCN-bytes property is gone.
            missing = _match_hier(bucket, itemsize)
            if missing is not None:
                for leg in missing:
                    findings.append(Finding(
                        program, "HVV105",
                        f"bucket {bucket.dtype}.b{bucket.index} "
                        f"({bucket.nbytes} B) reduce-scatters on the "
                        f"hierarchical ladder (inner "
                        f"{spec.hier_inner}) but its {leg} is missing "
                        "or mis-sized — the ladder must run rs -> "
                        "inter-slice exchange -> ag per bucket "
                        "(fusion.py hierarchical contract)"))
                continue
            if _take_flat(bucket):
                findings.append(Finding(
                    program, "HVV105",
                    f"bucket {bucket.dtype}.b{bucket.index} "
                    f"({bucket.nbytes} B) traced as the FLAT psum form "
                    f"while the plan declares the inner-"
                    f"{spec.hier_inner} hierarchical ladder: the "
                    "ladder silently did not engage, and the "
                    "inter-slice leg carries inner x the bytes the "
                    "program promises (resolve_hierarchical config "
                    "drift)"))
                continue
        elif _take_flat(bucket):
            continue
        findings.append(Finding(
            program, "HVV105",
            f"bucket {bucket.dtype}.b{bucket.index} of the declared "
            f"plan ({len(bucket.members)} tensor(s), {bucket.nbytes} B "
            f"at threshold {spec.threshold}) has NO matching collective "
            "in the traced schedule: the program does not execute the "
            "bucket plan it claims (plan_buckets would account bytes "
            "the wire never moves)"))
    for op in reduces + scatters + gathers + a2as:
        findings.append(Finding(
            program, "HVV105",
            f"schedule entry {op.describe()} matches NO bucket of the "
            f"declared plan ({len(plan)} bucket(s) at threshold "
            f"{spec.threshold}): unplanned traffic — a per-tensor "
            "exchange, a gather without its reduce-scatter, or a "
            "foreign collective on the gradient axis",
            op.path, op.source))
    return findings


# ------------------------------------------------------------------ HVV201


@dataclasses.dataclass
class ShardingSpec:
    """What a program claims about its shardings, against the rules
    table: ``mesh`` is the bound
    :class:`~horovod_tpu.parallel.logical.LogicalMesh`; ``entries`` is
    one ``(label, logical_dims, declared_spec)`` triple per sharded
    argument/output/param group — ``logical_dims`` the logical axis
    names per array dimension (``None`` = replicated dim) and
    ``declared_spec`` the ``PartitionSpec`` the program actually passes
    to ``in_specs``/``out_specs``/``with_sharding_constraint``. HVV201
    resolves ``logical_dims`` through the table and compares."""

    mesh: object
    entries: Sequence


def _norm_spec(spec) -> tuple:
    """PartitionSpec -> trailing-None-stripped tuple (``P('dp')`` and
    ``P('dp', None)`` shard identically)."""
    t = tuple(spec) if spec is not None else ()
    while t and t[-1] is None:
        t = t[:-1]
    return t


def check_shardings(program: str, spec: ShardingSpec) -> List[Finding]:
    """HVV201: every declared partition spec must equal what the
    axis-rules table resolves for the claimed logical dims. A declared
    spec spelling a different physical axis (or sharding a dim the
    table replicates, or vice versa) is a finding — the program's
    sharding drifted from the registry that is supposed to own it."""
    findings: List[Finding] = []
    for label, dims, declared in spec.entries:
        try:
            expected = spec.mesh.spec(*dims)
        except Exception as e:
            findings.append(Finding(
                program, "HVV201",
                f"sharding entry '{label}' claims logical dims "
                f"{tuple(dims)!r} the rules table cannot resolve: {e}"))
            continue
        if _norm_spec(declared) != _norm_spec(expected):
            findings.append(Finding(
                program, "HVV201",
                f"sharding entry '{label}': declared spec "
                f"{tuple(declared)!r} but the axis-rules table resolves "
                f"logical dims {tuple(dims)!r} to {tuple(expected)!r} "
                f"on mesh '{spec.mesh.config}' — the program's sharding "
                "drifted from the table (the sharding analogue of an "
                "HVV105 byte mismatch)"))
    return findings


# ------------------------------------------------------------------ HVV202


def check_axis_vocabulary(program: str, schedule: Sequence[CollectiveOp],
                          constraint_refs: Sequence,
                          logical_mesh) -> List[Finding]:
    """HVV202: every mesh axis a collective runs over — and every axis a
    ``with_sharding_constraint`` spells — must be defined by the bound
    LogicalMesh. An undefined axis means the program smuggled a physical
    spelling past the rules table (it may still trace if an enclosing
    shard_map binds the axis, which is exactly why HVV102 cannot catch
    this class)."""
    defined = set(logical_mesh.axis_names)
    findings: List[Finding] = []
    for op in schedule:
        for ax in op.axes:
            if ax not in defined:
                findings.append(Finding(
                    program, "HVV202",
                    f"collective {op.describe()} runs over mesh axis "
                    f"'{ax}' which the bound LogicalMesh "
                    f"('{logical_mesh.config}') does not define — the "
                    "axis spelling bypassed the rules table",
                    op.path, op.source))
    for axes, path, source in constraint_refs:
        for ax in axes:
            if ax not in defined:
                findings.append(Finding(
                    program, "HVV202",
                    f"with_sharding_constraint references mesh axis "
                    f"'{ax}' which the bound LogicalMesh "
                    f"('{logical_mesh.config}') does not define",
                    path, source))
    return findings


# ------------------------------------------------------------------ HVV203


@dataclasses.dataclass
class EquivalenceSpec:
    """One per-module reference a composed program must reproduce.

    ``reference``: zero-arg callable returning ``(fn, args)`` — the
    single-strategy program whose collective schedule is ground truth
    (built at the composed program's LOCAL shapes, i.e. with the other
    strategies' axes already divided out). ``axes``: the composed
    program's physical axes this reference owns (its collectives are
    filtered to ops touching them). ``axis_map``: composed -> reference
    axis renames (e.g. ``{"dp": "hvd"}`` when the reference spells the
    data axis the legacy way)."""

    reference: object
    axes: Sequence[str]
    axis_map: Dict[str, str] = dataclasses.field(default_factory=dict)
    name: str = "reference"


def _op_key(op: CollectiveOp, rename: Dict[str, str]) -> tuple:
    axes = tuple(rename.get(a, a) for a in op.axes)
    return (op.kind, axes, tuple(op.shape), op.dtype, op.times, op.params)


def check_equivalence(program: str, schedule: Sequence[CollectiveOp],
                      specs: Sequence[EquivalenceSpec]) -> List[Finding]:
    """HVV203: per reference, the composed program's collectives over
    that reference's axes must be OP-IDENTICAL — same kinds, axes
    (after renaming), shapes, dtypes, static multipliers and params, in
    the same issue order — to the reference's own trace. Composition
    through the rules table must not change what any single strategy
    puts on the wire."""
    import warnings

    import jax

    from tools.hvdverify.schedule import extract

    findings: List[Finding] = []
    for spec in specs:
        fn, args = spec.reference()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            closed = jax.make_jaxpr(fn)(*args)
        ref_schedule, _, _ = extract(closed)
        owned = set(spec.axes)
        mapped = {spec.axis_map.get(a, a) for a in spec.axes}
        composed = [op for op in schedule if set(op.axes) & owned]
        ref_ops = [op for op in ref_schedule if set(op.axes) & mapped]
        got = [_op_key(op, spec.axis_map) for op in composed]
        want = [_op_key(op, {}) for op in ref_ops]
        if got == want:
            continue
        if len(got) != len(want):
            findings.append(Finding(
                program, "HVV203",
                f"composed schedule has {len(got)} collective(s) over "
                f"axes {sorted(owned)} but reference "
                f"'{spec.name}' traces {len(want)} — composition "
                "changed what the strategy puts on the wire"))
            continue
        for i, (g, w) in enumerate(zip(got, want)):
            if g != w:
                g_op = composed[i]
                findings.append(Finding(
                    program, "HVV203",
                    f"composed schedule diverges from reference "
                    f"'{spec.name}' at op #{i}: composed "
                    f"{g_op.describe()} (key {g!r}) vs reference "
                    f"{ref_ops[i].describe()} (key {w!r}) — the stack "
                    "must be op-identical to the per-module trace",
                    g_op.path, g_op.source))
                break
    return findings
