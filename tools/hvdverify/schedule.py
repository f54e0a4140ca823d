"""Collective-schedule extraction from closed jaxprs.

The native coordinator validates collective consistency at RUNTIME: five
mismatch checks (op/dtype/root/shape/ragged, ``csrc/coordinator.cc``)
fire mid-negotiation, and a rank-divergent collective simply deadlocks
the job. Under XLA the whole rank program is one traced artifact, so the
same questions are decidable at TRACE time: this module walks a closed
jaxpr recursively through every higher-order primitive
(``jit``/``scan``/``while``/``cond``/``shard_map``/``custom_vjp``/
``remat``) and extracts the **collective schedule** — op kind, axis
names, shapes, dtypes, issue order, and payload bytes per collective —
plus the walk-local facts the HVV rules need:

* a **rank-taint** analysis (which values derive from ``axis_index``)
  so a ``cond``/``while`` conditioned on rank is recognized as
  rank-divergent control flow;
* per-branch sub-schedules of every rank-divergent ``cond`` (HVV101 /
  HVV103 compare them the way the coordinator compared per-rank
  submissions);
* the set of mesh-bound axis names in scope (HVV102);
* donation dataflow: ``donated_invars`` positions of each call eqn vs
  later reads of the same variable (HVV104).

Issue order is trace order — the order XLA sees the collectives, which
for one SPMD program IS the negotiation order the reference coordinated
at runtime. Collectives nested under ``scan`` carry a static execution
multiplier (the product of enclosing scan lengths); under ``while`` the
trip count is unknown and the multiplier is ``None``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

#: Collective primitives recognized in jaxprs. "psum2" is the renamed
#: psum on newer jax; both spellings are kept so the walker survives
#: version drift (same contract as tests/test_wire_bytes.py).
COLLECTIVE_PRIMS = {
    "psum", "psum2", "pmin", "pmax", "all_gather", "reduce_scatter",
    "psum_scatter", "all_to_all", "ppermute", "pbroadcast",
}

#: Reduce-type collectives (the ones bucket fusion amortizes).
REDUCE_PRIMS = {"psum", "psum2", "pmin", "pmax", "reduce_scatter",
                "psum_scatter"}


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in a program's static schedule."""

    kind: str                 # primitive name, e.g. "psum"
    axes: Tuple[str, ...]     # mesh axis names the collective runs over
    shape: Tuple[int, ...]    # operand shape (first array operand)
    dtype: str                # operand dtype name
    payload_bytes: int        # sum of array-operand bytes (one execution)
    index: int                # issue order within the traced program
    path: str                 # higher-order context, e.g. "jit:step/scan"
    times: Optional[int]      # static execution count (None: unknown —
                              # nested under a while loop)
    name_stack: str           # jax named_scope stack (fusion tags buckets
                              # "hvd_allreduce_*"; HVV105 filters on it)
    params: Tuple = ()        # stable signature of the collective's
                              # remaining params (groups/perm/dims) —
                              # the "root" part of the mismatch checks
    source: str = ""          # user-code source line, when available

    def describe(self) -> str:
        mult = "" if self.times == 1 else (
            f" x{self.times}" if self.times is not None else " x?")
        return (f"#{self.index} {self.kind}[{','.join(self.axes)}] "
                f"{self.dtype}{list(self.shape)}"
                f" ({self.payload_bytes} B){mult} @ {self.path}")


@dataclasses.dataclass(frozen=True)
class RawFinding:
    rule: str
    message: str
    path: str
    source: str = ""


def _axes_of(eqn) -> Tuple[str, ...]:
    """Mesh axis names a collective eqn runs over (strings only —
    positional sub-axes of vmapped collectives are not mesh axes)."""
    params = eqn.params
    axes = params.get("axes", params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _params_signature(eqn) -> Tuple:
    """The non-shape params of a collective that must agree across ranks
    (the coordinator's "root rank" class of mismatch): permutation,
    index groups, gather/scatter dimensions."""
    sig = []
    for key in ("axis_index_groups", "perm", "all_gather_dimension",
                "scatter_dimension", "split_axis", "concat_axis",
                "tiled", "axis_size"):
        if key in eqn.params:
            val = eqn.params[key]
            if isinstance(val, list):
                val = tuple(tuple(v) if isinstance(v, list) else v
                            for v in val)
            sig.append((key, val))
    return tuple(sig)


def _source_of(eqn) -> str:
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return ""
        return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        return ""


def _is_var(v) -> bool:
    # Literals carry .val; Vars do not. Works across jax versions without
    # importing private classes.
    return not hasattr(v, "val")


def _array_bytes(aval) -> int:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * dtype.itemsize


def _open(jaxpr_like):
    """Normalize ClosedJaxpr / Jaxpr to the open Jaxpr."""
    return getattr(jaxpr_like, "jaxpr", jaxpr_like)


def _reads_axis_index(jaxpr_like, _depth: int = 0) -> bool:
    """True when ``axis_index`` appears anywhere in the (recursively
    opened) jaxpr — how rank-taint is detected through sub-jaxprs whose
    internals are not walked eqn-by-eqn (``_taint_only``)."""
    if _depth > 32:
        return False
    jaxpr = _open(jaxpr_like)
    for eqn in getattr(jaxpr, "eqns", ()):
        if eqn.primitive.name == "axis_index":
            return True
        for val in eqn.params.values():
            for item in (val if isinstance(val, (tuple, list)) else [val]):
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    if _reads_axis_index(item, _depth + 1):
                        return True
    return False


def _align_taint(outer_invars, inner_invars, tainted: Set) -> Set:
    """Taint for a sub-jaxpr's invars: align outer call operands to inner
    binders from the END (every higher-order primitive here passes its
    constants first, so tail alignment pairs the data operands)."""
    inner = set()
    for outer, binder in zip(reversed(list(outer_invars)),
                             reversed(list(inner_invars))):
        if _is_var(outer) and outer in tainted:
            inner.add(binder)
    return inner


class ScheduleWalker:
    """Recursive jaxpr walk producing (schedule, findings)."""

    def __init__(self):
        self.schedule: List[CollectiveOp] = []
        self.findings: List[RawFinding] = []
        #: Every call eqn carrying a True donated_invars entry —
        #: (name, path, source). The elastic no-donation-while-snapshot
        #: invariant (core.verify forbid_donation) consumes this.
        self.donating_calls: List[Tuple[str, str, str]] = []
        self._counter = 0

    # -------------------------------------------------------------- taint

    def _taint_flow(self, jaxpr, tainted: Set) -> Tuple[bool, Set]:
        """Propagate rank-taint through ``jaxpr`` without recording
        collectives. Taint is born at ``axis_index`` — inline or inside
        any nested sub-jaxpr (a rank computed by a jitted/remat helper
        is just as rank-derived as an inline one). Returns
        ``(saw_axis_index, final tainted set)``."""
        tainted = set(tainted)
        saw_axis_index = False
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "axis_index":
                saw_axis_index = True
                tainted.update(eqn.outvars)
                continue
            if any(_reads_axis_index(item)
                   for val in eqn.params.values()
                   for item in (val if isinstance(val, (tuple, list))
                                else [val])
                   if hasattr(item, "eqns") or hasattr(item, "jaxpr")):
                tainted.update(eqn.outvars)
                continue
            if any(_is_var(v) and v in tainted for v in eqn.invars):
                tainted.update(eqn.outvars)
        return saw_axis_index, tainted

    def _taint_only(self, jaxpr, tainted: Set) -> bool:
        """True when ``jaxpr``'s output is rank-derived: any outvar ends
        tainted, or the body reads ``axis_index`` directly (used to
        decide whether a while cond output is rank-derived)."""
        saw_axis_index, final = self._taint_flow(jaxpr, tainted)
        out_tainted = any(_is_var(v) and v in final
                          for v in jaxpr.outvars)
        return saw_axis_index or out_tainted

    # --------------------------------------------------------------- walk

    def walk(self, jaxpr_like, *, path: str = "", bound_axes=frozenset(),
             tainted: Optional[Set] = None, mult: Optional[int] = 1):
        jaxpr = _open(jaxpr_like)
        # The taint set is mutated IN PLACE so a caller that hands us a
        # sub-jaxpr's binder taint (_descend) can read back which inner
        # vars ended rank-derived and lift that onto the call's outvars.
        if tainted is None:
            tainted = set()

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name

            if prim == "axis_index":
                tainted.update(eqn.outvars)
                continue

            if prim in COLLECTIVE_PRIMS:
                self._record(eqn, path, bound_axes, mult)

            elif prim == "cond":
                self._walk_cond(eqn, path, bound_axes, tainted, mult)

            elif prim == "while":
                self._walk_while(eqn, path, bound_axes, tainted, mult)

            elif prim == "scan":
                body = eqn.params["jaxpr"]
                length = int(eqn.params.get("length", 1))
                inner_mult = None if mult is None else mult * length
                self._descend(
                    body, eqn, f"{path}/scan[x{length}]", bound_axes,
                    tainted, inner_mult)

            elif prim == "shard_map":
                mesh = eqn.params.get("mesh")
                names = tuple(getattr(mesh, "axis_names", ()) or ())
                self._descend(
                    eqn.params["jaxpr"], eqn, f"{path}/shard_map",
                    frozenset(bound_axes) | set(names), tainted, mult)

            elif prim in ("custom_jvp_call", "custom_vjp_call"):
                body = eqn.params.get("call_jaxpr")
                if body is not None:
                    self._descend(body, eqn, f"{path}/{prim}", bound_axes,
                                  tainted, mult)

            elif prim in ("jit", "closed_call", "call", "remat2"):
                body = eqn.params.get("jaxpr",
                                      eqn.params.get("call_jaxpr"))
                if body is not None:
                    name = eqn.params.get("name", prim)
                    self._descend(body, eqn, f"{path}/{prim}:{name}",
                                  bound_axes, tainted, mult)
                self._check_donation(eqn, jaxpr, path)

            else:
                # Unknown higher-order primitive: still descend into any
                # jaxpr-shaped params so collectives cannot hide (the
                # same never-skip rule as tests/test_wire_bytes.py).
                for val in eqn.params.values():
                    for item in (val if isinstance(val, (tuple, list))
                                 else [val]):
                        if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                            self._descend(item, eqn, f"{path}/{prim}",
                                          bound_axes, tainted, mult)

            # Taint propagation for the current eqn.
            if any(_is_var(v) and v in tainted for v in eqn.invars):
                tainted.update(eqn.outvars)

        return self

    def _descend(self, body, eqn, path, bound_axes, tainted, mult):
        inner_taint = _align_taint(eqn.invars, _open(body).invars, tainted)
        self.walk(body, path=path, bound_axes=bound_axes,
                  tainted=inner_taint, mult=mult)
        # Taint born INSIDE the sub-jaxpr (axis_index under a nested
        # jit/remat/scan) must surface, or a cond on the call's result
        # is misclassified as uniform: align inner outvars to the call's
        # outvars from the end and lift.
        for outer, inner in zip(reversed(list(eqn.outvars)),
                                reversed(list(_open(body).outvars))):
            if _is_var(inner) and inner in inner_taint:
                tainted.add(outer)

    def _record(self, eqn, path, bound_axes, mult):
        arrays = [v.aval for v in eqn.invars if hasattr(v.aval, "shape")]
        shape = tuple(arrays[0].shape) if arrays else ()
        dtype = arrays[0].dtype.name if arrays else "?"
        axes = _axes_of(eqn)
        op = CollectiveOp(
            kind=eqn.primitive.name,
            axes=axes,
            shape=shape,
            dtype=dtype,
            payload_bytes=sum(_array_bytes(a) for a in arrays),
            index=self._counter,
            path=path or "<top>",
            times=mult,
            name_stack=str(getattr(eqn.source_info, "name_stack", "")),
            params=_params_signature(eqn),
            source=_source_of(eqn),
        )
        self._counter += 1
        self.schedule.append(op)
        unbound = [a for a in axes if a not in bound_axes]
        if unbound:
            self.findings.append(RawFinding(
                "HVV102",
                f"collective '{op.kind}' over axis "
                f"{'/'.join(unbound)!s} not bound by any enclosing "
                f"mesh/shard_map (in scope: "
                f"{sorted(bound_axes) or 'none'})",
                op.path, op.source))

    # ------------------------------------------------------------ control

    def _branch_schedule(self, branch, eqn, path, bound_axes, tainted,
                         mult, tag):
        """Walk one cond branch with a sub-walker; merge its schedule and
        findings into this one (issue indices stay globally ordered) and
        return the branch's own collective sequence for comparison."""
        sub = ScheduleWalker()
        sub._counter = self._counter
        inner_taint = _align_taint(
            eqn.invars[1:], _open(branch).invars, tainted)
        sub.walk(branch, path=f"{path}/{tag}", bound_axes=bound_axes,
                 tainted=inner_taint, mult=mult)
        self._counter = sub._counter
        self.schedule.extend(sub.schedule)
        self.findings.extend(sub.findings)
        self.donating_calls.extend(sub.donating_calls)
        for outer, inner in zip(reversed(list(eqn.outvars)),
                                reversed(list(_open(branch).outvars))):
            if _is_var(inner) and inner in inner_taint:
                tainted.add(outer)
        return sub.schedule

    def _walk_cond(self, eqn, path, bound_axes, tainted, mult):
        pred = eqn.invars[0]
        divergent = _is_var(pred) and pred in tainted
        where = _source_of(eqn)
        branches = eqn.params["branches"]
        cond_tag = f"cond@{self._counter}"
        scheds = [
            self._branch_schedule(
                b, eqn, path, bound_axes, tainted,
                # Divergent predicate: which branch (and so how often a
                # branch collective) runs is rank-dependent -> unknown
                # count. Uniform predicate: every rank takes the SAME
                # branch, so each branch op keeps the enclosing
                # multiplier — a static worst case, since mutually
                # exclusive branches are both counted (summarize() is an
                # upper bound there, exact everywhere the sweep
                # reconciles: the HVV105 programs are cond-free).
                None if divergent else mult, f"{cond_tag}.br{i}")
            for i, b in enumerate(branches)
        ]
        if not divergent:
            return
        sigs = [[(op.kind, op.axes, op.shape, op.dtype, op.params)
                 for op in s] for s in scheds]
        counts = [len(s) for s in sigs]
        if len(set(counts)) > 1:
            detail = ", ".join(
                f"branch {i}: {c} collective(s)"
                for i, c in enumerate(counts))
            ops = next(s for s in scheds if s)
            self.findings.append(RawFinding(
                "HVV101",
                "collective under RANK-DIVERGENT control flow: a "
                "cond whose predicate derives from axis_index issues "
                f"'{ops[0].kind}' in only some branches ({detail}); "
                "ranks taking the collective-free branch never join "
                "-> deadlock (the coordinator's missing-rank stall, "
                "decided at trace time)",
                f"{path}/{cond_tag}", where))
            return
        for i, sig in enumerate(sigs[1:], start=1):
            for k, (a, b) in enumerate(zip(sigs[0], sig)):
                if a != b:
                    mismatch = next(
                        name for name, x, y in zip(
                            ("op", "axes", "shape", "dtype",
                             "params(root/groups)"),
                            a, b) if x != y)
                    self.findings.append(RawFinding(
                        "HVV103",
                        "rank-divergent branches submit MISMATCHED "
                        f"collective schedules: position {k} is "
                        f"{a[0]}{list(a[2])}:{a[3]} in branch 0 but "
                        f"{b[0]}{list(b[2])}:{b[3]} in branch {i} "
                        f"({mismatch} mismatch) — the coordinator's "
                        "runtime mismatch validation, decided at "
                        "trace time",
                        f"{path}/{cond_tag}", where))
                    break

    def _walk_while(self, eqn, path, bound_axes, tainted, mult):
        cond_j = _open(eqn.params["cond_jaxpr"])
        body_j = eqn.params["body_jaxpr"]
        body_open = _open(body_j)
        cond_nc = eqn.params.get("cond_nconsts", 0)
        body_nc = eqn.params.get("body_nconsts", 0)
        carry = list(eqn.invars[cond_nc + body_nc:])
        # Fixpoint over carry taint: the body can BIRTH rank-taint
        # (axis_index written into the carry), which the next
        # iteration's condition then reads — divergence decided from
        # the initial carry alone misses it. Monotone over <= n_carry
        # positions, so it converges in <= n_carry rounds.
        taint_pos: Set[int] = {
            i for i, v in enumerate(carry)
            if _is_var(v) and v in tainted}
        body_consts = list(eqn.invars[cond_nc:cond_nc + body_nc])
        for _ in range(len(carry) + 1):
            binder_taint = set()
            for outer, binder in zip(body_consts,
                                     body_open.invars[:body_nc]):
                if _is_var(outer) and outer in tainted:
                    binder_taint.add(binder)
            for i in taint_pos:
                binder_taint.add(body_open.invars[body_nc + i])
            _, final = self._taint_flow(body_open, binder_taint)
            new_pos = {i for i, v in enumerate(body_open.outvars)
                       if _is_var(v) and v in final}
            if new_pos <= taint_pos:
                break
            taint_pos |= new_pos
        cond_taint = _align_taint(
            list(eqn.invars[:cond_nc]) + carry, cond_j.invars, tainted)
        for i in taint_pos:
            cond_taint.add(cond_j.invars[cond_nc + i])
        divergent = self._taint_only(cond_j, cond_taint)
        before = len(self.schedule)
        body_binder_taint = _align_taint(
            eqn.invars, body_open.invars, tainted)
        for i in taint_pos:
            body_binder_taint.add(body_open.invars[body_nc + i])
        self.walk(body_j, path=f"{path}/while", bound_axes=bound_axes,
                  tainted=body_binder_taint, mult=None)
        for i in taint_pos:       # the loop's outputs ARE the carry
            if i < len(eqn.outvars):
                tainted.add(eqn.outvars[i])
        body_colls = self.schedule[before:]
        if divergent and body_colls:
            self.findings.append(RawFinding(
                "HVV101",
                "collective under RANK-DIVERGENT control flow: a while "
                "loop whose trip count derives from axis_index contains "
                f"'{body_colls[0].kind}' — ranks exit the loop after "
                "different iteration counts and the extra collectives "
                "never match up -> deadlock",
                f"{path}/while", _source_of(eqn)))
        # Collectives in the loop CONDITION run one extra time vs the
        # body on every rank — never legal for a collective.
        sub = ScheduleWalker()
        sub._counter = self._counter
        sub.walk(cond_j, path=f"{path}/while.cond", bound_axes=bound_axes,
                 tainted=cond_taint, mult=None)
        self._counter = sub._counter
        self.findings.extend(sub.findings)
        self.donating_calls.extend(sub.donating_calls)
        if sub.schedule:
            self.findings.append(RawFinding(
                "HVV101",
                f"collective '{sub.schedule[0].kind}' inside a while "
                "loop CONDITION: the condition evaluates once more than "
                "the body and data-dependently per rank -> deadlock",
                f"{path}/while.cond", _source_of(eqn)))
            self.schedule.extend(sub.schedule)

    # ----------------------------------------------------------- donation

    def _check_donation(self, eqn, jaxpr, path):
        donated = eqn.params.get("donated_invars")
        if not donated or not any(donated):
            return
        where = _source_of(eqn)
        name = eqn.params.get("name", eqn.primitive.name)
        donated_vars = [v for v, d in zip(eqn.invars, donated)
                        if d and _is_var(v)]
        self.donating_calls.append((name, path, where))
        if not donated_vars:
            return
        eqns = list(jaxpr.eqns)
        start = eqns.index(eqn) + 1
        later_reads = set()
        for later in eqns[start:]:
            for v in later.invars:
                if _is_var(v) and v in donated_vars:
                    later_reads.add(v)
        for v in jaxpr.outvars:
            if _is_var(v) and v in donated_vars:
                later_reads.add(v)
        for v in later_reads:
            self.findings.append(RawFinding(
                "HVV104",
                f"buffer {v} (shape {tuple(getattr(v.aval, 'shape', ()))}) "
                f"is donated to '{name}' and READ AGAIN afterwards in the "
                "same program: XLA invalidates donated buffers, the "
                "read returns garbage on hardware (IR-level HVD003)",
                path or "<top>", where))


def extract(closed_jaxpr, *, bound_axes=frozenset()):
    """(schedule, findings, donating_calls) of a closed jaxpr."""
    w = ScheduleWalker()
    w.walk(closed_jaxpr, bound_axes=bound_axes)
    return w.schedule, w.findings, w.donating_calls


def sharding_constraint_refs(closed_jaxpr, *, _depth: int = 0
                             ) -> List[Tuple[Tuple[str, ...], str, str]]:
    """Every ``with_sharding_constraint`` in the (recursively opened)
    jaxpr as ``(axis names referenced, path, source)`` tuples — the
    HVV202 input: a constraint spelling a physical axis the bound
    LogicalMesh does not define is exactly the vocabulary drift the
    rules table exists to prevent. Axis names come from the constraint's
    NamedSharding spec; non-named shardings (GSPMD opaque) contribute
    nothing."""
    if _depth > 32:
        return []
    out: List[Tuple[Tuple[str, ...], str, str]] = []
    jaxpr = _open(closed_jaxpr)
    for eqn in getattr(jaxpr, "eqns", ()):
        if eqn.primitive.name == "sharding_constraint":
            sharding = eqn.params.get("sharding")
            spec = getattr(sharding, "spec", None)
            if spec is not None:
                axes: List[str] = []
                for entry in spec:
                    parts = (entry if isinstance(entry, (tuple, list))
                             else (entry,))
                    axes.extend(p for p in parts if isinstance(p, str))
                if axes:
                    out.append((tuple(axes), "sharding_constraint",
                                _source_of(eqn)))
            continue
        for val in eqn.params.values():
            for item in (val if isinstance(val, (tuple, list)) else [val]):
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    out.extend(sharding_constraint_refs(
                        item, _depth=_depth + 1))
    return out


def summarize(schedule: Sequence[CollectiveOp]) -> Dict[str, Any]:
    """Static audit numbers for one program: collective count and bytes
    (payload x static multiplier; while-nested ops count once and are
    reported separately): what :func:`tools.hvdverify.audit_collectives`
    returns."""
    by_kind: Dict[str, int] = {}
    total = 0
    unbounded = 0
    for op in schedule:
        by_kind[op.kind] = by_kind.get(op.kind, 0) + 1
        if op.times is None:
            unbounded += 1
            total += op.payload_bytes
        else:
            total += op.payload_bytes * op.times
    out = {
        "count": len(schedule),
        "bytes": int(total),
        "mb": round(total / (1024 * 1024), 2),
        "by_kind": dict(sorted(by_kind.items())),
    }
    if unbounded:
        out["unbounded_trip_ops"] = unbounded
    return out
