"""hvdverify engine: trace a program, walk its jaxpr, run the rules.

The AST linter's contract, ported to IR land: :func:`verify` takes a
callable + abstract example args, traces it with ``jax.make_jaxpr``
under the CPU backend (no devices or compilation — tracing is
backend-free), extracts the collective schedule, and returns a
:class:`VerifiedProgram` with findings. ``python -m tools.hvdverify
--sweep`` runs the whole program registry (tools/hvdverify/registry.py)
and exits nonzero on any unsuppressed finding — the CI gate, mirroring
the hvdlint sweep.

Suppression: a registry entry (or fixture) carries
``suppress={"HVVxxx": "reason"}``; suppressed findings are reported but
never fail the gate, and every shipped suppression must carry its
reason (the same discipline as ``# hvdlint: disable=``).
"""

from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from tools.hvdverify.rules import (
    EquivalenceSpec,
    Finding,
    ReconcileSpec,
    ShardingSpec,
    check_axis_vocabulary,
    check_equivalence,
    check_reconciliation,
    check_shardings,
    from_raw,
)
from tools.hvdverify.schedule import (
    CollectiveOp,
    ScheduleWalker,
    sharding_constraint_refs,
    summarize,
)

_UNBOUND_RE = re.compile(r"unbound axis name:?\s*(\w+)")


@dataclasses.dataclass
class VerifiedProgram:
    name: str
    schedule: List[CollectiveOp]
    findings: List[Finding]
    summary: Dict[str, Any]
    traced: bool = True

    @property
    def active(self) -> List[Finding]:
        return [f for f in self.findings if not f.suppressed]

    @property
    def suppressed(self) -> List[Finding]:
        return [f for f in self.findings if f.suppressed]


def _apply_suppressions(findings: List[Finding],
                        suppress: Dict[str, str]) -> List[Finding]:
    out = []
    for f in findings:
        reason = suppress.get(f.rule)
        if reason:
            f = dataclasses.replace(f, suppressed=True,
                                    suppress_reason=reason)
        out.append(f)
    return out


def verify(
    fn: Callable,
    args: Sequence[Any],
    *,
    name: str = "<program>",
    forbid_donation: bool = False,
    forbid_donation_why: str = "",
    reconcile: Optional[ReconcileSpec] = None,
    shardings: Optional[ShardingSpec] = None,
    logical_mesh: Any = None,
    equivalence: Optional[Sequence[EquivalenceSpec]] = None,
    suppress: Optional[Dict[str, str]] = None,
) -> VerifiedProgram:
    """Trace ``fn(*args)`` and verify its collective schedule.

    ``args`` may be concrete arrays or ``jax.ShapeDtypeStruct`` pytrees —
    only shapes/dtypes matter; nothing executes. A trace failure from an
    unbound collective axis is converted into an HVV102 finding (that IS
    the bug class: the collective names an axis no enclosing mesh
    binds); any other trace failure propagates, because a program the
    verifier cannot trace is a broken registry entry, not a clean one.

    ``forbid_donation`` encodes a program-level invariant (the elastic
    windowed loop: no state donation while async snapshot copies are in
    flight — donation would let XLA reuse a buffer the d2h copy is
    still reading): ANY donating call in the trace is an HVV104
    finding, not just use-after-donation.

    The HVV2xx sharding pass: ``shardings`` (a :class:`ShardingSpec`)
    reconciles declared partition specs against the LogicalMesh rules
    table (HVV201); ``logical_mesh`` (a LogicalMesh) checks every
    collective axis and ``with_sharding_constraint`` against the mesh's
    vocabulary (HVV202); ``equivalence`` (a sequence of
    :class:`EquivalenceSpec`) pins the composed schedule op-identical
    to per-module reference traces (HVV203).
    """
    import jax

    try:
        with warnings.catch_warnings():
            # Nested-donation warnings are expected: tracing a dispatch
            # handle under make_jaxpr nests its jit, and HVV104 judges
            # the donation flags itself.
            warnings.simplefilter("ignore")
            closed = jax.make_jaxpr(fn)(*args)
    except NameError as e:
        m = _UNBOUND_RE.search(str(e))
        if not m:
            raise
        finding = Finding(
            program=name, rule="HVV102",
            message=(f"collective over axis {m.group(1)!r} which no "
                     "enclosing mesh/shard_map binds — the program "
                     "cannot even trace under its declared mesh "
                     "(the runtime spelling is a per-rank NameError "
                     "or a mis-wired mesh)"),
            path="<trace>")
        return VerifiedProgram(
            name=name, schedule=[],
            findings=_apply_suppressions([finding], suppress or {}),
            summary={"count": 0, "bytes": 0, "mb": 0.0, "by_kind": {}},
            traced=False)

    walker = ScheduleWalker()
    walker.walk(closed)
    findings = [from_raw(name, raw) for raw in walker.findings]

    if forbid_donation and walker.donating_calls:
        why = forbid_donation_why or (
            "this program declares donation forbidden")
        for call_name, path, source in walker.donating_calls:
            findings.append(Finding(
                program=name, rule="HVV104",
                message=(f"'{call_name}' donates its input buffers, but "
                         f"{why} — donation here lets XLA overwrite a "
                         "buffer an in-flight async snapshot d2h copy "
                         "is still reading (PR-5 elastic invariant, "
                         "horovod_tpu/elastic/loop.py)"),
                path=path, source=source))

    if reconcile is not None:
        findings.extend(
            check_reconciliation(name, walker.schedule, reconcile))

    if shardings is not None:
        findings.extend(check_shardings(name, shardings))

    if logical_mesh is not None:
        findings.extend(check_axis_vocabulary(
            name, walker.schedule, sharding_constraint_refs(closed),
            logical_mesh))

    if equivalence:
        findings.extend(
            check_equivalence(name, walker.schedule, equivalence))

    return VerifiedProgram(
        name=name,
        schedule=walker.schedule,
        findings=_apply_suppressions(findings, suppress or {}),
        summary=summarize(walker.schedule),
    )


def audit_collectives(fn: Callable, *args) -> Dict[str, Any]:
    """The static-audit summary of one program: collective count +
    bytes, by kind (cross-checked against the dynamic accounting in
    tests/test_wire_bytes.py; ``chip_smoke.py`` asks it of the
    data-parallel lanes it steps). Pure tracing; safe anywhere jax
    traces."""
    import jax

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        closed = jax.make_jaxpr(fn)(*args)
    walker = ScheduleWalker()
    walker.walk(closed)
    return summarize(walker.schedule)


def verify_programs(programs) -> List[VerifiedProgram]:
    """Verify a sequence of registry Program entries (build + verify)."""
    out = []
    for prog in programs:
        fn, args = prog.build()
        out.append(verify(
            fn, args,
            name=prog.name,
            forbid_donation=prog.forbid_donation,
            forbid_donation_why=prog.forbid_donation_why,
            reconcile=prog.reconcile() if prog.reconcile else None,
            shardings=prog.shardings() if prog.shardings else None,
            logical_mesh=(prog.logical_mesh() if prog.logical_mesh
                          else None),
            equivalence=(prog.equivalence() if prog.equivalence
                         else None),
            suppress=prog.suppress,
        ))
    return out
