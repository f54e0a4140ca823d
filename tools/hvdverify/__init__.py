"""hvdverify — jaxpr-level collective-schedule & sharding verifier.

The native coordinator's runtime mismatch checks (op/dtype/root/shape/
ragged, ``csrc/coordinator.cc``), made STATIC: any entry program is
traced via ``jax.make_jaxpr`` on CPU (no devices, no compilation), the
closed jaxpr is walked recursively through ``jit``/``scan``/``cond``/
``while``/``shard_map``/``custom_vjp`` sub-jaxprs, and the extracted
collective schedule — op kind, axis names, shapes, dtypes, issue order,
wire bytes — is checked against the HVV rule catalogue
(docs/static_analysis.md):

* **HVV101** — collective in only some branches of rank-divergent
  control flow (deadlock; the IR-level HVD002).
* **HVV102** — collective over an axis no enclosing mesh binds.
* **HVV103** — rank-divergent branches submit mismatched schedules
  (the coordinator's five runtime validations, decided at trace time).
* **HVV104** — donated buffer read after the donating call (IR-level
  HVD003), or donation where a program forbids it (the elastic
  snapshot-in-flight invariant).
* **HVV105** — static wire-byte accounting must reconcile exactly with
  ``horovod_tpu.jax.fusion.plan_buckets``.
* **HVV201** — declared in/out/param partition specs must reconcile
  with the LogicalMesh axis-rules table (the sharding analogue of
  HVV105).
* **HVV202** — every collective / ``with_sharding_constraint`` axis
  must be in the bound LogicalMesh's vocabulary.
* **HVV203** — a composed stack's collective schedule must be
  op-identical to its per-module reference traces.

Usage::

    python -m tools.hvdverify --sweep        # the CI gate (registry)
    python -m tools.hvdverify --list
    python -m tools.hvdverify --program optimizer.overlap --schedule

Library surface: :func:`verify` (one program), :func:`audit_collectives`
(the count+bytes summary of one program), the ``REGISTRY`` of real
repo programs, and the schedule walker itself.
"""

from tools.hvdverify.core import (
    VerifiedProgram,
    audit_collectives,
    verify,
    verify_programs,
)
from tools.hvdverify.registry import (
    FAST_GROUPS,
    Program,
    REGISTRY,
    abstractify,
    programs,
)
from tools.hvdverify.rules import (
    EquivalenceSpec,
    Finding,
    ReconcileSpec,
    RULES,
    ShardingSpec,
)
from tools.hvdverify.schedule import (
    COLLECTIVE_PRIMS,
    CollectiveOp,
    ScheduleWalker,
    extract,
    summarize,
)

__all__ = [
    "COLLECTIVE_PRIMS",
    "CollectiveOp",
    "EquivalenceSpec",
    "FAST_GROUPS",
    "Finding",
    "Program",
    "REGISTRY",
    "RULES",
    "ReconcileSpec",
    "ScheduleWalker",
    "ShardingSpec",
    "VerifiedProgram",
    "abstractify",
    "audit_collectives",
    "extract",
    "programs",
    "summarize",
    "verify",
    "verify_programs",
]
