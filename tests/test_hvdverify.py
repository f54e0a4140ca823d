"""Tests for tools/hvdverify: every HVV rule must fire on its positive
traced-program fixtures (tests/hvdverify_fixtures/) and stay silent on
the negatives, and the repo's real program registry must sweep clean.

Fixture contract: each module defines ``build() -> (fn, args)`` plus an
``EXPECT`` tuple of rule ids (empty for ``*_neg_*`` files), with
optional ``FORBID_DONATION``/``FORBID_DONATION_WHY`` and zero-arg
callables ``RECONCILE`` (-> ReconcileSpec), ``SHARDINGS``
(-> ShardingSpec, HVV201), ``LOGICAL_MESH`` (-> LogicalMesh, HVV202)
and ``EQUIVALENCE`` (-> [EquivalenceSpec], HVV203). The corpus includes
the two named incidents: the PR-3 ring-attention rotation-inside-the-
rank-divergent-cond shape (hvv101_pos_ring_rotation_in_cond) and the
PR-5 elastic donating-window variant
(hvv104_pos_elastic_donating_window).
"""

import importlib
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "hvdverify_fixtures"

sys.path.insert(0, str(REPO))

from tools.hvdverify import (  # noqa: E402
    FAST_GROUPS,
    REGISTRY,
    RULES,
    programs,
    verify,
    verify_programs,
)


def _fixture_modules():
    files = sorted(p for p in FIXTURES.glob("hvv*.py"))
    assert files, "fixture corpus missing"
    return files


def _load(path: Path):
    return importlib.import_module(
        f"tests.hvdverify_fixtures.{path.stem}")


def _verify_fixture(mod, name):
    fn, args = mod.build()
    reconcile = getattr(mod, "RECONCILE", None)
    shardings = getattr(mod, "SHARDINGS", None)
    logical_mesh = getattr(mod, "LOGICAL_MESH", None)
    equivalence = getattr(mod, "EQUIVALENCE", None)
    return verify(
        fn, args, name=name,
        forbid_donation=getattr(mod, "FORBID_DONATION", False),
        forbid_donation_why=getattr(mod, "FORBID_DONATION_WHY", ""),
        reconcile=reconcile() if reconcile else None,
        shardings=shardings() if shardings else None,
        logical_mesh=logical_mesh() if logical_mesh else None,
        equivalence=equivalence() if equivalence else None)


@pytest.mark.parametrize("path", _fixture_modules(),
                         ids=lambda p: p.stem)
def test_fixture(path, hvd):
    mod = _load(path)
    result = _verify_fixture(mod, path.stem)
    fired = {f.rule for f in result.findings}
    expected = set(mod.EXPECT)
    if "_neg_" in path.name:
        assert not expected, f"negative fixture {path.name} sets EXPECT"
        assert not fired, (
            f"negative fixture {path.name} produced findings:\n"
            + "\n".join(f.format() for f in result.findings))
    else:
        assert expected, f"positive fixture {path.name} lacks EXPECT"
        assert fired == expected, (
            f"{path.name}: expected {sorted(expected)}, got "
            f"{sorted(fired)}:\n"
            + "\n".join(f.format() for f in result.findings))


def test_corpus_covers_every_rule_both_ways():
    """>= 2 positive and >= 2 negative fixtures per rule (the ISSUE's
    corpus floor), counting hvv-prefixed files — the HVV2xx sharding
    rules included."""
    for rule in RULES:
        prefix = rule.lower()
        pos = list(FIXTURES.glob(f"{prefix}_pos_*.py"))
        neg = list(FIXTURES.glob(f"{prefix}_neg_*.py"))
        assert len(pos) >= 2, f"{rule}: {len(pos)} positive fixtures (<2)"
        assert len(neg) >= 2, f"{rule}: {len(neg)} negative fixtures (<2)"


def test_named_incident_fixtures_present():
    """The two historical shapes ride the corpus by name: PR 3's
    rank-divergent ring rotation and PR 5's donating elastic window."""
    assert (FIXTURES / "hvv101_pos_ring_rotation_in_cond.py").exists()
    assert (FIXTURES / "hvv104_pos_elastic_donating_window.py").exists()


# ------------------------------------------------------------- registry


def test_registry_shape():
    """The acceptance floor: >= 9 gate lanes, 3 optimizer modes, all 6
    parallel modules, the elastic loop — and the byte-reconciled +
    donation-forbidden entries are actually marked."""
    by_group = {}
    for p in REGISTRY:
        by_group.setdefault(p.group, []).append(p)
    assert len(by_group["gate"]) >= 9
    assert len(by_group["optimizer"]) == 3
    # The hierarchical DP exchange programs (PR-10): both DCN exchange
    # shapes, each byte-reconciled per ladder leg.
    assert {p.name for p in by_group["dp"]} == {
        "dp.hier_overlap", "dp.hier_int8"}
    assert all(p.reconcile is not None for p in by_group["dp"])
    names = {p.name for p in by_group["parallel"]}
    assert names == {
        "parallel.spmd", "parallel.tp", "parallel.pipeline",
        "parallel.ulysses", "parallel.ring_attention", "parallel.moe"}
    elastic = by_group["elastic"]
    assert {p.name for p in elastic} == {
        "elastic.windowed_loop", "elastic.windowed_loop_resized"}
    assert all(p.forbid_donation for p in elastic)
    serve = by_group["serve"]
    assert {p.name for p in serve} == {
        "serve.step", "serve.step_paged",
        "serve.step_tp", "serve.step_tp_paged",
        "serve.step_spec", "serve.step_spec_paged",
        "serve.step_spec_tp",
        "serve.step_prefill_pool", "serve.step_decode_pool",
        "serve.step_decode_pool_tp"}
    assert all(p.forbid_donation for p in serve)
    # The disaggregated pool steps carry the handoff-sharpened
    # rationale: across the transfer the pages are the only copy.
    disagg = [p for p in serve if "pool" in p.name]
    assert len(disagg) == 3
    assert all("ONLY copy" in p.forbid_donation_why for p in disagg)
    # The speculative programs carry the sharpened donation rationale:
    # the pre-step pages are the rejected window's rollback substrate.
    spec = [p for p in serve if "spec" in p.name]
    assert len(spec) == 3
    assert all("rejected window" in p.forbid_donation_why or
               "rejection falls back" in p.forbid_donation_why
               for p in spec)
    # The TP variants carry the full HVV2xx surface (sharding table +
    # bound LogicalMesh), like the composed stacks.
    tp_serve = [p for p in serve if "_tp" in p.name]
    assert len(tp_serve) == 4
    assert all(p.shardings is not None for p in tp_serve)
    assert all(p.logical_mesh is not None for p in tp_serve)
    assert all(p.reconcile is not None for p in by_group["optimizer"])
    # The composed-stack lanes (logical-axis registry): each carries
    # the full HVV2xx surface — a sharding table, a bound LogicalMesh
    # and per-module equivalence references.
    composed = by_group["composed"]
    assert {p.name for p in composed} == {
        "composed.dp_tp", "composed.dp_ulysses", "composed.tp_pp"}
    assert all(p.shardings is not None for p in composed)
    assert all(p.logical_mesh is not None for p in composed)
    assert all(p.equivalence is not None for p in composed)


def test_repo_sweep_core_is_clean(hvd):
    """The fast-lane shipping gate: the optimizer/parallel/elastic
    registry programs (cheap traces) verify at zero unsuppressed
    findings. The full registry incl. the big-model gate lanes is
    pinned by test_repo_sweep_is_clean (slow) and tools/check.sh
    --verify."""
    results = verify_programs(programs(groups=FAST_GROUPS))
    bad = [f.format() for r in results for f in r.active]
    assert not bad, "\n".join(bad)
    # Schedules must be non-trivially extracted, not vacuously clean.
    with_colls = [r for r in results if r.summary["count"]]
    assert len(with_colls) >= 8, [
        (r.name, r.summary["count"]) for r in results]


def test_repo_sweep_is_clean(hvd):
    """The full acceptance gate, mirroring hvdlint's
    test_repo_sweep_is_clean: EVERY registry program — the 9 driver
    gate lanes included — traces at zero unsuppressed findings."""
    results = verify_programs(programs())
    bad = [f.format() for r in results for f in r.active]
    assert not bad, "\n".join(bad)
    assert len(results) == len(REGISTRY)


def test_optimizer_overlap_issue_order_is_reverse(hvd):
    """The IR-level pin of PR 4's reverse-order overlap emission: with
    overlap on, the FIRST issued bucket is the LAST plan bucket
    (backward availability order), vs forward order with overlap off —
    read directly off the verified schedules' issue indices."""
    fused, over = verify_programs(
        programs(names=["optimizer.fused", "optimizer.overlap"]))
    def buckets(result):
        # [(bucket scope, its members' payloads in issue order)], in order
        # of first issue
        by_scope = {}
        for op in result.schedule:
            scope = [p for p in op.name_stack.split("/")
                     if p.startswith("hvd_allreduce_")][0]
            by_scope.setdefault(scope, []).append(op.payload_bytes)
        return list(by_scope.items())

    fwd, rev = buckets(fused), buckets(over)
    assert fwd == rev[::-1], (fwd, rev)
    assert len(fwd) >= 2  # multi-bucket plan, or the pin is vacuous


def test_scan_multiplier_accounting(hvd):
    """Collectives under lax.scan are accounted once per iteration: a
    K-step window multiplies its per-step collective bytes by K."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd_mod
    from horovod_tpu.jax.window import windowed

    def step(state, batch):
        return state + hvd_mod.allreduce(batch.mean()), batch.mean()

    k = 5
    run = hvd_mod.spmd_fn(windowed(step, k),
                          in_specs=(P(), P(None, "hvd")),
                          out_specs=(P(), P()))
    state = jax.ShapeDtypeStruct((), jnp.float32)
    batch = jax.ShapeDtypeStruct((k, 8, 4), jnp.float32)
    res = verify(lambda s, b: run(s, b), (state, batch), name="win")
    assert res.summary["count"] == 1
    (op,) = res.schedule
    assert op.times == k
    assert res.summary["bytes"] == op.payload_bytes * k


def test_elastic_donating_variant_is_flagged(hvd):
    """The PR-5 invariant as a regression test: take the REAL elastic
    window builder, swap in the donating jit, and the verifier must
    flag it under forbid_donation (the registry entry guards the
    shipped, non-donating build)."""
    import jax

    from horovod_tpu.jax.window import windowed
    from tools.hvdverify.registry import (
        _ELASTIC_WHY,
        _build_elastic_windowed_loop,
    )

    fn, args = _build_elastic_windowed_loop()
    clean = verify(fn, args, name="elastic", forbid_donation=True,
                   forbid_donation_why=_ELASTIC_WHY)
    assert not clean.findings

    def donating(state, batch):
        import optax

        from horovod_tpu import models

        model = models.MNISTNet()
        step_fn = models.make_train_step(model, optax.sgd(0.1),
                                         average_loss=False)
        window_fn = jax.jit(windowed(step_fn, 4), donate_argnums=(0,))
        return window_fn(state, batch)

    flagged = verify(donating, args, name="elastic-donating",
                     forbid_donation=True,
                     forbid_donation_why=_ELASTIC_WHY)
    assert [f.rule for f in flagged.findings] == ["HVV104"]
    assert "snapshot" in flagged.findings[0].message


def test_serve_step_verifies_and_donating_variant_is_flagged(hvd):
    """The PR-7 serving invariant: the REAL mixed prefill+decode step
    (traced exactly as ServeEngine jits it) verifies clean under
    forbid_donation, and a donate-the-pages variant is an HVV104
    finding — the KV cache must never be donated while a request
    holds pages."""
    import functools

    import jax

    from tools.hvdverify.registry import _SERVE_WHY, _build_serve_step

    fn, args = _build_serve_step()
    clean = verify(fn, args, name="serve.step", forbid_donation=True,
                   forbid_donation_why=_SERVE_WHY)
    assert not clean.findings
    # Zero collectives today — the schedule is honestly empty, and the
    # verified property is the donation rule alone.
    assert clean.summary["count"] == 0

    from horovod_tpu.serve.engine import serve_step

    donating = jax.jit(functools.partial(serve_step, page_size=8),
                       donate_argnums=(1,))    # donate the pages
    flagged = verify(lambda p, pages, d, pr: donating(p, pages, d, pr),
                     args, name="serve-donating", forbid_donation=True,
                     forbid_donation_why=_SERVE_WHY)
    assert "HVV104" in [f.rule for f in flagged.findings]
    assert "pages" in flagged.findings[0].message


def test_serve_step_paged_verifies_and_donating_variant_is_flagged(hvd):
    """The PR-8 edition of the same invariant: the fused
    paged-attention step (the Pallas kernel streams pages READ-ONLY;
    the new-row insert stays the scatter outside it) verifies clean
    under forbid_donation, and donating the pages is flagged exactly
    like the gather step — requests hold pages under an in-flight
    step in both modes."""
    import functools

    import jax

    from tools.hvdverify.registry import _SERVE_WHY, _build_serve_step

    fn, args = _build_serve_step(attention="paged")
    clean = verify(fn, args, name="serve.step_paged",
                   forbid_donation=True, forbid_donation_why=_SERVE_WHY)
    assert not clean.findings
    assert clean.summary["count"] == 0

    from horovod_tpu.serve.engine import serve_step

    donating = jax.jit(functools.partial(serve_step, page_size=8,
                                         attention="paged"),
                       donate_argnums=(1,))    # donate the pages
    flagged = verify(lambda p, pages, d, pr: donating(p, pages, d, pr),
                     args, name="serve-paged-donating",
                     forbid_donation=True, forbid_donation_why=_SERVE_WHY)
    assert "HVV104" in [f.rule for f in flagged.findings]


@pytest.mark.parametrize("attention", ["gather", "paged"])
def test_serve_step_tp_verifies_and_donating_variant_is_flagged(
        hvd, attention):
    """The TP-sharded step (this PR): the SPMD spelling verifies clean
    under forbid_donation + the full HVV2xx surface, with a NON-empty
    collective schedule (the TP all-reduces/all-gathers) — and the
    donate-the-pages variant is still an HVV104 finding: donation of
    any head-shard of a live page is the same bug, per chip."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from tools.hvdverify.registry import (
        _SERVE_WHY,
        _build_serve_step_tp,
        _logical_mesh,
        _serve_tp_logical_mesh,
        _serve_tp_shardings,
        _shmapped,
    )

    fn, args = _build_serve_step_tp(attention=attention)
    clean = verify(fn, args, name=f"serve.step_tp[{attention}]",
                   forbid_donation=True, forbid_donation_why=_SERVE_WHY,
                   shardings=_serve_tp_shardings(),
                   logical_mesh=_serve_tp_logical_mesh())
    assert not clean.findings
    # Unlike the tp=1 step, the schedule is NOT empty: the TP
    # reductions (attention output, MLP down-proj, vocab all-gather)
    # are the whole point.
    assert clean.summary["count"] > 0

    from horovod_tpu.models.parallel_lm import lm_param_specs
    from horovod_tpu.serve.engine import serve_step

    lm = _logical_mesh("dp=1,tp=4")
    tp_ax = lm.role_axis("tensor")
    kv = P(None, None, tp_ax, None)
    specs = lm_param_specs(2, tp_ax, vocab_parallel=True)
    step = functools.partial(serve_step, page_size=8,
                             attention=attention, tp=tp_ax,
                             vocab_parallel=True)
    donating = jax.jit(
        _shmapped(lambda p, pages, d, pr: step(p, pages, d, pr),
                  lm.mesh, in_specs=(specs, kv, P(), P()),
                  out_specs=(kv, P(), P())),
        donate_argnums=(1,))    # donate the (sharded) pages
    flagged = verify(lambda p, pages, d, pr: donating(p, pages, d, pr),
                     args, name="serve-tp-donating",
                     forbid_donation=True, forbid_donation_why=_SERVE_WHY)
    assert "HVV104" in [f.rule for f in flagged.findings]


def test_serve_disagg_pool_steps_verify_and_donating_variants_flagged(
        hvd):
    """The disaggregated pool programs (this PR): the prefill pool's
    prefill-lane-only tick (serve_step_prefill) and the decode pool's
    ``pre=None`` tick both verify clean under forbid_donation, and a
    donate-the-pages variant of EACH is an HVV104 finding — across the
    KV handoff the pages are the only copy of the request's history,
    so donation on either side of the wire is the same bug."""
    import functools

    import jax

    from tools.hvdverify.registry import (
        _build_serve_step_decode_pool,
        _build_serve_step_prefill_pool,
    )

    why = programs(names=["serve.step_prefill_pool"])[0] \
        .forbid_donation_why
    assert "ONLY copy" in why   # the handoff-sharpened rationale

    # Prefill pool: the lane alone, pages parked for handoff.
    fn, args = _build_serve_step_prefill_pool()
    clean = verify(fn, args, name="serve.step_prefill_pool",
                   forbid_donation=True, forbid_donation_why=why)
    assert not clean.findings
    assert clean.summary["count"] == 0   # tp=1: no collectives

    from horovod_tpu.serve.engine import serve_step, serve_step_prefill

    donating = jax.jit(
        functools.partial(serve_step_prefill, page_size=8),
        donate_argnums=(1,))    # donate the parked pages
    flagged = verify(lambda p, pages, pr: donating(p, pages, pr),
                     args, name="prefill-pool-donating",
                     forbid_donation=True, forbid_donation_why=why)
    assert "HVV104" in [f.rule for f in flagged.findings]
    assert "pages" in flagged.findings[0].message

    # Decode pool: serve_step with pre=None, pages just imported.
    fn, args = _build_serve_step_decode_pool()
    clean = verify(fn, args, name="serve.step_decode_pool",
                   forbid_donation=True, forbid_donation_why=why)
    assert not clean.findings

    step = functools.partial(serve_step, page_size=8)
    donating = jax.jit(lambda p, pages, d: step(p, pages, d, None),
                       donate_argnums=(1,))   # donate imported pages
    flagged = verify(lambda p, pages, d: donating(p, pages, d),
                     args, name="decode-pool-donating",
                     forbid_donation=True, forbid_donation_why=why)
    assert "HVV104" in [f.rule for f in flagged.findings]


def test_serve_step_decode_pool_tp_verifies_and_donating_is_flagged(
        hvd):
    """The TP decode-pool tick: verifies clean under forbid_donation +
    the HVV2xx surface with a NON-empty schedule (the TP reductions),
    and donating the head-sharded imported pages is an HVV104
    finding — a shard of an imported page on any chip is still the
    request's only copy of that slice of its history."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from tools.hvdverify.registry import (
        _build_serve_step_decode_pool_tp,
        _logical_mesh,
        _serve_tp_logical_mesh,
        _serve_tp_shardings,
        _shmapped,
    )

    fn, args = _build_serve_step_decode_pool_tp()
    clean = verify(fn, args, name="serve.step_decode_pool_tp",
                   forbid_donation=True,
                   shardings=_serve_tp_shardings(),
                   logical_mesh=_serve_tp_logical_mesh())
    assert not clean.findings
    assert clean.summary["count"] > 0

    from horovod_tpu.models.parallel_lm import lm_param_specs
    from horovod_tpu.serve.engine import serve_step

    lm = _logical_mesh("dp=1,tp=4")
    tp_ax = lm.role_axis("tensor")
    kv = P(None, None, tp_ax, None)
    specs = lm_param_specs(2, tp_ax, vocab_parallel=True)
    step = functools.partial(serve_step, page_size=8, tp=tp_ax,
                             vocab_parallel=True)
    donating = jax.jit(
        _shmapped(lambda p, pages, d: step(p, pages, d, None)[:2],
                  lm.mesh, in_specs=(specs, kv, P()),
                  out_specs=(kv, P())),
        donate_argnums=(1,))    # donate the (sharded) imported pages
    flagged = verify(lambda p, pages, d: donating(p, pages, d),
                     args, name="decode-pool-tp-donating",
                     forbid_donation=True)
    assert "HVV104" in [f.rule for f in flagged.findings]


def test_serve_step_tp_rogue_axis_is_flagged(hvd):
    """HVV202 pin for the serve TP lane: run the same step over a mesh
    whose axis the bound LogicalMesh does NOT define ('rogue' instead
    of 'tp') — every TP collective then spells an axis outside the
    mesh vocabulary, and each is a finding. This is the smuggled-
    physical-spelling class the rules table exists to prevent."""
    import functools

    from jax.sharding import PartitionSpec as P

    from tools.hvdverify.registry import (
        _build_serve_step_tp,
        _serve_tp_logical_mesh,
        _shmapped,
        _submesh,
    )

    _, args = _build_serve_step_tp()

    from horovod_tpu.models.parallel_lm import lm_param_specs
    from horovod_tpu.serve.engine import serve_step

    mesh = _submesh({"rogue": 4})
    kv = P(None, None, "rogue", None)
    specs = lm_param_specs(2, "rogue", vocab_parallel=True)
    step = functools.partial(serve_step, page_size=8, tp="rogue",
                             vocab_parallel=True)
    rogue = _shmapped(lambda p, pages, d, pr: step(p, pages, d, pr),
                      mesh, in_specs=(specs, kv, P(), P()),
                      out_specs=(kv, P(), P()))
    flagged = verify(lambda p, pages, d, pr: rogue(p, pages, d, pr),
                     args, name="serve-tp-rogue-axis",
                     logical_mesh=_serve_tp_logical_mesh())
    rules = [f.rule for f in flagged.findings]
    assert rules and set(rules) == {"HVV202"}
    assert any("rogue" in f.message for f in flagged.findings)


def test_while_condition_findings_are_merged(hvd):
    """Findings produced INSIDE a while-loop condition's sub-walk (here
    a rank-divergent one-branch cond) must surface alongside the
    collective-in-condition finding, not be dropped with the sub-walker."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tests.hvdverify_fixtures._common import P, f32, mesh, shmap

    def program(x):
        rank = lax.axis_index("hvd")

        def cond_fn(carry):
            i, v = carry
            s = lax.cond(rank == 0,
                         lambda u: lax.psum(u, "hvd"),
                         lambda u: u, v)
            return i < jnp.int32(3) + (jnp.sum(s) * 0).astype(jnp.int32)

        def body_fn(carry):
            i, v = carry
            return i + 1, v + 1.0

        _, out = lax.while_loop(cond_fn, body_fn, (jnp.int32(0), x))
        return out

    fn = shmap(program, mesh(hvd=8), in_specs=P("hvd"),
               out_specs=P("hvd"))
    res = verify(fn, (f32(8, 4),), name="while-cond")
    msgs = [f.message for f in res.findings if f.rule == "HVV101"]
    assert any("only some branches" in m for m in msgs), msgs
    assert any("CONDITION" in m for m in msgs), msgs


def test_while_body_born_taint_makes_trip_count_divergent(hvd):
    """A while loop whose BODY writes axis_index into the carry counter
    is rank-divergent even though the initial carry is clean — the
    carry-taint fixpoint must surface it (each rank exits after a
    different iteration count; the body psum then deadlocks)."""
    import jax.numpy as jnp
    from jax import lax

    from tests.hvdverify_fixtures._common import P, f32, mesh, shmap

    def program(x):
        def cond_fn(carry):
            i, _ = carry
            return i < 8

        def body_fn(carry):
            i, v = carry
            # Taint born HERE: the counter advances by a rank-derived
            # stride, so ranks trip the condition at different counts.
            return (i + lax.axis_index("hvd") + 1,
                    lax.psum(v, "hvd"))

        _, out = lax.while_loop(cond_fn, body_fn, (jnp.int32(0), x))
        return out

    fn = shmap(program, mesh(hvd=8), in_specs=P("hvd"),
               out_specs=P("hvd"))
    res = verify(fn, (f32(8, 4),), name="body-born-taint")
    msgs = [f.message for f in res.findings if f.rule == "HVV101"]
    assert any("trip count" in m for m in msgs), [
        f.format() for f in res.findings]


def test_hvv105_flags_untagged_exchange_beside_tagged(hvd):
    """A hand-rolled gradient-sized psum on the gradient axis is
    unplanned traffic even when a TAGGED fused exchange exists — the
    tag pre-filter must not blind the rule to the bypass (metric-sized
    psums stay exempt)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.jax.fusion import fused_reduce
    from tests.hvdverify_fixtures._common import P, f32, mesh, shmap
    from tools.hvdverify.rules import ReconcileSpec

    leaves = [jax.ShapeDtypeStruct((128,), jnp.float32)]

    def exchange(a):
        (g,) = fused_reduce([a])              # the tagged, planned path
        stray = lax.psum(a * 2.0, "hvd")      # hand-rolled bypass
        metric = lax.psum(jnp.sum(a), "hvd")  # loss mean: stays exempt
        return g + stray + metric

    fn = shmap(exchange, mesh(hvd=8), in_specs=(P(),), out_specs=P())
    # fused_reduce reads the SPMD-axis contextvar hvd.spmd_run sets;
    # the raw shard_map fixture must set it for the tagged path.
    from horovod_tpu.common.state import reset_spmd_axis, set_spmd_axis

    token = set_spmd_axis("hvd")
    try:
        res = verify(fn, (f32(128),), name="tagged-plus-stray",
                     reconcile=ReconcileSpec(leaves=leaves,
                                             threshold=1 << 20,
                                             axis_size=8))
    finally:
        reset_spmd_axis(token)
    assert [f.rule for f in res.findings] == ["HVV105"], [
        f.format() for f in res.findings]
    assert "OUTSIDE the tagged fused exchange" in res.findings[0].message


def test_hvv105_shaped_bucket_must_sum_to_its_bytes(hvd):
    """The flat path's bucket is the psum entries under its own scope:
    they reconcile when they sum to the bucket's bytes, and a plan whose
    bucket holds a leaf the traced bucket lacks (a gradient that fell out
    of the exchange) does not — though every traced entry is tagged."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.jax.fusion import fused_reduce
    from tools.hvdverify.rules import ReconcileSpec

    traced = [jax.ShapeDtypeStruct((16, 24), jnp.float32),
              jax.ShapeDtypeStruct((24,), jnp.float32)]

    def exchange(a, b):
        return tuple(fused_reduce([a, b], fusion_threshold=1 << 20,
                                  name="grads"))

    run = hvd.spmd_fn(exchange, in_specs=(P(), P()), out_specs=(P(), P()))

    def check(leaves):
        return verify((lambda a, b: run(a, b)), tuple(traced), name="shaped",
                      reconcile=ReconcileSpec(leaves=leaves,
                                              threshold=1 << 20,
                                              axis_size=8)).findings

    assert not check(traced), [f.format() for f in check(traced)]
    dropped = check(traced + [jax.ShapeDtypeStruct((8,), jnp.float32)])
    assert dropped and {f.rule for f in dropped} == {"HVV105"}
    assert any("NO matching collective" in f.message for f in dropped)


def test_hvv105_flags_flat_trace_under_declared_ladder(hvd):
    """A program that DECLARES the hierarchical ladder (hier_inner set)
    but traces one flat full-bytes psum per bucket must NOT reconcile
    clean: the ladder silently never engaged (resolve_hierarchical
    config drift) and the inter-slice leg carries inner x the promised
    bytes — the exact regression that would otherwise keep the dp.*
    sweep green while the DCN win is gone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.jax.fusion import fused_reduce
    from tools.hvdverify.rules import ReconcileSpec

    leaves = [jax.ShapeDtypeStruct((128,), jnp.float32)]

    def exchange(a):
        return fused_reduce([a], average=True, fusion_threshold=1 << 20,
                            hierarchical="off", name="grads")[0]

    run = hvd.spmd_fn(exchange, in_specs=(P(),), out_specs=P())
    result = verify(
        (lambda a: run(a)), (leaves[0],), name="flat_under_ladder",
        reconcile=ReconcileSpec(leaves=leaves, threshold=1 << 20,
                                axis_size=8, hier_inner=4))
    msgs = [f.message for f in result.findings if f.rule == "HVV105"]
    assert any("FLAT psum" in m and "ladder" in m for m in msgs), (
        [f.format() for f in result.findings])
    # The SAME trace with no ladder declared reconciles clean.
    clean = verify(
        (lambda a: run(a)), (leaves[0],), name="flat_no_ladder",
        reconcile=ReconcileSpec(leaves=leaves, threshold=1 << 20,
                                axis_size=8))
    assert not clean.findings, [f.format() for f in clean.findings]


def test_hvv105_flags_gather_without_scatter(hvd):
    """A stray all_gather on the gradient axis that matches no bucket is
    unplanned traffic, same as a stray psum — the leftover pool must
    include the gathers."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tests.hvdverify_fixtures._common import P, f32, mesh, shmap
    from tools.hvdverify.rules import ReconcileSpec

    leaves = [jax.ShapeDtypeStruct((128,), jnp.float32)]

    def exchange(a):
        g = lax.psum(a, "hvd") / 8.0          # the planned fused bucket
        extra = lax.all_gather(a[:2], "hvd")  # matches no bucket
        return g + jnp.sum(extra) * 0

    fn = shmap(exchange, mesh(hvd=8), in_specs=(P(),), out_specs=P())
    res = verify(fn, (f32(128),), name="stray-gather",
                 reconcile=ReconcileSpec(leaves=leaves,
                                         threshold=1 << 20, axis_size=8))
    assert [f.rule for f in res.findings] == ["HVV105"], [
        f.format() for f in res.findings]
    assert "all_gather" in res.findings[0].message


def test_suppression_reported_not_failing(hvd):
    """A suppressed finding is carried (with its reason) but does not
    count as active — the hvdlint suppression contract."""
    from jax import lax

    from tests.hvdverify_fixtures._common import P, f32, mesh, shmap

    def program(x):
        rank = lax.axis_index("hvd")
        return lax.cond(rank == 0,
                        lambda v: lax.psum(v, "hvd"),
                        lambda v: v, x)

    fn = shmap(program, mesh(hvd=8), in_specs=P("hvd"),
               out_specs=P("hvd"))
    res = verify(fn, (f32(8, 4),), name="sup",
                 suppress={"HVV101": "fixture: justification text"})
    assert res.findings and all(f.suppressed for f in res.findings)
    assert not res.active
    assert res.findings[0].suppress_reason.startswith("fixture")


def test_cli_contracts():
    """--list-rules and --list run without a backend; an unknown
    --program is a usage error; a clean program exits 0."""
    env_cwd = str(REPO)
    rules = subprocess.run(
        [sys.executable, "-m", "tools.hvdverify", "--list-rules"],
        cwd=env_cwd, capture_output=True, text=True)
    assert rules.returncode == 0
    for rule in RULES:
        assert rule in rules.stdout
    listing = subprocess.run(
        [sys.executable, "-m", "tools.hvdverify", "--list"],
        cwd=env_cwd, capture_output=True, text=True)
    assert listing.returncode == 0
    for p in REGISTRY:
        assert p.name in listing.stdout
    bogus = subprocess.run(
        [sys.executable, "-m", "tools.hvdverify", "--program", "nope"],
        cwd=env_cwd, capture_output=True, text=True)
    assert bogus.returncode == 2, bogus.stderr


def test_cli_clean_program_exits_zero():
    out = subprocess.run(
        [sys.executable, "-m", "tools.hvdverify",
         "--program", "optimizer.fused", "--json"],
        cwd=str(REPO), capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    import json

    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["program"] == "optimizer.fused"
    assert rec["collectives"]["count"] >= 2
    assert rec["findings"] == []


def test_serve_step_spec_verifies_and_donating_variant_is_flagged(hvd):
    """Round-19 speculative serving invariant: the speculative step
    (layer-skip draft scan + rectangular verify pass, traced exactly
    as ServeEngine jits it when speculate_k > 0) verifies clean under
    forbid_donation — and the donate-the-pages variant is an HVV104
    finding. Sharpened rationale: a rejected window rolls back by page
    arithmetic over the PRE-step pages, so donating them destroys the
    very state a rejection falls back to."""
    import functools

    import jax

    from tools.hvdverify.registry import _build_serve_step_spec
    from tools.hvdverify.registry import REGISTRY as _REG

    why = next(p for p in _REG
               if p.name == "serve.step_spec").forbid_donation_why
    fn, args = _build_serve_step_spec()
    clean = verify(fn, args, name="serve.step_spec",
                   forbid_donation=True, forbid_donation_why=why)
    assert not clean.findings
    assert clean.summary["count"] == 0     # tp=1: no collectives

    from horovod_tpu.serve.engine import serve_step_spec

    donating = jax.jit(
        functools.partial(serve_step_spec, k=2, draft_layers=1,
                          page_size=8),
        donate_argnums=(1,))               # donate the pages
    flagged = verify(lambda p, pages, d, pr: donating(p, pages, d, pr),
                     args, name="serve-spec-donating",
                     forbid_donation=True, forbid_donation_why=why)
    assert "HVV104" in [f.rule for f in flagged.findings]
    assert "pages" in flagged.findings[0].message
