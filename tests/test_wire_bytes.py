"""Wire-byte invariants: the jaxpr the training step lowers to must move
EXACTLY the communication volume the design claims (docs/concepts.md,
docs/parallelism.md) — the structural counterpart of the reference's
bytes/sec autotuner scoring (reference parameter_manager.h:211-217).

* DP (fused DistributedOptimizer): one psum per gradient leaf, in the
  leaf's own shape; total psum bytes == total gradient bytes, plus scalar
  metric reductions — nothing else.
* ZeRO-1: reduce-scatter + all-gather of the padded flat gradients, and
  NO parameter-sized flat psum (that is the whole point).
"""

# These harnesses trace full rank-programs (train steps, sharded
# attention) whose outputs are rank-varying or flow through
# grouped/scatter collectives the vma checker cannot statically
# infer — the same documented opt-out class as the spmd harness
# (docs/parallelism.md); what is pinned here is the WIRE BYTES.
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu import models
from horovod_tpu.common import state as _state

COLLECTIVES = ("psum", "psum2", "all_gather", "reduce_scatter",
               "psum_scatter", "all_to_all", "ppermute")


def collect_collectives(jaxpr):
    """[(primitive_name, operand_bytes)] over the whole jaxpr tree."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in COLLECTIVES:
                nbytes = sum(v.aval.size * v.aval.dtype.itemsize
                             for v in eqn.invars
                             if hasattr(v.aval, "size"))
                found.append((eqn.primitive.name, nbytes))
            for v in eqn.params.values():
                for item in (v if isinstance(v, (tuple, list)) else [v]):
                    if hasattr(item, "jaxpr"):
                        walk(item.jaxpr)
                    elif hasattr(item, "eqns"):
                        walk(item)

    walk(jaxpr.jaxpr)
    return found


def _trace_step(zero):
    model = models.MNISTNet()
    state, opt = models.create_train_state(
        jax.random.PRNGKey(0), model, optax.sgd(0.1, momentum=0.9),
        jnp.zeros((1, 28, 28, 1)), zero=zero)
    step = models.make_train_step(model, opt)
    spec = models.state_partition_specs(state) if zero else P()
    batch = {"image": jnp.zeros((16, 28, 28, 1)),
             "label": jnp.zeros((16,), jnp.int32)}
    tok = _state.set_spmd_axis("hvd")
    try:
        jaxpr = jax.make_jaxpr(jax.shard_map(
            step, mesh=hvd.mesh(), in_specs=(spec, P("hvd")),
            out_specs=(spec, P()), check_vma=False))(state, batch)
    finally:
        _state.reset_spmd_axis(tok)
    leaf_bytes = [l.size * 4
                  for l in jax.tree_util.tree_leaves(state["params"])]
    return collect_collectives(jaxpr), leaf_bytes


def _without_leaves(psums, leaf_bytes):
    """``psums`` less one psum of each leaf's bytes: what is left is not
    gradient traffic. Fails where a leaf has no psum of its own size."""
    rest = list(psums)
    for nbytes in leaf_bytes:
        assert nbytes in rest, (nbytes, psums)
        rest.remove(nbytes)
    return rest


def test_dp_step_moves_exactly_gradient_bytes(hvd):
    colls, leaf_bytes = _trace_step(zero=False)
    psums = [b for n, b in colls if n.startswith("psum")]
    others = [(n, b) for n, b in colls if not n.startswith("psum")]
    assert not others, f"unexpected collectives in the DP step: {others}"
    # One bucket whose members each go in their own shape: every
    # gradient byte exactly once, and beside them scalar metrics only.
    rest = _without_leaves(psums, leaf_bytes)
    assert len(rest) <= 3 and all(b <= 64 for b in rest), rest


def test_overlap_dp_step_conserves_gradient_bytes(hvd):
    """Overlap mode (fusion.py): the DP step's reduce traffic stays
    EXACTLY the gradient bytes — the reverse-order multi-bucket psums
    carry every gradient byte exactly once, each leaf in its own shape,
    and nothing goes in scatters or gathers. The issue order changes, the
    volume cannot."""
    import optax

    from horovod_tpu.jax.optimizer import DistributedOptimizer

    model = models.MNISTNet()
    state, _ = models.create_train_state(
        jax.random.PRNGKey(0), model, optax.sgd(0.1, momentum=0.9),
        jnp.zeros((1, 28, 28, 1)))
    # Rewrap with a 64 KB threshold (multi-bucket plan) + overlap on.
    opt = DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                               fusion_threshold=64 * 1024, overlap="on")
    state["opt_state"] = opt.init(state["params"])
    step = models.make_train_step(model, opt)
    batch = {"image": jnp.zeros((16, 28, 28, 1)),
             "label": jnp.zeros((16,), jnp.int32)}
    tok = _state.set_spmd_axis("hvd")
    try:
        jaxpr = jax.make_jaxpr(jax.shard_map(
            step, mesh=hvd.mesh(), in_specs=(P(), P("hvd")),
            out_specs=(P(), P()), check_vma=False))(state, batch)
    finally:
        _state.reset_spmd_axis(tok)
    leaf_bytes = [l.size * 4
                  for l in jax.tree_util.tree_leaves(state["params"])]
    colls = collect_collectives(jaxpr)
    rest = _without_leaves(
        [b for n, b in colls if n.startswith("psum")], leaf_bytes)
    assert len(rest) <= 3 and all(b <= 64 for b in rest), rest
    others = [(n, b) for n, b in colls if not n.startswith("psum")]
    assert not others, f"scatters or gathers in the flat exchange: {others}"


@pytest.mark.parametrize("inner,comp_name", [(4, "none"), (4, "int8"),
                                             (2, "int8")])
def test_hierarchical_dp_step_wire_bytes(hvd, inner, comp_name):
    """Hierarchical path (fusion.py, PR-10): per-leg bytes of the DP
    step's exchange. The intra-slice rs carries the inner-padded
    buckets and its all-gather the 1/inner shards; the inter-slice
    (DCN) leg carries exactly the shard bytes — divided by ~4 again
    under int8 (quantized payloads + 4 B scales) — and the whole split
    must agree with fusion.hier_wire_summary, so that summary is
    checkable against the traced schedule."""
    import optax

    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.jax.fusion import (
        hier_wire_summary,
        plan_buckets,
    )
    from horovod_tpu.jax.optimizer import DistributedOptimizer

    comp = getattr(hvd_jax.Compression, comp_name)
    model = models.MNISTNet()
    state, _ = models.create_train_state(
        jax.random.PRNGKey(0), model, optax.sgd(0.1, momentum=0.9),
        jnp.zeros((1, 28, 28, 1)))
    opt = DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                               fusion_threshold=64 * 1024,
                               hierarchical="on", compression=comp)
    st = _state.global_state()
    saved = st.config.hierarchical_inner_size
    st.config.hierarchical_inner_size = inner
    try:
        state["opt_state"] = opt.init(state["params"])
        spec = models.state_partition_specs(state)
        step = models.make_train_step(model, opt)
        batch = {"image": jnp.zeros((16, 28, 28, 1)),
                 "label": jnp.zeros((16,), jnp.int32)}
        tok = _state.set_spmd_axis("hvd")
        try:
            jaxpr = jax.make_jaxpr(jax.shard_map(
                step, mesh=hvd.mesh(), in_specs=(spec, P("hvd")),
                out_specs=(spec, P()), check_vma=False))(state, batch)
        finally:
            _state.reset_spmd_axis(tok)
    finally:
        st.config.hierarchical_inner_size = saved
    leaves = jax.tree_util.tree_leaves(state["params"])
    plan = plan_buckets(leaves, 64 * 1024)
    expect = hier_wire_summary(plan, 8, inner, comp)
    colls = collect_collectives(jaxpr)
    # The flat parameter-sized psum must be GONE (metric scalars stay).
    big_psums = [b for n, b in colls if n.startswith("psum") and b > 64]
    rs = sum(b for n, b in colls
             if n in ("reduce_scatter", "psum_scatter"))
    ag = sum(b for n, b in colls if n == "all_gather")
    a2a = sum(b for n, b in colls if n == "all_to_all")
    grad_bytes = sum(l.size * 4 for l in leaves)
    assert grad_bytes <= rs <= grad_bytes + 8 * inner * 4 * len(plan)
    if comp_name == "none":
        # DCN leg = shard psums (payload = padded/inner each).
        dcn = sum(b for b in big_psums)
        assert dcn == expect["dcn_bytes"], (dcn, expect)
        assert rs + ag + dcn == (expect["ici_bytes"]
                                 + expect["dcn_bytes"])
        assert not a2a
    else:
        # DCN leg = quantized payloads + scale scalars; nothing
        # gradient-sized psums anymore.
        assert not big_psums, big_psums
        int8_bytes = sum(b for n, b in colls
                         if n in ("all_gather", "all_to_all"))
        # Everything on the wire reconciles with the static stamp.
        assert rs + int8_bytes == (expect["ici_bytes"]
                                   + expect["dcn_bytes"]), (
            rs, int8_bytes, expect)
    # The headline property: DCN bytes <= 1/inner of the flat psum
    # bytes, and /4 again (up to scale scalars) under int8.
    assert expect["dcn_bytes"] <= grad_bytes / inner + 8 * 4 * len(plan)
    if comp_name == "int8":
        assert expect["dcn_bytes"] < grad_bytes / inner / 2


def test_zero_step_reduce_scatters_instead_of_allreducing(hvd):
    colls, leaf_bytes = _trace_step(zero=True)
    grad_bytes = sum(leaf_bytes)
    names = {n for n, _ in colls}
    assert names & {"reduce_scatter", "psum_scatter"}, names
    assert "all_gather" in names, names
    # The flat parameter-sized allreduce must be GONE (scalars remain).
    big_psums = [b for n, b in colls
                 if n.startswith("psum") and b > 64]
    assert not big_psums, big_psums
    # Scatter + gather each carry the padded flat gradients (>= the raw
    # gradient bytes, < 2x from padding on this tiny model).
    rs = sum(b for n, b in colls if n in ("reduce_scatter", "psum_scatter"))
    ag = sum(b for n, b in colls if n == "all_gather")
    assert grad_bytes <= rs < 2 * grad_bytes, (rs, grad_bytes)
    assert ag >= grad_bytes // 8, (ag, grad_bytes)  # gather of shards


def test_ring_attention_rotates_exactly_local_kv_bytes(hvd):
    """Long-context claim (docs/parallelism.md): ring attention's per-
    rotation wire traffic is the LOCAL K/V block — constant per chip as
    context grows with the mesh — and nothing else crosses the wire."""
    import horovod_tpu.parallel as par

    mesh = par.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, L_local, H, D = 2, 8, 2, 4
    q = jnp.zeros((B, 4 * L_local, H, D))
    jaxpr = jax.make_jaxpr(jax.shard_map(
        lambda q, k, v: par.ring_attention(q, k, v, axis="sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))(q, q, q)
    colls = collect_collectives(jaxpr)
    names = {n for n, _ in colls}
    assert names == {"ppermute"}, colls
    kv_local = 2 * B * L_local * H * D * 4  # K and V blocks, fp32
    # The scan body appears once in the jaxpr: its two ppermutes (K, V)
    # together carry exactly the local blocks each rotation.
    assert sum(b for _, b in colls) == kv_local, (colls, kv_local)


def test_tp_mlp_one_psum_of_activation_bytes(hvd):
    """Megatron MLP claim (parallel/tp.py): column-parallel up costs no
    comm; the whole block's wire traffic is ONE psum of the activation."""
    import horovod_tpu.parallel as par

    mesh = par.make_mesh({"tp": 4}, devices=jax.devices()[:4])
    B, L, E, F = 2, 8, 16, 32
    args = (jnp.zeros((B, L, E)), jnp.zeros((E, F)), jnp.zeros((F,)),
            jnp.zeros((F, E)), jnp.zeros((E,)))
    jx = jax.make_jaxpr(jax.shard_map(
        lambda x, wu, bu, wd, bd: par.tp_mlp(x, wu, bu, wd, bd, axis="tp"),
        mesh=mesh,
        in_specs=(P(), P(None, "tp"), P("tp"), P("tp", None), P()),
        out_specs=P(), check_vma=False))(*args)
    colls = collect_collectives(jx)
    assert colls == [("psum", B * L * E * 4)], colls


def test_ulysses_four_alltoalls_of_local_tensor_bytes(hvd):
    """Ulysses SP: exactly four all_to_alls (q, k, v in; output back),
    each carrying one local [B, L/P, H, D] tensor."""
    import horovod_tpu.parallel as par

    mesh = par.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    B, L_local, H, D = 2, 8, 4, 8
    q = jnp.zeros((B, 4 * L_local, H, D))
    jx = jax.make_jaxpr(jax.shard_map(
        lambda q, k, v: par.ulysses_attention(q, k, v, axis="sp",
                                              causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))(q, q, q)
    colls = collect_collectives(jx)
    tensor = B * L_local * H * D * 4
    assert colls == [("all_to_all", tensor)] * 4, (colls, tensor)


def test_expert_shares_add_up_with_one_allreduce_of_token_bytes(hvd):
    """The expert layer sharded over an axis (every chip routes all the
    tokens and computes its own experts' share): the only wire traffic is
    the one all-reduce that adds the shares up, of the tokens' bytes: no
    capacity-bounded slots, nothing dropped."""
    import horovod_tpu.parallel as par

    mesh = par.make_mesh({"ep": 4}, devices=jax.devices()[:4])
    T, D, F, experts = 16, 8, 16, 8

    def share(x, router, held):
        y, _ = par.routed_experts(x, router, held,
                                  first=jax.lax.axis_index("ep") * 2,
                                  top_k=2)
        return jax.lax.psum(y, "ep")

    stacked = {"gate": P("ep"), "up": P("ep"), "down": P("ep")}
    jx = jax.make_jaxpr(jax.shard_map(
        share, mesh=mesh, in_specs=(P(), P(), stacked), out_specs=P(),
        check_vma=False))(
        jnp.zeros((T, D)), jnp.zeros((D, experts)),
        {"gate": jnp.zeros((experts, D, F)), "up": jnp.zeros((experts, D, F)),
         "down": jnp.zeros((experts, F, D))})
    assert collect_collectives(jx) == [("psum", T * D * 4)]


def test_static_audit_matches_dynamic_accounting(hvd):
    """hvdverify cross-check (docs/static_analysis.md): the schedule
    walker behind ``audit_collectives`` and HVV105 must
    agree EXACTLY — per-op count and payload bytes — with this file's
    independent dynamic jaxpr accounting, on both step shapes it pins
    (fused DP and ZeRO-1). Two walkers, two authors, one jaxpr: any
    divergence means one of the two audits is lying about the wire."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from tools.hvdverify.schedule import ScheduleWalker, summarize

    model = models.MNISTNet()
    for zero in (False, True):
        state, opt = models.create_train_state(
            jax.random.PRNGKey(0), model, optax.sgd(0.1, momentum=0.9),
            jnp.zeros((1, 28, 28, 1)), zero=zero)
        step = models.make_train_step(model, opt)
        spec = models.state_partition_specs(state) if zero else P()
        batch = {"image": jnp.zeros((16, 28, 28, 1)),
                 "label": jnp.zeros((16,), jnp.int32)}
        tok = _state.set_spmd_axis("hvd")
        try:
            jaxpr = jax.make_jaxpr(jax.shard_map(
                step, mesh=hvd.mesh(), in_specs=(spec, P("hvd")),
                out_specs=(spec, P()), check_vma=False))(state, batch)
        finally:
            _state.reset_spmd_axis(tok)
        dynamic = collect_collectives(jaxpr)
        walker = ScheduleWalker().walk(jaxpr)
        static = [(op.kind, op.payload_bytes) for op in walker.schedule]
        assert sorted(static) == sorted(dynamic), (zero, static, dynamic)
        # No scan in these steps, so the summary (what
        # audit_collectives returns) is the plain sum of the dynamic walk.
        summary = summarize(walker.schedule)
        assert summary["count"] == len(dynamic)
        assert summary["bytes"] == sum(b for _, b in dynamic)


def test_pipeline_hops_one_microbatch_per_tick(hvd):
    """GPipe claim (parallel/pipeline.py): each tick ppermutes ONE
    microbatch activation to the next stage; the only other traffic is
    the final broadcast of the assembled outputs."""
    import horovod_tpu.parallel as par

    mesh = par.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    D, M, Bm = 8, 6, 2
    ws = jnp.zeros((4, D, D))
    x = jnp.zeros((M, Bm, D))
    jx = jax.make_jaxpr(jax.shard_map(
        lambda ws, x: par.pipeline_apply(
            lambda w, a: jnp.tanh(a @ w), ws, x, "pp"),
        mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False))(ws, x)
    colls = collect_collectives(jx)
    micro = Bm * D * 4
    assert colls == [("ppermute", micro), ("psum", M * Bm * D * 4)], (
        colls, micro)
