"""Backward-overlapped bucketed collectives (horovod_tpu/jax/fusion.py):
the overlap knob changes DISPATCH SHAPE — issue order, start-all/
unpack-later — and NEVER numerics; on the flat path every member is
reduced in its own shape, whatever the knob says. Pinned
bit-exactly over the 8-chip virtual mesh with closed-form integer-valued
tensors (any cross-rank summation order is exact, so a single differing
bit means a real semantic change, not float noise), across bucket counts
including oversize singletons, both reduction ops, wire compression, and
the full DistributedOptimizer/train-step wiring.
"""

import json
import math
import re

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.common import state as _state
from horovod_tpu.common.exceptions import InvalidArgumentError
from horovod_tpu.jax.fusion import (
    fused_reduce,
    plan_buckets,
    plan_summary,
    resolve_overlap,
)

# Shapes chosen so thresholds carve distinct plans: 33*4=132 B, 7*5*4=140,
# 101*4=404 (an oversize singleton below threshold 400), 64*4=256, 257*4=1028.
_SHAPES = [(33,), (7, 5), (101,), (4, 4, 4), (257,)]


def _bases(seed=0):
    rng = np.random.RandomState(seed)
    return [np.asarray(rng.randint(-8, 8, size=s), np.float32)
            for s in _SHAPES]


def _run(bases, overlap, threshold, average, compression=None):
    comp = compression or hvd.Compression.none

    def fn():
        ts = [b * (hvd.rank() + 1).astype(b.dtype) for b in bases]
        return tuple(fused_reduce(ts, average=average,
                                  compression=comp,
                                  fusion_threshold=threshold,
                                  overlap=overlap))

    return [np.asarray(o) for o in hvd.spmd_run(fn)]


# threshold 10**9 -> one bucket; 400 -> several incl. an oversize
# singleton (404 B > 400); 64 -> every tensor its own bucket.
@pytest.mark.parametrize("threshold", [10**9, 400, 64])
@pytest.mark.parametrize("average", [False, True])
def test_overlapped_matches_sequential_bitexact(hvd, threshold, average):
    bases = _bases()
    ref = _run(bases, "off", threshold, average)
    for overlap in ("on", "auto"):
        got = _run(bases, overlap, threshold, average)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(r, g)


def test_overlap_bitexact_under_wire_compression(hvd):
    # fp16 wire: each leaf is cast, reduced in its own shape and cast
    # back; the division stays at the decompressed tail, so both modes
    # share one reduction + division sequence exactly.
    bases = _bases(seed=1)
    ref = _run(bases, "off", 400, True, compression=hvd.Compression.fp16)
    got = _run(bases, "on", 400, True, compression=hvd.Compression.fp16)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


def test_overlap_bitexact_mixed_dtypes_and_min(hvd):
    rng = np.random.RandomState(2)
    bases = [np.asarray(rng.randint(0, 9, (13,)), np.float32),
             np.asarray(rng.randint(0, 9, (6,)), np.int32),
             np.asarray(rng.randint(0, 9, (50,)), np.float32)]
    ref = _run(bases, "off", 128, False)
    got = _run(bases, "on", 128, False)
    for r, g in zip(ref, got):
        assert r.dtype == g.dtype
        np.testing.assert_array_equal(r, g)

    # Min and Product reduce a bucket's members in place like Sum (a
    # multi-member float bucket at this threshold): overlap mode must
    # produce the identical result, and the right one.
    def fn(overlap, op):
        def inner():
            ts = [b * (hvd.rank() + 1).astype(b.dtype) for b in bases]
            return tuple(fused_reduce(ts, op=op, fusion_threshold=10**6,
                                      overlap=overlap))
        return [np.asarray(o) for o in hvd.spmd_run(inner)]

    for op in (hvd.Min, hvd.Product):
        for r, g in zip(fn("off", op), fn("on", op)):
            np.testing.assert_array_equal(r, g)
    for b, g in zip(bases, fn("on", hvd.Min)):
        np.testing.assert_array_equal(b, g)        # rank 0 holds the least
    for b, g in zip(bases[:1], fn("on", hvd.Product)):
        np.testing.assert_allclose(
            b.astype(np.float64) ** hvd.size()
            * math.factorial(hvd.size()), g, rtol=1e-6)


def _eqns(jaxpr, names):
    """Every equation of ``jaxpr`` (nested programs included) whose
    primitive is one of ``names``, in program order."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in names:
                found.append(eqn)
            for v in eqn.params.values():
                for item in (v if isinstance(v, (tuple, list)) else [v]):
                    if hasattr(item, "jaxpr"):
                        walk(item.jaxpr)
                    elif hasattr(item, "eqns"):
                        walk(item)

    walk(jaxpr.jaxpr)
    return found


def _collect(jaxpr, names):
    return [(eqn.primitive.name,
             sum(v.aval.size * v.aval.dtype.itemsize
                 for v in eqn.invars if hasattr(v.aval, "size")))
            for eqn in _eqns(jaxpr, names)]


def _trace(overlap, threshold):
    import jax

    bases = _bases()

    def fn():
        ts = [np.asarray(b) * (hvd.rank() + 1).astype(np.float32)
              for b in bases]
        return tuple(fused_reduce(ts, average=False,
                                  fusion_threshold=threshold,
                                  overlap=overlap))

    tok = _state.set_spmd_axis("hvd")
    try:
        return jax.make_jaxpr(jax.shard_map(
            fn, mesh=hvd.mesh(), in_specs=(), out_specs=(P(),) * len(bases),
            check_vma=False))()
    finally:
        _state.reset_spmd_axis(tok)


_PACKING = {"concatenate", "pad", "psum_scatter", "reduce_scatter",
            "all_gather"}


def test_shaped_wire_shape(hvd):
    """The flat path's wire shape: a multi-member bucket traces to one
    psum a member under the bucket's scope, each in its member's own
    shape, and nothing packs, scatters or gathers anywhere."""
    jx = _trace("on", 10**9)                # one bucket of all five
    assert not _eqns(jx, _PACKING), _eqns(jx, _PACKING)
    psums = _eqns(jx, {"psum", "psum2"})
    assert [tuple(e.invars[0].aval.shape) for e in psums] == _SHAPES
    assert all(len(e.invars) == 1 and "hvd_allreduce_fused_float32_b0"
               in str(e.source_info.name_stack) for e in psums), psums


def test_overlap_auto_single_bucket_keeps_issue_order(hvd):
    """auto with a one-bucket plan = the sequential emission (nothing to
    interleave): the members' psums in input order, as under "off"."""
    auto, off = _trace("auto", 10**9), _trace("off", 10**9)
    assert not _eqns(auto, _PACKING)
    assert (_collect(auto, {"psum", "psum2"})
            == _collect(off, {"psum", "psum2"})
            == [("psum", int(np.prod(s)) * 4) for s in _SHAPES])


def test_overlap_issues_buckets_in_reverse_order(hvd):
    """The tentpole's schedule: under overlap the FIRST collective in
    program order is the LAST bucket's (the gradients backward produces
    first), so XLA's async scheduler gets each start next to its
    producers. threshold 400 makes per-bucket byte sizes distinct."""
    def bucket_bytes(jx):
        # Payload bytes by bucket scope, in order of first issue.
        sizes = {}
        for e in _eqns(jx, {"psum", "psum2"}):
            scope = [p for p in str(e.source_info.name_stack).split("/")
                     if p.startswith("hvd_allreduce_")]
            sizes[scope[0]] = (sizes.get(scope[0], 0)
                               + e.invars[0].aval.size * 4)
        return list(sizes.values())

    sizes_off = bucket_bytes(_trace("off", 400))
    sizes_on = bucket_bytes(_trace("on", 400))
    assert len(sizes_off) >= 3 and len(set(sizes_off)) == len(sizes_off)
    assert sizes_on == list(reversed(sizes_off)), (sizes_off, sizes_on)


def test_overlap_knob_validation(hvd):
    with pytest.raises(InvalidArgumentError):
        _run(_bases(), "bogus", 400, True)


def test_resolve_overlap_semantics(hvd):
    assert resolve_overlap("off", 99) is False
    assert resolve_overlap("on", 1) is True
    assert resolve_overlap("auto", 1) is False
    assert resolve_overlap("auto", 2) is True
    # bool spellings normalize; None reads the config default (auto).
    assert resolve_overlap(True, 1) is True
    assert resolve_overlap(False, 9) is False
    assert resolve_overlap(None, 2) is True
    with pytest.raises(InvalidArgumentError):
        resolve_overlap("sometimes", 2)


def test_plan_buckets_accounting(hvd):
    import jax.numpy as jnp

    leaves = [jnp.zeros((100,)), jnp.zeros((50,)), jnp.zeros((500,)),
              jnp.zeros((8,), jnp.int32)]
    plan = plan_buckets(leaves, 600)
    # f32 group: [100, 50] pack (600 B), 500 alone (2000 B, oversize);
    # i32 group: its own bucket.
    assert [(b.dtype, b.members, b.nbytes, b.oversize) for b in plan] == [
        ("float32", (0, 1), 600, False),
        ("float32", (2,), 2000, True),
        ("int32", (3,), 32, False),
    ]
    assert plan_summary(plan) == {
        "count": 3, "total_bytes": 2632, "total_mb": 0.0,
        "oversize_singletons": 1, "largest_bytes": 2000,
    }


def test_distributed_optimizer_overlap_bitexact(hvd):
    """The full user wiring: create_train_state(overlap=...) ->
    DistributedOptimizer -> fused_reduce. One SPMD training step's
    parameters must be BIT-identical across overlap modes (multi-bucket
    plan via a tiny fusion threshold; integer-valued data keeps every
    reduction order exact)."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models

    def step_params(overlap):
        model = models.MNISTNet()
        state, opt = models.create_train_state(
            jax.random.PRNGKey(0), model, optax.sgd(0.125, momentum=0.5),
            jnp.zeros((1, 28, 28, 1)), overlap=overlap)
        # ~450 KB of MNIST params over a 4 KB threshold -> a many-bucket
        # plan, so the reverse-order issue path really runs.
        from horovod_tpu.jax.optimizer import DistributedOptimizer

        opt = DistributedOptimizer(optax.sgd(0.125, momentum=0.5),
                                   fusion_threshold=4096, overlap=overlap)
        state["opt_state"] = opt.init(state["params"])
        step = models.make_train_step(model, opt, average_loss=False)
        rng = np.random.RandomState(3)
        batch = {"image": jnp.asarray(
            rng.randint(0, 2, (16, 28, 28, 1)), jnp.float32),
            "label": jnp.asarray(rng.randint(0, 10, (16,)))}
        new_state, _ = hvd.spmd_run(step, state, batch,
                                    in_specs=(P(), P("hvd")),
                                    out_specs=(P(), P()))
        return jax.tree_util.tree_leaves(new_state["params"])

    ref = step_params("off")
    for mode in ("on", "auto"):
        got = step_params(mode)
        assert len(ref) == len(got)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


def test_timeline_marks_in_flight_buckets(hvd, tmp_path):
    """Per-in-flight-bucket observability: under overlap each bucket's
    ALLREDUCE span opens at issue (args carry issue order + in-flight
    count + path); on the flat path nothing is packed, so no MEMCPY or
    REDUCESCATTER / ALLGATHER activity appears inside it."""
    from horovod_tpu.utils.timeline import Timeline

    st = _state.global_state()
    trace = tmp_path / "overlap_trace.json"
    saved = st.timeline
    st.timeline = Timeline(str(trace))
    try:
        _run(_bases(), "on", 400, True)
    finally:
        st.timeline.close()
        st.timeline = saved
    events = json.loads(trace.read_text().rstrip().rstrip(",\n") + "]")
    starts = [e for e in events
              if e.get("name") == "ALLREDUCE" and e["ph"] == "B"]
    assert starts, events
    issues = sorted(e["args"]["issue"] for e in starts)
    assert issues == list(range(len(starts)))
    assert all(e["args"]["overlap"] for e in starts)
    assert all(e["args"]["in_flight"] == e["args"]["issue"] + 1
               for e in starts)
    assert {"psum"} == {e["args"]["path"] for e in starts}
    names = {e.get("name") for e in events}
    assert not names & {"REDUCESCATTER", "ALLGATHER",
                        "MEMCPY_IN_FUSION_BUFFER",
                        "MEMCPY_OUT_FUSION_BUFFER"}, names
    # Every span closes.
    ends = [e for e in events if e["ph"] == "E"]
    assert len(ends) >= len(starts)


def test_distributed_optimizer_step_lowers_without_concatenating_gradients(
        hvd):
    """A DistributedOptimizer step over a tree with tiled 2-D leaves lowers
    to one all-reduce a gradient leaf, in the leaf's own shape, and to no
    concatenate at all: no gradient byte goes through a flat buffer."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.jax.optimizer import DistributedOptimizer

    params = {"w1": jnp.ones((256, 512)), "b1": jnp.zeros((512,)),
              "w2": jnp.ones((512, 128)), "scale": jnp.ones((128,))}
    opt = DistributedOptimizer(optax.adam(1e-3))
    opt_state = opt.init(params)

    def loss(p, x):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"]) * p["scale"])

    def step(p, s, x):
        updates, s = opt.update(jax.grad(loss)(p, x), s, p)
        return optax.apply_updates(p, updates), s

    tok = _state.set_spmd_axis("hvd")
    try:
        text = jax.jit(jax.shard_map(
            step, mesh=hvd.mesh(), in_specs=(P(), P(), P("hvd")),
            out_specs=(P(), P()), check_vma=False)).lower(
                params, opt_state, jnp.ones((16, 256))).as_text()
    finally:
        _state.reset_spmd_axis(tok)
    assert "concatenate" not in text
    shapes = re.findall(
        r'"stablehlo\.all_reduce"\(%[^)]*\).*?\}\) : \(tensor<([0-9x]+)xf32>\)',
        text, flags=re.S)
    assert sorted(shapes) == ["128", "256x512", "512", "512x128"], shapes
