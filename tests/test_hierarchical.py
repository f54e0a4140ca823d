"""Hierarchical bucket collectives + low-bit DCN wire compression
(horovod_tpu/jax/fusion.py, HOROVOD_HIERARCHICAL): the ladder changes
WIRE SHAPE — intra-slice reduce-scatter, inter-slice exchange of the
1/inner shard (optionally int8/fp8-quantized with error feedback),
intra-slice all-gather — and, for ``Compression.none``, NEVER numerics:
pinned bit-exactly against the flat psum over the 8-chip virtual mesh
with integer-valued tensors (every summation order exact), at both DCN
exchange shapes (inner 4 -> 2 slices, all-gather exchange; inner 2 ->
4 slices, two-stage all-to-all). The quantized wire is pinned three
ways: exactly on quantization-grid data (the Average no-double-scaling
contract from the fusion.py dtype-ladder table), within tolerance on
random data, and by an error-feedback convergence run on a small LM
(quantized-DP loss trajectory near fp32 DP and strictly better than
feedback-free quantization).
"""

import contextlib

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.common import state as _state
from horovod_tpu.common.exceptions import InvalidArgumentError
from horovod_tpu.jax.fusion import (
    ef_residual_specs,
    fused_reduce,
    hier_bucket_layout,
    hier_wire_summary,
    plan_buckets,
    resolve_hierarchical,
)

_SHAPES = [(33,), (7, 5), (101,), (4, 4, 4), (257,)]
_THRESHOLD = 400  # multi-bucket plan incl. an oversize singleton


@contextlib.contextmanager
def _inner_size(inner):
    st = _state.global_state()
    saved = st.config.hierarchical_inner_size
    st.config.hierarchical_inner_size = inner
    try:
        yield
    finally:
        st.config.hierarchical_inner_size = saved


@contextlib.contextmanager
def _config_mode(mode):
    """Pin the HOROVOD_HIERARCHICAL tri-state default for assertions on
    mode=None resolution (another test file may have left a non-default
    value behind — e.g. the autotuner legitimately applies its winner
    to the live config)."""
    st = _state.global_state()
    saved = st.config.hierarchical
    st.config.hierarchical = mode
    try:
        yield
    finally:
        st.config.hierarchical = saved


def _bases(seed=0, lo=-8, hi=8):
    rng = np.random.RandomState(seed)
    return [np.asarray(rng.randint(lo, hi, size=s), np.float32)
            for s in _SHAPES]


def _run(bases, *, hierarchical, inner, overlap="off", average=True,
         compression=None, threshold=_THRESHOLD):
    comp = compression or hvd.Compression.none

    def fn():
        ts = [b * (hvd.rank() + 1).astype(b.dtype) for b in bases]
        return tuple(fused_reduce(ts, average=average, compression=comp,
                                  fusion_threshold=threshold,
                                  overlap=overlap,
                                  hierarchical=hierarchical))

    with _inner_size(inner):
        return [np.asarray(o) for o in hvd.spmd_run(fn)]


# ------------------------------------------------- flat-vs-hier exactness


@pytest.mark.parametrize("inner", [4, 2])
@pytest.mark.parametrize("overlap", ["off", "on"])
@pytest.mark.parametrize("average", [False, True])
def test_hier_matches_flat_bitexact(hvd, inner, overlap, average):
    """Compression.none: the hierarchical ladder is a wire-shape change
    only — bit-identical to the flat psum at every inner size and
    overlap mode (integer-valued tensors make every summation order
    exact, so one differing bit is a semantic change)."""
    bases = _bases()
    ref = _run(bases, hierarchical="off", inner=0, average=average)
    got = _run(bases, hierarchical="on", inner=inner, overlap=overlap,
               average=average)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


def test_hier_cast_compression_bitexact(hvd):
    """fp16 wire rides the ladder unchanged: the whole bucket is fp16 on
    every leg and the 1/n divide stays at the decompressed tail (dtype
    ladder table, fusion.py) — hier on/off share one reduction +
    division sequence exactly."""
    bases = _bases(seed=1)
    ref = _run(bases, hierarchical="off", inner=0,
               compression=hvd.Compression.fp16)
    got = _run(bases, hierarchical="on", inner=4,
               compression=hvd.Compression.fp16)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


def test_hier_min_falls_back_to_flat(hvd):
    """Min/Max have no scatter primitive: hierarchical mode must still
    produce the identical flat-path result."""
    bases = _bases(seed=2, lo=0, hi=9)

    def fn(hierarchical, inner):
        def inner_fn():
            ts = [b * (hvd.rank() + 1).astype(b.dtype) for b in bases]
            return tuple(fused_reduce(ts, op=hvd.Min,
                                      fusion_threshold=_THRESHOLD,
                                      hierarchical=hierarchical))
        with _inner_size(inner):
            return [np.asarray(o) for o in hvd.spmd_run(inner_fn)]

    for r, g in zip(fn("off", 0), fn("on", 4)):
        np.testing.assert_array_equal(r, g)


# ------------------------------------------------------- knob resolution


def test_resolve_hierarchical_semantics(hvd):
    st = _state.global_state()
    assert resolve_hierarchical("off", 8) == 0
    with _inner_size(4):
        assert resolve_hierarchical("on", 8) == 4
        assert resolve_hierarchical(True, 8) == 4
        assert resolve_hierarchical(False, 8) == 0
        # inner must strictly divide (1 < inner < axis): degrade to flat.
        assert resolve_hierarchical("on", 4) == 0
    with _inner_size(3):
        assert resolve_hierarchical("on", 8) == 0
    # auto keys off a DCN boundary; the CPU harness is one process ->
    # flat, even with an explicit inner size.
    from horovod_tpu.parallel.mesh import dcn_present

    assert not dcn_present(st.devices)
    assert resolve_hierarchical("auto", 8) == 0
    with _inner_size(4):
        assert resolve_hierarchical("auto", 8) == 0
    with _config_mode("auto"):
        assert resolve_hierarchical(None, 8) == 0  # config default
    # The legacy boolean spelling is an explicit opt-in: it forces the
    # ladder over any tri-state default.
    saved = st.config.hierarchical_allreduce
    st.config.hierarchical_allreduce = True
    try:
        with _inner_size(2):
            for ambient in ("auto", "off"):
                with _config_mode(ambient):
                    assert resolve_hierarchical(None, 8) == 2
    finally:
        st.config.hierarchical_allreduce = saved
    with pytest.raises(InvalidArgumentError):
        resolve_hierarchical("sometimes", 8)


class _FakeDev:
    """Minimal device stand-in for topology-detection tests (the CPU
    harness cannot fabricate multi-slice/ragged device sets)."""

    def __init__(self, i, process_index=0, slice_index=None):
        self.id = i
        self.process_index = process_index
        self.slice_index = slice_index


def test_auto_degrades_flat_on_heterogeneous_topology(hvd):
    """Default auto mode on a RAGGED chips-per-domain layout (3+5): no
    valid ladder tiling exists, so resolve must degrade to flat (the
    reference's is_homogeneous rule) instead of raising out of every
    DistributedOptimizer trace."""
    st = _state.global_state()
    ragged = ([_FakeDev(i, process_index=0) for i in range(3)]
              + [_FakeDev(3 + i, process_index=1) for i in range(5)])
    from horovod_tpu.parallel.mesh import dcn_present

    assert dcn_present(ragged)  # heterogeneous counts as multi-domain
    saved = st.devices
    st.devices = ragged
    try:
        with _config_mode("auto"):
            assert resolve_hierarchical("auto", 8) == 0
            assert resolve_hierarchical(None, 8) == 0
            # An explicit inner size still engages (the escape hatch).
            with _inner_size(4):
                assert resolve_hierarchical("auto", 8) == 4
    finally:
        st.devices = saved


def test_auto_engages_on_multi_slice_topology(hvd):
    """Default auto mode on a clean 2-slice x 4-chip set resolves to
    the detected chips-per-slice — the zero-config multi-slice story."""
    st = _state.global_state()
    slices = [_FakeDev(i, slice_index=i // 4) for i in range(8)]
    saved = st.devices
    st.devices = slices
    try:
        with _config_mode("auto"):
            assert resolve_hierarchical("auto", 8) == 4
            assert resolve_hierarchical(None, 8) == 4
    finally:
        st.devices = saved


def test_hybrid_mesh_rejects_ici_axis_spanning_slices(hvd):
    """hybrid_mesh contract: on a REAL multi-slice device set, ICI axes
    must tile exactly one slice — an ICI product crossing the DCN
    boundary (which would run the ladder's 'fast' legs over the slow
    fabric) raises instead of silently building. Single-domain sets
    (the CPU virtual testing path) may factor freely."""
    from horovod_tpu.parallel.mesh import hybrid_mesh

    two_slices = [_FakeDev(i, slice_index=i // 2) for i in range(4)]
    with pytest.raises(InvalidArgumentError, match="DCN boundary"):
        hybrid_mesh(ici_axes={"ici": 4}, dcn_axes={"dcn": 1},
                    devices=two_slices)
    mesh = hybrid_mesh(devices=two_slices)  # detected 2x2 builds
    assert mesh.devices.shape == (2, 2)
    assert mesh.axis_names == ("dcn", "ici")
    # Virtual factorization of a single-domain set stays allowed.
    import jax

    mesh = hybrid_mesh(ici_axes={"ici": 2}, dcn_axes={"dcn": 4},
                       devices=list(jax.devices()))
    assert mesh.devices.shape == (4, 2)


# -------------------------------------- quantized wire: exactness pins


@pytest.mark.parametrize("inner", [4, 2])
@pytest.mark.parametrize("comp_name", ["int8", "fp8"])
def test_quantized_average_no_double_scaling(hvd, inner, comp_name):
    """The dtype-ladder contract (fusion.py satellite): int8/fp8 composes
    with Average WITHOUT double-scaling. On quantization-grid data
    (every post-reduce-scatter value in {-A, 0, +A}, one magnitude per
    shard) the absmax-scaled codec round-trips exactly, so the
    hierarchical quantized Average must BIT-match the flat fp32 Average
    — any double divide (or mis-applied scale) shows up as an 8x/128x
    error, not noise."""
    rng = np.random.RandomState(5)
    bases = [np.asarray(rng.randint(-1, 2, size=s), np.float32)
             for s in _SHAPES]
    comp = getattr(hvd.Compression, comp_name)

    def fn(hierarchical, compression, inner_sz):
        def inner_fn():
            # Every rank contributes the SAME tensor: all reduction
            # stages see a single magnitude per shard -> exact codec.
            ts = [np.asarray(b) for b in bases]
            return tuple(fused_reduce(ts, average=True,
                                      compression=compression,
                                      fusion_threshold=_THRESHOLD,
                                      hierarchical=hierarchical))
        with _inner_size(inner_sz):
            return [np.asarray(o) for o in hvd.spmd_run(inner_fn)]

    ref = fn("off", hvd.Compression.none, 0)
    got = fn("on", comp, inner)
    for b, r, g in zip(bases, ref, got):
        np.testing.assert_array_equal(r, b)  # Average of n copies = b
        np.testing.assert_array_equal(g, r)


@pytest.mark.parametrize("inner", [4, 2])
def test_int8_random_data_close_and_sum_mode(hvd, inner):
    """Random data: the quantized hierarchical result tracks the flat
    result within codec tolerance in BOTH Average and Sum modes (a
    double-scale or missed divide would be off by 8x)."""
    bases = _bases(seed=7)
    for average in (True, False):
        ref = _run(bases, hierarchical="off", inner=0, average=average)
        got = _run(bases, hierarchical="on", inner=inner, average=average,
                   compression=hvd.Compression.int8)
        for r, g in zip(ref, got):
            scale = max(1.0, float(np.max(np.abs(r))))
            assert float(np.max(np.abs(r - g))) < 0.05 * scale, (
                average, float(np.max(np.abs(r - g))), scale)


def test_quantizer_without_hier_is_lossless(hvd):
    """int8/fp8 compress only the DCN leg; with no hierarchical ladder
    engaged there is nothing to compress — the flat path must be
    bit-identical to Compression.none."""
    bases = _bases(seed=8)
    ref = _run(bases, hierarchical="off", inner=0)
    got = _run(bases, hierarchical="off", inner=0,
               compression=hvd.Compression.int8)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


# --------------------------------------------- error-feedback residuals


def _ef_run_factory(inner, comp, bases):
    import jax
    import jax.numpy as jnp

    leaves = [jax.ShapeDtypeStruct(b.shape, jnp.float32) for b in bases]
    specs = ef_residual_specs(leaves, _THRESHOLD, 8, inner)
    res0 = tuple(jnp.zeros(s.shape, s.dtype) for s in specs)
    res_spec = tuple(P("hvd") for _ in res0)

    def step(res):
        ts = [jnp.asarray(b) * (hvd.rank() + 1).astype(jnp.float32)
              for b in bases]
        out, new_res = fused_reduce(
            ts, average=True, compression=comp,
            fusion_threshold=_THRESHOLD, hierarchical="on",
            residuals=res)
        return tuple(out), new_res

    with _inner_size(inner):
        run = hvd.spmd_fn(step, in_specs=(res_spec,),
                          out_specs=((P(),) * len(bases), res_spec))
    return run, res0


@pytest.mark.parametrize("inner", [4, 2])
def test_error_feedback_time_average_converges(hvd, inner):
    """The EF contract (1-bit SGD / DGC): with a FIXED gradient, the
    per-step quantized output has bounded error but the running MEAN of
    outputs converges to the true average — the residual re-injects
    exactly what the wire dropped. Feedback-free quantization keeps a
    constant bias instead."""
    bases = [b * 0.37 for b in _bases(seed=9)]  # off the quant grid
    true = [sum(r + 1 for r in range(8)) / 8.0 * b for b in bases]
    run, res = _ef_run_factory(inner, hvd.Compression.int8, bases)
    with _inner_size(inner):
        acc = [np.zeros_like(b) for b in bases]
        first_err = last_err = None
        steps = 10
        for it in range(steps):
            out, res = run(res)
            for a, o in zip(acc, out):
                a += np.asarray(o)
            err = max(float(np.max(np.abs(a / (it + 1) - t)))
                      for a, t in zip(acc, true))
            if it == 0:
                first_err = err
            last_err = err
    assert last_err < 0.35 * first_err, (first_err, last_err)
    # Residuals are rank-local per-chip shards of the declared specs.
    expected = [s.shape for s in ef_residual_specs(
        [np.zeros(s, np.float32) for s in _SHAPES], _THRESHOLD, 8,
        inner)]
    assert [r.shape for r in res] == expected


def test_ef_exact_codec_leaves_zero_residual(hvd):
    """On quantization-grid data the codec round-trips exactly up to
    one ulp of the scale division (absmax/127 is not a power of two),
    so the residual (wire error in the SUM domain) must come back at
    ulp level — orders below the ~1% real quantization error — AND the
    output must bit-equal the true average: error feedback composes
    with Average without touching the result when there is no error to
    feed back."""
    rng = np.random.RandomState(11)
    bases = [np.asarray(rng.randint(-1, 2, size=s), np.float32)
             for s in _SHAPES]
    import jax
    import jax.numpy as jnp

    leaves = [jax.ShapeDtypeStruct(b.shape, jnp.float32) for b in bases]
    res0 = tuple(jnp.zeros(s.shape, s.dtype)
                 for s in ef_residual_specs(leaves, _THRESHOLD, 8, 4))
    res_spec = tuple(P("hvd") for _ in res0)

    def step(res):
        ts = [jnp.asarray(b) for b in bases]  # same on every rank
        out, new_res = fused_reduce(
            ts, average=True, compression=hvd.Compression.int8,
            fusion_threshold=_THRESHOLD, hierarchical="on",
            residuals=res)
        return tuple(out), new_res

    with _inner_size(4):
        run = hvd.spmd_fn(step, in_specs=(res_spec,),
                          out_specs=((P(),) * len(bases), res_spec))
        out, res = run(res0)
    for b, o in zip(bases, out):
        np.testing.assert_array_equal(np.asarray(o), b)
    for r in res:
        assert float(np.max(np.abs(np.asarray(r)))) < 1e-6


def test_ef_residual_structure_validation(hvd):
    """A residual tuple that does not match the plan fails loudly with
    the rebuild hint (stale after a threshold/world/inner change)."""
    bases = _bases()

    def fn():
        import jax.numpy as jnp

        ts = [jnp.asarray(b) for b in bases]
        return fused_reduce(ts, average=True,
                            compression=hvd.Compression.int8,
                            fusion_threshold=_THRESHOLD,
                            hierarchical="on",
                            residuals=(np.zeros((3,), np.float32),))[0]

    with _inner_size(4):
        with pytest.raises(InvalidArgumentError, match="ef_residual_specs"):
            hvd.spmd_run(fn)


def test_ef_residuals_with_flat_resolution_fail_loudly(hvd):
    """EF residuals present + a quantizing compressor, but the ladder
    resolves FLAT on this axis (init-world vs trace-axis drift, e.g.
    inner == axis size): silently skipping the quantized exchange would
    let fp32 flow while the user believes int8 EF is active — must
    raise with the re-init hint, not pass through."""
    import jax.numpy as jnp

    bases = _bases()

    def fn():
        ts = [jnp.asarray(b) for b in bases]
        return fused_reduce(ts, average=True,
                            compression=hvd.Compression.int8,
                            fusion_threshold=_THRESHOLD,
                            hierarchical="on",
                            residuals=(jnp.zeros((8,), jnp.float32),))[0]

    with _inner_size(8):  # inner == axis size -> ladder degrades flat
        with pytest.raises(InvalidArgumentError,
                           match="resolves to FLAT"):
            hvd.spmd_run(fn)


def test_ef_residuals_on_eager_lane_fail_loudly(hvd):
    """Multi-process eager lane (no SPMD axis): there is no
    hierarchical/quantized exchange, so EF residuals + a quantizing
    compressor must raise instead of silently allreducing full
    precision while the state says int8 is active."""
    import jax.numpy as jnp

    st = _state.global_state()
    saved = st.process_count
    st.process_count = 2
    try:
        with pytest.raises(InvalidArgumentError, match="eager lane"):
            fused_reduce([jnp.ones((4,))], average=True,
                         compression=hvd.Compression.int8,
                         hierarchical="on",
                         residuals=(jnp.zeros((2,), jnp.float32),))
    finally:
        st.process_count = saved


def test_residuals_pass_through_when_disengaged(hvd):
    """With the ladder off (or no quantizer) residuals flow through
    untouched — a caller can thread state unconditionally."""
    import jax.numpy as jnp

    bases = _bases()
    marker = (jnp.full((7,), 3.25, jnp.float32),)

    def fn():
        ts = [jnp.asarray(b) for b in bases]
        out, res = fused_reduce(ts, average=True,
                                fusion_threshold=_THRESHOLD,
                                hierarchical="off", residuals=marker)
        return tuple(out) + tuple(res)

    outs = hvd.spmd_run(fn)
    np.testing.assert_array_equal(np.asarray(outs[-1]),
                                  np.asarray(marker[0]))


# ------------------------------------ DistributedOptimizer + train step


def test_distributed_optimizer_hier_none_wiring(hvd):
    """The full user wiring at Compression.none: one SPMD training
    step's parameters with the ladder on vs off. Bit-exactness of the
    exchange itself is pinned by test_hier_matches_flat_bitexact on
    integer-valued data (where every summation order is exact); real
    model gradients are arbitrary floats and the ladder legally
    re-associates the cross-rank sum (8 = 2x4 tree vs XLA's flat
    order), so THIS pin asserts ulp-level closeness — anything beyond
    reassociation noise (a dropped shard, a double divide) is orders
    louder."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.jax.optimizer import DistributedOptimizer

    rng = np.random.RandomState(3)
    shard_img = rng.randint(0, 2, (2, 28, 28, 1)).astype(np.float32)
    shard_lab = rng.randint(0, 10, (2,))

    def step_params(hierarchical, inner):
        model = models.MNISTNet()
        state, _ = models.create_train_state(
            jax.random.PRNGKey(0), model, optax.sgd(0.125, momentum=0.5),
            jnp.zeros((1, 28, 28, 1)))
        with _inner_size(inner):
            opt = DistributedOptimizer(optax.sgd(0.125, momentum=0.5),
                                       fusion_threshold=4096,
                                       hierarchical=hierarchical)
            state["opt_state"] = opt.init(state["params"])

            def step(state, batch):
                # Deterministic eval-mode forward (no dropout): with the
                # replicated batch, every rank's gradient is identical.
                def loss_fn(params):
                    logits = model.apply(
                        {"params": params,
                         "batch_stats": state["batch_stats"]},
                        batch["image"], train=False)
                    return models.cross_entropy_loss(
                        logits, batch["label"])

                grads = jax.grad(loss_fn)(state["params"])
                return models.apply_gradients(opt, state, grads)

            batch = {"image": jnp.asarray(np.tile(shard_img, (8, 1, 1, 1))),
                     "label": jnp.asarray(np.tile(shard_lab, 8))}
            new_state = hvd.spmd_run(step, state, batch,
                                     in_specs=(P(), P("hvd")),
                                     out_specs=P())
        return jax.tree_util.tree_leaves(new_state["params"])

    ref = step_params("off", 0)
    for inner in (4, 2):
        got = step_params("on", inner)
        assert len(ref) == len(got)
        for r, g in zip(ref, got):
            np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                       rtol=1e-6, atol=1e-7)


def test_distributed_optimizer_int8_ef_state_wiring(hvd):
    """create_train_state(compression=int8, hierarchical=on) carries
    rank-local EF residuals in the optimizer state;
    state_partition_specs maps them to P("hvd"); two steps run with a
    stable state structure and the residuals become nonzero."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.jax.optimizer import _AllreduceState

    with _inner_size(4):
        model = models.MNISTNet()
        state, opt = models.create_train_state(
            jax.random.PRNGKey(0), model, optax.sgd(0.1, momentum=0.9),
            jnp.zeros((1, 28, 28, 1)),
            compression=hvd.Compression.int8, hierarchical="on")
        spec = models.state_partition_specs(state)
        step = models.make_train_step(model, opt, average_loss=False)
        rng = np.random.RandomState(3)
        batch = {"image": jnp.asarray(
            rng.rand(16, 28, 28, 1), jnp.float32),
            "label": jnp.asarray(rng.randint(0, 10, (16,)))}
        s1, _ = hvd.spmd_run(step, state, batch,
                             in_specs=(spec, P("hvd")),
                             out_specs=(spec, P()))
        s2, _ = hvd.spmd_run(step, s1, batch,
                             in_specs=(spec, P("hvd")),
                             out_specs=(spec, P()))

    def residuals_of(tree):
        found = []

        def visit(node):
            if isinstance(node, _AllreduceState):
                found.extend(node.residuals)
            return node

        jax.tree_util.tree_map(
            visit, tree,
            is_leaf=lambda n: isinstance(n, _AllreduceState))
        return found

    res0 = residuals_of(state["opt_state"])
    res2 = residuals_of(s2["opt_state"])
    assert res0 and len(res0) == len(res2)
    assert all(float(jnp.max(jnp.abs(r))) == 0 for r in res0)
    assert any(float(jnp.max(jnp.abs(r))) > 0 for r in res2)
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(s2))


# ------------------------------------------ EF convergence on a small LM


def _lm_loss_history(wire, inner, steps=24, feedback=True):
    """Train a tiny LM under DP for ``steps`` with the given DCN wire
    ("none" = fp32 flat reference); returns the loss trajectory."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models

    comp = getattr(hvd.Compression, wire)
    quantized = wire in ("int8", "fp8")
    model = models.TransformerLM(vocab_size=64, num_layers=2,
                                 num_heads=2, embed_dim=32, max_len=32)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, 16), jnp.int32)
    params = model.init(rng, sample, train=False)["params"]
    opt = optax.sgd(0.3)
    opt_state = opt.init(params)
    leaves = jax.tree_util.tree_leaves(params)
    threshold = 16 * 1024  # several buckets over the tiny LM tree
    if quantized and feedback:
        res = tuple(jnp.zeros(s.shape, s.dtype) for s in
                    ef_residual_specs(leaves, threshold, 8, inner))
    else:
        res = None

    use_ef = res is not None

    def step(params, opt_state, res, tokens):
        def loss_fn(p):
            logits = model.apply({"params": p}, tokens, train=False)
            logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            tgt = tokens[:, 1:]
            nll = -jnp.take_along_axis(logp, tgt[..., None], -1)
            return jnp.mean(nll)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        kwargs = dict(average=True, compression=comp,
                      fusion_threshold=threshold,
                      hierarchical="on" if quantized else "off")
        if use_ef:
            red, new_res = fused_reduce(g_leaves, residuals=res, **kwargs)
        else:
            red, new_res = fused_reduce(g_leaves, **kwargs), ()
        grads = jax.tree_util.tree_unflatten(treedef, red)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, new_res, hvd.allreduce(loss)

    res_spec = tuple(P("hvd") for _ in (res or ()))
    with _inner_size(inner if quantized else 0):
        run = hvd.spmd_fn(
            step,
            in_specs=(P(), P(), res_spec, P("hvd")),
            out_specs=(P(), P(), res_spec, P()))
        data_rng = np.random.RandomState(0)
        losses = []
        res_in = res if res is not None else ()
        for it in range(steps):
            tokens = jnp.asarray(
                data_rng.randint(0, 64, (16, 16)), jnp.int32)
            params, opt_state, res_in, loss = run(
                params, opt_state, res_in, tokens)
            losses.append(float(loss))
    return np.asarray(losses)


def test_ef_convergence_small_lm(hvd):
    """The convergence pin (ISSUE satellite): on a small LM under DP,
    the fp8-quantized-DCN loss trajectory with error feedback stays
    within tolerance of the fp32 trajectory, and is STRICTLY closer to
    it than feedback-free quantization — the error-feedback residual is
    what keeps low-bit wire compression from biasing training."""
    ref = _lm_loss_history("none", 0)
    ef = _lm_loss_history("fp8", 2, feedback=True)
    noef = _lm_loss_history("fp8", 2, feedback=False)
    dev_ef = float(np.mean(np.abs(ef - ref)))
    dev_noef = float(np.mean(np.abs(noef - ref)))
    # Within tolerance of fp32 DP...
    assert dev_ef < 0.05 * float(np.mean(ref)), (dev_ef, ref.mean())
    assert abs(ef[-1] - ref[-1]) < 0.05 * ref[-1], (ef[-1], ref[-1])
    # ...and strictly better than quantization without feedback.
    assert dev_ef < dev_noef, (dev_ef, dev_noef)


# -------------------------------------------------- static wire summary


def test_hier_wire_summary_accounting(hvd):
    """The per-leg byte split: operand bytes derived from
    the same hier_bucket_layout the executing path uses. DCN bytes must
    be <= 1/inner of the flat-psum bytes, and ~4x less again under
    int8."""
    import jax
    import jax.numpy as jnp

    leaves = [jax.ShapeDtypeStruct(s, jnp.float32) for s in _SHAPES]
    plan = plan_buckets(leaves, _THRESHOLD)
    flat_bytes = sum(b.nbytes for b in plan)
    for inner in (4, 2):
        none = hier_wire_summary(plan, 8, inner)
        q = hier_wire_summary(plan, 8, inner, hvd.Compression.int8)
        # Uncompressed DCN leg: exactly the (padded) shard bytes.
        assert flat_bytes / inner <= none["dcn_bytes"] \
            <= flat_bytes / inner + 8 * 4 * len(plan)
        assert none["ratio"] == 1.0 and none["dtype"] == "float32"
        # int8 leg: ~4x below that (plus scale scalars / sub-shard leg).
        assert q["dcn_bytes"] < none["dcn_bytes"] / 2
        assert q["dtype"] == "int8" and q["ratio"] > 2.5
        # ICI legs stay at the input dtype — identical up to the
        # two-stage padding quantum (inner*m elements per bucket).
        m = 8 // inner
        slack = inner * m * 4 * 2 * len(plan)
        assert none["ici_bytes"] <= q["ici_bytes"] \
            <= none["ici_bytes"] + slack


def test_hier_layout_matches_ef_specs(hvd):
    """hier_bucket_layout and ef_residual_specs agree on shard/sub
    geometry (one layout, many consumers)."""
    import jax
    import jax.numpy as jnp

    leaves = [jax.ShapeDtypeStruct(s, jnp.float32) for s in _SHAPES]
    for inner in (4, 2):
        specs = ef_residual_specs(leaves, _THRESHOLD, 8, inner)
        expect = []
        for b in plan_buckets(leaves, _THRESHOLD):
            layout = hier_bucket_layout(b.nbytes // 4, 8, inner,
                                        quantized=True)
            expect.append((8 * layout["shard_elems"],))
            if layout["two_stage"]:
                expect.append((8 * layout["sub_elems"],))
        assert [s.shape for s in specs] == expect
