"""Model zoo + training-step tests.

Ports the reference's gradient/optimizer test strategy (SURVEY §4: expected
grads compared to closed forms, test_torch.py:377-429; end-to-end DP step)
onto the 8-device virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu import models


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


def test_resnet_family_builds():
    for name in ["resnet18", "resnet34", "resnet50"]:
        m = models.build(name, num_classes=7)
        assert m.num_classes == 7
    with pytest.raises(ValueError):
        models.build("resnet99")


def test_resnet_forward_shape(rng):
    model = models.ResNet18(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(rng, x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32


def test_mnist_forward_shape(rng):
    model = models.MNISTNet()
    x = jnp.zeros((3, 28, 28, 1))
    variables = model.init(rng, x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (3, 10)


def test_train_step_single_process(hvd, rng):
    """size()==1 degradation: the same step runs eagerly under plain jit."""
    model = models.MNISTNet()
    state, opt = models.create_train_state(
        rng, model, optax.adam(1e-3), jnp.zeros((1, 28, 28, 1))
    )
    step = jax.jit(models.make_train_step(model, opt))
    batch = {
        "image": jax.random.normal(rng, (8, 28, 28, 1)),
        "label": jax.random.randint(rng, (8,), 0, 10),
    }
    losses = []
    for _ in range(10):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert int(state["step"]) == 10
    # Learns the fixed batch (dropout keeps it noisy; compare min to start).
    assert min(losses[3:]) < losses[0]
    # The read-outs are ordered before the in-place update of the donated
    # state (models.read_before_update). The hazard is XLA:TPU's; here one
    # can only check that the barrier is in the program.
    assert "optimization_barrier" in str(jax.make_jaxpr(
        models.make_train_step(model, opt))(state, batch))


def test_train_step_spmd_matches_large_batch(hvd, rng):
    """DP invariance: N ranks at batch B/N with averaged grads == 1 rank at
    batch B (the contract behind the reference's lr × size scaling advice,
    reference docs; exact for sum-based losses)."""
    model = models.MNISTNet()
    # Dropout off for determinism: eval-style apply inside a custom loss.
    state, opt = models.create_train_state(
        rng, model, optax.sgd(0.1), jnp.zeros((1, 28, 28, 1))
    )

    def loss_fn(params, batch):
        logits = model.apply(
            {"params": params, "batch_stats": state["batch_stats"]},
            batch["image"],
            train=False,
        )
        return models.cross_entropy_loss(logits, batch["label"])

    batch = {
        "image": jax.random.normal(rng, (16, 28, 28, 1)),
        "label": jax.random.randint(rng, (16,), 0, 10),
    }

    # Single-device reference grads on the full batch.
    ref_grads = jax.grad(loss_fn)(state["params"], batch)

    # SPMD: each rank grads its shard, DistributedOptimizer-style average.
    def spmd_grads(params, batch):
        g = jax.grad(loss_fn)(params, batch)
        from horovod_tpu.jax.fusion import fused_reduce

        leaves, treedef = jax.tree_util.tree_flatten(g)
        return jax.tree_util.tree_unflatten(treedef, fused_reduce(leaves, average=True))

    got = hvd.spmd_run(
        spmd_grads, state["params"], batch, in_specs=(P(), P("hvd")), out_specs=P()
    )
    for a, b in zip(jax.tree_util.tree_leaves(ref_grads), jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)


def test_full_spmd_train_step(hvd, rng):
    model = models.ResNet18(num_classes=10, dtype=jnp.float32)
    state, opt = models.create_train_state(
        rng, model, optax.sgd(0.1), jnp.zeros((1, 32, 32, 3))
    )
    step = models.make_train_step(model, opt)
    batch = {
        "image": jax.random.normal(rng, (16, 32, 32, 3)),
        "label": jax.random.randint(rng, (16,), 0, 10),
    }
    state, metrics = hvd.spmd_run(
        step, state, batch, in_specs=(P(), P("hvd")), out_specs=(P(), P())
    )
    assert int(state["step"]) == 1
    assert np.isfinite(float(metrics["loss"]))


def test_graft_entry_dryrun():
    import __graft_entry__ as g

    fn, args = g.entry()
    jax.eval_shape(fn, *args)  # traceable without a real forward


def test_graft_entry_multichip_subprocess():
    """Run the driver's multichip gate end-to-end, exactly as the driver
    does: a fresh interpreter with NO env setup, calling
    ``dryrun_multichip(8)``. The entry point must self-provision the
    8-device virtual mesh (round-1 regression: it assumed devices existed)."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8); print('MULTICHIP_OK')"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "MULTICHIP_OK" in proc.stdout


def test_graft_entry_gate_catches_broken_conjugate(hvd, monkeypatch):
    """The driver gate's closed-form asserts must catch a
    gradient-only bug: replace the Megatron ``g`` conjugate with one
    whose forward is identical (psum) but whose backward scales the
    cotangent by 1.25 — wrong in every gradient regime, invisible to a
    finite-loss check. The tp x sp x dp lane has to fail its
    dense-reference check, NOT sail through."""
    from functools import partial

    import __graft_entry__ as g
    from jax import lax

    from horovod_tpu.parallel import tp as tp_mod

    @partial(jax.custom_vjp, nondiff_argnums=(1,))
    def bad_output(x, axis):
        return lax.psum(x, axis)

    def _bad_fwd(x, axis):
        return lax.psum(x, axis), None

    def _bad_bwd(axis, _, grad):
        return (lax.pcast(grad * 1.25, axis, to="varying"),)

    bad_output.defvjp(_bad_fwd, _bad_bwd)
    monkeypatch.setattr(tp_mod, "tp_region_output", bad_output)
    with pytest.raises(AssertionError):
        g._dryrun_tp_sp_dp(8)


def test_eval_step(hvd, rng):
    model = models.MNISTNet()
    state, _ = models.create_train_state(
        rng, model, optax.sgd(0.1), jnp.zeros((1, 28, 28, 1))
    )
    ev = models.make_eval_step(model)
    batch = {
        "image": jax.random.normal(rng, (8, 28, 28, 1)),
        "label": jax.random.randint(rng, (8,), 0, 10),
    }
    out = jax.jit(ev)(state, batch)
    assert float(out["count"]) == 8.0
    assert 0 <= float(out["correct"]) <= 8


def test_bf16_momentum_tracks_fp32(hvd, rng):
    """Mixed-precision optimizer state (bench --bf16-momentum): keeping
    SGD momentum in bfloat16 halves the optimizer-state HBM traffic
    (PERF.md) and must track the fp32-momentum trajectory closely while
    the momentum leaves are actually stored in bf16."""
    model = models.MNISTNet()
    batch = {
        "image": jax.random.normal(rng, (16, 28, 28, 1)),
        "label": jax.random.randint(rng, (16,), 0, 10),
    }

    def train(accumulator_dtype):
        sgd = optax.sgd(0.05, momentum=0.9,
                        accumulator_dtype=accumulator_dtype)
        state, opt = models.create_train_state(
            rng, model, sgd, jnp.zeros((1, 28, 28, 1)))
        step = jax.jit(models.make_train_step(model, opt))
        losses = []
        for _ in range(15):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        return state, losses

    state16, losses16 = train(jnp.bfloat16)
    state32, losses32 = train(None)

    momentum_dtypes = {
        leaf.dtype.name
        for leaf in jax.tree_util.tree_leaves(state16["opt_state"])
        if hasattr(leaf, "dtype") and leaf.ndim > 0
    }
    assert "bfloat16" in momentum_dtypes, momentum_dtypes
    # Early trajectory tracks within bf16 accumulation error (later steps
    # drift chaotically through dropout + nonconvexity, in either
    # direction), and the bf16 run still learns.
    np.testing.assert_allclose(losses16[:5], losses32[:5], rtol=0.1)
    assert min(losses16[5:]) < losses16[0]
    # Params stay fp32 (only the accumulator is quantized).
    p16 = jax.tree_util.tree_leaves(state16["params"])[0]
    assert p16.dtype == jnp.float32


def test_transformer_lm_trains_with_flash_attention(rng):
    """The pallas flash kernel plugs into TransformerLM's attn_fn hook
    AND trains (its custom-VJP backward): logits, loss, and one gradient
    step must match the dense-attention model."""
    import functools

    from horovod_tpu.ops.attention import flash_attention

    flash = functools.partial(flash_attention, causal=True, block_q=8,
                              block_k=8)
    kw = dict(vocab_size=32, num_layers=2, num_heads=2, embed_dim=16,
              max_len=32, dtype=jnp.float32)
    dense_m = models.TransformerLM(**kw)
    flash_m = models.TransformerLM(attn_fn=flash, **kw)

    tokens = jax.random.randint(rng, (2, 16), 0, 32)
    params = dense_m.init(rng, tokens, train=False)["params"]

    def loss_fn(model, params):
        logits = model.apply({"params": params}, tokens, train=False)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp[:, :-1], tokens[:, 1:, None], -1))

    # Same params work in both models (attn_fn is parameter-free).
    ld, gd = jax.value_and_grad(lambda p: loss_fn(dense_m, p))(params)
    lf, gf = jax.value_and_grad(lambda p: loss_fn(flash_m, p))(params)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("width, remat", [(64, False), (64, True),
                                          (8, False)])
def test_transformer_lm_hands_attend_its_fused_projection(rng, width, remat):
    """``TransformerBlock`` hands ``attend`` (a partial of it here, as
    ``bench.py`` builds one) the fused ``qkv`` projection whole: at heads of
    64 the kernels read q, k and v out of it two heads a program and write
    what the output projection reads, at another width ``attend`` splits it
    for one-head programs. Loss and gradients equal those of the same model
    around a plain callable, which gets q, k and v ``[B, L, H, D]`` split in
    the block as before."""
    import functools

    from horovod_tpu.ops.attention import attend, dot_product_attention
    from horovod_tpu.utils import timeline

    kw = dict(vocab_size=32, num_layers=2, num_heads=2, embed_dim=2 * width,
              max_len=32, dtype=jnp.float32, remat=remat)
    dense_m = models.TransformerLM(
        attn_fn=lambda q, k, v: dot_product_attention(q, k, v, causal=True),
        **kw)
    flash_m = models.TransformerLM(
        attn_fn=functools.partial(attend, impl="flash", block_q=8,
                                  block_k=8), **kw)
    tokens = jax.random.randint(rng, (2, 16), 0, 32)
    params = dense_m.init(rng, tokens, train=False)["params"]

    def loss_fn(model, params):
        logits = model.apply({"params": params}, tokens, train=False)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp[:, :-1], tokens[:, 1:, None], -1))

    ld, gd = jax.value_and_grad(lambda p: loss_fn(dense_m, p))(params)
    timeline.reset()
    with timeline.span("hvd.spmd.dispatch", handle="loss", program="loss#0",
                       call=0):
        lf, gf = jax.value_and_grad(lambda p: loss_fn(flash_m, p))(params)
    gauges = timeline.snapshot()["gauges"]
    assert gauges["hvd.attn.flash_calls"]["loss#0"] == 2
    assert gauges["hvd.attn.paired_calls"]["loss#0"] == (2 if width == 64
                                                         else 0)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_scan_layers_matches_unrolled(rng):
    """scan_layers compiles ONE weight-stacked block (lax.scan) instead
    of num_layers unrolled copies; per-layer math must be identical.
    Transplants the stacked params into the unrolled layout and pins
    logits AND gradients across the two layouts, plus the remat
    variants (which must be numerically a no-op)."""
    kw = dict(vocab_size=61, num_layers=3, num_heads=2, embed_dim=24,
              max_len=32, dtype=jnp.float32)
    scan_m = models.TransformerLM(scan_layers=True, **kw)
    unrl_m = models.TransformerLM(**kw)
    tokens = jax.random.randint(rng, (2, 16), 0, 61)

    ps = scan_m.init(rng, tokens, train=False)["params"]
    stacked = ps["layers"]["TransformerBlock_0"]
    pu = {k: v for k, v in ps.items() if k != "layers"}
    for i in range(kw["num_layers"]):
        pu[f"TransformerBlock_{i}"] = jax.tree.map(
            lambda a, i=i: a[i], stacked)

    def loss(model, params):
        logits = model.apply({"params": params}, tokens, train=False)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp[:, :-1], tokens[:, 1:, None], -1))

    ls, gs = jax.value_and_grad(lambda p: loss(scan_m, p))(ps)
    lu, gu = jax.value_and_grad(lambda p: loss(unrl_m, p))(pu)
    np.testing.assert_allclose(float(ls), float(lu), rtol=1e-6)

    # Gradients: restack the unrolled per-layer grads and compare.
    gu_stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[gu[f"TransformerBlock_{i}"] for i in range(kw["num_layers"])])
    for a, b in zip(jax.tree_util.tree_leaves(
            gs["layers"]["TransformerBlock_0"]),
            jax.tree_util.tree_leaves(gu_stacked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for name in ["Embed_0", "Embed_1", "LayerNorm_0", "lm_head"]:
        for a, b in zip(jax.tree_util.tree_leaves(gs[name]),
                        jax.tree_util.tree_leaves(gu[name])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    # remat is a scheduling choice, not a numerical one.
    for scan in (True, False):
        m = models.TransformerLM(scan_layers=scan, remat=True, **kw)
        p = ps if scan else pu
        lr, gr = jax.value_and_grad(lambda q: loss(m, q))(p)
        np.testing.assert_allclose(float(lr), float(ls), rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(gr),
                        jax.tree_util.tree_leaves(
                            gs if scan else gu)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
