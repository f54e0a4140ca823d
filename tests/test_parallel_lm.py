"""Exactness tests for the composed dp x sp x tp LM
(horovod_tpu/models/parallel_lm.py): the sharded model must reproduce
the dense single-device math bit-for-bit-ish (fp32 tolerances), the
sequence-shard-aware loss must equal the dense shift, and one full
training step (grads + SGD update) must yield the same dense parameters
when the mesh reassembles the tp shards."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.parallel as par
from horovod_tpu.models import parallel_lm as plm

V, LMAX, LAYERS, H, DH, FFN = 64, 64, 2, 4, 8, 32
B, L = 4, 16  # global batch, global sequence


@pytest.fixture(scope="module")
def setup():
    rng = jax.random.PRNGKey(0)
    params = plm.init_lm_params(rng, V, LMAX, LAYERS, H, DH, FFN)
    tokens = jax.random.randint(jax.random.fold_in(rng, 1), (B, L), 0, V)
    return params, tokens


def _mesh():
    return par.make_mesh({"dp": 2, "sp": 2, "tp": 2})


def test_forward_matches_dense(hvd, setup):
    params, tokens = setup
    dense = plm.lm_apply(params, tokens)  # sp=tp=None: plain math

    mesh = _mesh()
    specs = plm.lm_param_specs(LAYERS, "tp")
    fn = jax.jit(jax.shard_map(
        lambda p, t: plm.lm_apply(p, t, sp="sp", tp="tp"),
        mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp", "sp", None)))
    sharded = fn(params, tokens)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)


def test_loss_matches_dense_shift(hvd, setup):
    params, tokens = setup
    dense_logits = plm.lm_apply(params, tokens)
    # Dense reference: shift by one, drop the final position.
    logp = jax.nn.log_softmax(dense_logits.astype(jnp.float32), -1)
    ref = -jnp.mean(jnp.take_along_axis(
        logp[:, :-1], tokens[:, 1:, None], -1))

    mesh = _mesh()
    specs = plm.lm_param_specs(LAYERS, "tp")
    fn = jax.jit(jax.shard_map(
        lambda p, t: plm.next_token_nll(
            plm.lm_apply(p, t, sp="sp", tp="tp"), t, sp="sp")[None],
        mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp")))
    # Per-dp-shard means over that shard's tokens; their mean == global.
    per_dp = fn(params, tokens)
    dense_per_dp = jax.vmap(
        lambda lg, tk: -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(lg.astype(jnp.float32), -1)[:, :-1],
            tk[:, 1:, None], -1)))(
        dense_logits.reshape(2, B // 2, L, V), tokens.reshape(2, B // 2, L))
    np.testing.assert_allclose(np.asarray(per_dp), np.asarray(dense_per_dp),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(jnp.mean(per_dp)), float(ref),
                               rtol=2e-4)


def test_train_step_matches_dense(hvd, setup):
    """One SGD step, both worlds: the mesh's out_specs reassemble the
    tp-sharded updated params into dense arrays, which must equal the
    dense-path update."""
    params, tokens = setup
    lr = 0.1

    def dense_step(p, t):
        def loss_fn(p):
            return plm.next_token_nll(plm.lm_apply(p, t), t)

        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), loss

    dense_params, dense_loss = jax.jit(dense_step)(params, tokens)

    mesh = _mesh()
    specs = plm.lm_param_specs(LAYERS, "tp")

    def sharded_step(p, t):
        def loss_fn(p):
            return plm.next_token_nll(
                plm.lm_apply(p, t, sp="sp", tp="tp"), t, sp="sp")

        loss, g = jax.value_and_grad(loss_fn)(p)
        g = plm.reduce_grads(g, dp="dp", sp="sp")
        new_p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
        return new_p, jax.lax.pmean(loss, "dp")

    fn = jax.jit(jax.shard_map(
        sharded_step, mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=(specs, P())))
    sharded_params, sharded_loss = fn(params, tokens)

    np.testing.assert_allclose(float(sharded_loss), float(dense_loss),
                               rtol=2e-4)
    flat_d, _ = jax.tree_util.tree_flatten(dense_params)
    flat_s, _ = jax.tree_util.tree_flatten(sharded_params)
    for d, s in zip(flat_d, flat_s):
        np.testing.assert_allclose(np.asarray(s), np.asarray(d),
                                   rtol=3e-4, atol=3e-5)


def test_sp_only_and_tp_only_compose_independently(hvd, setup):
    """Each axis works alone: sp-only (dense weights, ring attention)
    and tp-only (full sequence, sharded weights) both match dense."""
    params, tokens = setup
    dense = plm.lm_apply(params, tokens)

    sp_mesh = par.make_mesh({"sp": 4}, devices=jax.devices()[:4])
    fn_sp = jax.jit(jax.shard_map(
        lambda p, t: plm.lm_apply(p, t, sp="sp"),
        mesh=sp_mesh, in_specs=(plm.lm_param_specs(LAYERS, None),
                                P(None, "sp")),
        out_specs=P(None, "sp", None)))
    np.testing.assert_allclose(np.asarray(fn_sp(params, tokens)),
                               np.asarray(dense), rtol=2e-4, atol=2e-5)

    tp_mesh = par.make_mesh({"tp": 4}, devices=jax.devices()[:4])
    fn_tp = jax.jit(jax.shard_map(
        lambda p, t: plm.lm_apply(p, t, tp="tp"),
        mesh=tp_mesh, in_specs=(plm.lm_param_specs(LAYERS, "tp"), P()),
        out_specs=P()))
    np.testing.assert_allclose(np.asarray(fn_tp(params, tokens)),
                               np.asarray(dense), rtol=2e-4, atol=2e-5)


def test_zero_composes_with_sequence_parallel(hvd, setup):
    """ZeRO-1 over dp composes with ring-attention SP in the same step:
    the sharded-optimizer trajectory must match plain dp-averaged adam
    (ZeRO-1 is mathematically the same update), with the optimizer
    vectors physically sharded over dp only."""
    import optax

    from horovod_tpu.jax import zero

    params, tokens = setup
    mesh = par.make_mesh({"dp": 2, "sp": 4})
    specs = plm.lm_param_specs(LAYERS, None)  # replicated params
    sp_in = P("dp", "sp")

    def make_step(use_zero):
        opt = (zero.sharded_distributed_optimizer(optax.adam(1e-2),
                                                  axis_name="dp")
               if use_zero else optax.adam(1e-2))
        opt_state = opt.init(params)
        ospec = (zero.state_partition_specs(opt_state, "dp")
                 if use_zero else P())

        def step(p, s, t):
            def loss_fn(p):
                return plm.next_token_nll(
                    plm.lm_apply(p, t, sp="sp"), t, sp="sp")

            loss, g = jax.value_and_grad(loss_fn)(p)
            # ZeRO averages over dp inside its reduce-scatter; the plain
            # path averages explicitly.
            g = plm.reduce_grads(g, dp=None if use_zero else "dp", sp="sp")
            u, s = opt.update(g, s, p)
            import optax as _ox

            return _ox.apply_updates(p, u), s, jax.lax.pmean(loss, "dp")

        # ZeRO's scatter/gather collectives produce replicated values
        # the vma checker cannot statically infer; scoped opt-out.
        fn = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(specs, ospec, sp_in),
            out_specs=(specs, ospec, P()), check_vma=False))
        return fn, opt_state

    zfn, zstate = make_step(True)
    pfn, pstate = make_step(False)
    zp, pp = params, params
    zlosses, plosses = [], []
    for _ in range(5):
        zp, zstate, zl = zfn(zp, zstate, tokens)
        pp, pstate, pl = pfn(pp, pstate, tokens)
        zlosses.append(float(zl))
        plosses.append(float(pl))
    np.testing.assert_allclose(zlosses, plosses, rtol=5e-4)
    # The adam moment vectors really live dp-sharded.
    sharded = [l for l in jax.tree_util.tree_leaves(zstate)
               if getattr(l, "ndim", 0) == 1 and l.shape[0] > 4
               and not l.sharding.is_fully_replicated]
    assert sharded, "no sharded optimizer vectors"


def test_decode_matches_naive_recompute(setup):
    """KV-cache greedy decode must produce EXACTLY the tokens a naive
    loop gets by re-running the full forward on the growing sequence and
    taking argmax of the last position."""
    params, tokens = setup
    prompt = tokens[:, :6]
    steps = 8

    got = plm.lm_decode(params, prompt, steps)
    seq = prompt
    want = []
    for _ in range(steps):
        logits = plm.lm_apply(params, seq)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    want = jnp.stack(want, axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_composes_with_tp(hvd, setup):
    """The same decode runs with head-sharded params inside shard_map
    (forward-only Megatron f/g) and yields identical tokens."""
    params, tokens = setup
    prompt = tokens[:, :4]
    dense = plm.lm_decode(params, prompt, 6)

    tp_mesh = par.make_mesh({"tp": 4}, devices=jax.devices()[:4])
    fn = jax.jit(jax.shard_map(
        lambda p, t: plm.lm_decode(p, t, 6, tp="tp"),
        mesh=tp_mesh, in_specs=(plm.lm_param_specs(LAYERS, "tp"), P()),
        out_specs=P()))
    sharded = fn(params, prompt)
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(dense))


def test_decode_sampling_reproducible(setup):
    params, tokens = setup
    prompt = tokens[:, :4]
    key = jax.random.PRNGKey(11)
    a = plm.lm_decode(params, prompt, 5, temperature=0.8, rng=key)
    b = plm.lm_decode(params, prompt, 5, temperature=0.8, rng=key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (prompt.shape[0], 5)


def test_pipeline_parallel_matches_dense(hvd):
    """The LM under GPipe pipeline parallelism (one block per stage):
    forward logits AND all gradients — stage-sharded layers reassembled
    by the mesh, replicated embed/head grads psum'd over pp — must match
    the flat lm_apply autodiff."""
    rng = jax.random.PRNGKey(2)
    layers = 4
    params = plm.init_lm_params(rng, V, LMAX, layers, H, DH, FFN)
    tokens = jax.random.randint(jax.random.fold_in(rng, 1), (B, L), 0, V)

    def dense_loss(p):
        return plm.next_token_nll(plm.lm_apply(p, tokens), tokens)

    dense_val, dense_g = jax.value_and_grad(dense_loss)(params)
    dense_rest, dense_layer_g = plm.stack_layers(dense_g)

    rest, stacked = plm.stack_layers(params)
    rest_spec, layer_spec = plm.lm_pp_specs(rest, stacked)
    mesh = par.make_mesh({"pp": layers}, devices=jax.devices()[:layers])

    def pp_loss_and_grads(rest, stacked, t):
        def loss_fn(rest, stacked):
            logits = plm.lm_apply_pp(rest, stacked, t, axis="pp",
                                     microbatches=2)
            return plm.next_token_nll(logits, t)

        loss, (g_rest, g_layers) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(rest, stacked)
        return loss, plm.pp_reduce_rest_grads(g_rest), g_layers

    fn = jax.jit(jax.shard_map(
        pp_loss_and_grads, mesh=mesh,
        in_specs=(rest_spec, layer_spec, P()),
        out_specs=(P(), rest_spec, layer_spec)))
    loss, g_rest, g_layers = fn(rest, stacked, tokens)

    np.testing.assert_allclose(float(loss), float(dense_val), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_layers),
                    jax.tree_util.tree_leaves(dense_layer_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_rest),
                    jax.tree_util.tree_leaves(dense_rest)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def test_pp_shape_validation_messages(hvd):
    """lm_apply_pp rejects a batch that does not divide the microbatch
    count, and a stage stack whose length mismatches the pp axis, with
    DESCRIPTIVE errors (advisor r2: these used to surface as cryptic
    reshape/ppermute failures deep inside pipeline_apply)."""
    rng = jax.random.PRNGKey(9)
    n = 8
    mesh = par.make_mesh({"pp": n})
    params = plm.init_lm_params(rng, V, LMAX, n, H, DH, FFN)
    rest, stacked = plm.stack_layers(params)
    rest_spec, layer_spec = plm.lm_pp_specs(rest, stacked)
    tokens = jax.random.randint(jax.random.fold_in(rng, 1), (6, L), 0, V)

    def run(rest, stacked, tokens, microbatches, lspec):
        return jax.jit(jax.shard_map(
            lambda r, s, t: plm.lm_apply_pp(r, s, t,
                                            microbatches=microbatches),
            mesh=mesh,
            in_specs=(rest_spec, lspec, P()),
            out_specs=P()))(rest, stacked, tokens)

    with pytest.raises(ValueError, match="microbatches"):
        run(rest, stacked, tokens, 4, layer_spec)  # 6 % 4 != 0

    # n/2 stacked blocks over an n-chip pp axis: replicate the (wrongly
    # sized) stack so the shape error is the function's own check.
    short = jax.tree_util.tree_map(lambda l: l[: n // 2], stacked)
    short_spec = jax.tree_util.tree_map(lambda _: P(), short)
    with pytest.raises(ValueError, match="axis"):
        run(rest, short, tokens[:4], 2, short_spec)


def test_bf16_composed_step_and_decode(hvd):
    """The dtype path a real TPU run uses: bf16 params/activations
    through the full dp x sp x tp step (grads finite, loss falls over a
    few steps) and through the KV-cache decode."""
    rng = jax.random.PRNGKey(3)
    params = plm.init_lm_params(rng, V, LMAX, LAYERS, H, DH, FFN,
                                dtype=jnp.bfloat16)
    tokens = jax.random.randint(jax.random.fold_in(rng, 1), (B, L), 0, V)
    mesh = _mesh()
    specs = plm.lm_param_specs(LAYERS, "tp")

    def step(p, t):
        def loss_fn(p):
            return plm.next_token_nll(
                plm.lm_apply(p, t, sp="sp", tp="tp"), t, sp="sp")

        loss, g = jax.value_and_grad(loss_fn)(p)
        g = plm.reduce_grads(g, dp="dp", sp="sp")
        new_p = jax.tree_util.tree_map(
            lambda a, b: (a.astype(jnp.float32) -
                          0.5 * b.astype(jnp.float32)).astype(a.dtype),
            p, g)
        return new_p, jax.lax.pmean(loss, "dp")

    fn = jax.jit(jax.shard_map(step, mesh=mesh,
                               in_specs=(specs, P("dp", "sp")),
                               out_specs=(specs, P())))
    losses = []
    ps = params
    for _ in range(6):
        ps, l = fn(ps, tokens)
        losses.append(float(l))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses

    gen = plm.lm_decode(ps, tokens[:, :4], 5)
    assert gen.shape == (B, 5)
    assert (np.asarray(gen) >= 0).all() and (np.asarray(gen) < V).all()


def test_fused_loss_train_step_matches_dense(hvd, setup):
    """next_token_nll_fused — chunked CE with a VOCAB-PARALLEL head
    (lm_param_specs vocab_parallel=True) — reproduces the dense
    logits-path training step exactly: same loss, same updated params
    once the mesh reassembles the shards. Also pins the dense fused
    path (no mesh) against next_token_nll."""
    params, tokens = setup
    lr = 0.1

    # Dense fused path == dense logits path.
    hidden = plm.lm_apply(params, tokens, return_hidden=True)
    fused_dense = plm.next_token_nll_fused(params, hidden, tokens,
                                           t_chunk=8)
    logits_dense = plm.next_token_nll(plm.lm_apply(params, tokens),
                                      tokens)
    np.testing.assert_allclose(float(fused_dense), float(logits_dense),
                               rtol=1e-6)

    def dense_step(p, t):
        def loss_fn(p):
            return plm.next_token_nll(plm.lm_apply(p, t), t)

        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), loss

    dense_params, dense_loss = jax.jit(dense_step)(params, tokens)

    mesh = _mesh()
    specs = plm.lm_param_specs(LAYERS, "tp", vocab_parallel=True)

    def sharded_step(p, t):
        def loss_fn(p):
            h = plm.lm_apply(p, t, sp="sp", tp="tp", return_hidden=True)
            return plm.next_token_nll_fused(
                p, h, t, sp="sp", tp="tp", vocab_parallel=True, t_chunk=8)

        loss, g = jax.value_and_grad(loss_fn)(p)
        g = plm.reduce_grads(g, dp="dp", sp="sp")
        new_p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
        return new_p, jax.lax.pmean(loss, "dp")

    # check_vma opt-out class 4 (docs/parallelism.md): the fused-loss
    # custom VJP returns per-rank partial dw for the tp-sharded head
    # (reduced later by reduce_grads), which the strict checker's
    # cotangent-type-equality rule rejects; this very test is the
    # exactness pin that justifies the opt-out.
    fn = jax.jit(jax.shard_map(
        sharded_step, mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=(specs, P()), check_vma=False))
    sharded_params, sharded_loss = fn(params, tokens)

    np.testing.assert_allclose(float(sharded_loss), float(dense_loss),
                               rtol=2e-4)
    flat_d, _ = jax.tree_util.tree_flatten(dense_params)
    flat_s, _ = jax.tree_util.tree_flatten(sharded_params)
    for d, s in zip(flat_d, flat_s):
        np.testing.assert_allclose(np.asarray(s), np.asarray(d),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("k,dl", [(1, 1), (2, 1), (4, 2), (7, 2)])
def test_spec_decode_matches_lm_decode(setup, k, dl):
    """The model-level speculative reference (lm_decode_spec: layer-skip
    draft + ONE rectangular verify window per tick) is bit-identical to
    greedy lm_decode for every window size and draft depth — proposals
    only decide how many target argmaxes one dispatch yields, never
    what they are. k=7 exercises the budget clamp (k > steps)."""
    params, tokens = setup
    prompt = tokens[:1, :6]
    want = np.asarray(plm.lm_decode(params, prompt, 8))
    got = np.asarray(plm.lm_decode_spec(params, prompt, 8, k=k,
                                        draft_layers=dl))
    np.testing.assert_array_equal(got, want)


def test_verify_window_w1_is_decode_step(setup):
    """w=1 verify window IS lm_decode_step shape-for-shape: identical
    logits and identical cache rows — the rectangular pass degrades to
    the sequential step exactly."""
    params, tokens = setup
    prompt = tokens[:2, :5]
    caches, logits = plm.lm_prefill(params, prompt)
    tok = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(
        jnp.int32)
    c_seq, lg_seq = plm.lm_decode_step(params, caches, tok, 5)
    c_win, lg_win = plm.lm_verify_window(params, caches, tok[:, None], 5)
    np.testing.assert_array_equal(np.asarray(lg_win[:, 0]),
                                  np.asarray(lg_seq))
    for a, b in zip(c_seq, c_win):
        np.testing.assert_array_equal(np.asarray(a["k"]),
                                      np.asarray(b["k"]))
        np.testing.assert_array_equal(np.asarray(a["v"]),
                                      np.asarray(b["v"]))


def test_draft_params_is_a_zero_copy_view(setup):
    """The layer-skip draft shares the target's arrays (no copy): same
    embed/head objects, layer list a prefix slice — and out-of-range
    depths die loudly."""
    params, _ = setup
    d = plm.draft_params(params, 1)
    assert d["embed"] is params["embed"]
    assert d["head"] is params["head"]
    assert d["layers"] == params["layers"][:1]
    assert len(plm.draft_params(params, LAYERS)["layers"]) == LAYERS
    for bad in (0, -1, LAYERS + 1):
        with pytest.raises(ValueError, match="draft_params"):
            plm.draft_params(params, bad)


def test_spec_decode_validation(setup):
    params, tokens = setup
    with pytest.raises(ValueError, match="single-row"):
        plm.lm_decode_spec(params, tokens[:2, :4], 4, k=2,
                           draft_layers=1)
    with pytest.raises(ValueError, match="k must be"):
        plm.lm_decode_spec(params, tokens[:1, :4], 4, k=0,
                           draft_layers=1)
    with pytest.raises(ValueError, match="position table"):
        plm.lm_decode_spec(params, tokens[:1, :4], LMAX, k=2,
                           draft_layers=1)
