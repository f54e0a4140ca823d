"""Chunked fused cross-entropy (ops/xent.py) vs the dense composition.

The dense reference materializes [T, V] logits and log-softmaxes them —
exactly what the LM step's unfused loss does (models.make_lm_train_step); the
fused op must match its loss and gradients while never building the
full logits tensor.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.xent import fused_cross_entropy, token_nll


def _dense_nll(h, w, targets):
    logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], -1))


@pytest.mark.parametrize("t,chunk", [(64, 16), (60, 16), (16, 16)])
def test_fused_ce_matches_dense(t, chunk):
    """Loss + dh + dw exact vs dense, incl. a non-divisible token count
    (60 % 16 != 0 exercises the pad/weight path)."""
    key = jax.random.PRNGKey(0)
    e, v = 32, 97
    h = jax.random.normal(key, (t, e), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, v), jnp.float32)
    targets = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0, v)

    ld, (gdh, gdw) = jax.value_and_grad(_dense_nll, argnums=(0, 1))(
        h, w, targets)
    lf, (fdh, fdw) = jax.value_and_grad(
        lambda h, w: fused_cross_entropy(h, w, targets, chunk),
        argnums=(0, 1))(h, w)

    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fdh), np.asarray(gdh),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fdw), np.asarray(gdw),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("t,chunk", [(64, 16), (60, 16), (7, 16)])
def test_token_nll_matches_dense_in_value_and_all_three_gradients(t, chunk):
    """The per-token loss under a learned weighting (the looped LM's exit
    distribution): the value and the gradients into the hidden state, the
    head and the weighting equal the dense composition's."""
    key = jax.random.PRNGKey(7)
    e, v = 24, 61
    h = jax.random.normal(key, (t, e), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, v), jnp.float32)
    z = jax.random.normal(jax.random.fold_in(key, 2), (t,), jnp.float32)
    targets = jax.random.randint(jax.random.fold_in(key, 3), (t,), 0, v)

    def dense(h, w, z):
        logp = jax.nn.log_softmax(h @ w, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.sigmoid(z) * nll) / t

    def chunked(h, w, z):
        return jnp.sum(jax.nn.sigmoid(z)
                       * token_nll(h, w, targets, chunk)) / t

    want, want_grads = jax.value_and_grad(dense, argnums=(0, 1, 2))(h, w, z)
    got, got_grads = jax.value_and_grad(chunked, argnums=(0, 1, 2))(h, w, z)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    per_token = token_nll(h, w, targets, chunk)
    assert per_token.shape == (t,) and per_token.dtype == jnp.float32
    # and through the weighted sum, where the weighting used to read zeros
    dz = jax.grad(lambda z: fused_cross_entropy(
        h, w, targets, chunk, weights=jax.nn.sigmoid(z), denom=t))(z)
    np.testing.assert_allclose(np.asarray(dz), np.asarray(want_grads[2]),
                               rtol=1e-5, atol=1e-6)


def test_fused_ce_bf16_hidden():
    """bf16 hidden states (the LM's compute dtype): fp32 accumulation
    inside, gradients returned in the input dtypes."""
    key = jax.random.PRNGKey(3)
    t, e, v = 48, 16, 53
    h = jax.random.normal(key, (t, e), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, v), jnp.float32)
    targets = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0, v)

    ld, (gdh, gdw) = jax.value_and_grad(
        lambda h, w: _dense_nll(h.astype(jnp.float32), w, targets),
        argnums=(0, 1))(h, w)
    lf, (fdh, fdw) = jax.value_and_grad(
        lambda h, w: fused_cross_entropy(
            h.astype(jnp.float32), w, targets, 16),
        argnums=(0, 1))(h, w)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    assert fdh.dtype == h.dtype and fdw.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(fdh, np.float32),
                               np.asarray(gdh, np.float32),
                               rtol=2e-2, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fdw), np.asarray(gdw),
                               rtol=1e-4, atol=1e-5)


def test_fused_ce_never_builds_full_logits():
    """Structural guarantee: the jaxpr of the fused op contains no
    [T, V]-shaped intermediate when T spans multiple chunks."""
    t, e, v, chunk = 64, 8, 331, 16
    h = jnp.zeros((t, e), jnp.float32)
    w = jnp.zeros((e, v), jnp.float32)
    targets = jnp.zeros((t,), jnp.int32)

    jaxpr = jax.make_jaxpr(
        jax.grad(lambda h: fused_cross_entropy(h, w, targets, chunk)))(h)

    def subjaxprs(params):
        for val in params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v_ in vals:
                if hasattr(v_, "jaxpr"):     # ClosedJaxpr
                    yield v_.jaxpr
                elif hasattr(v_, "eqns"):    # raw Jaxpr
                    yield v_

    def walk(jx):
        for eqn in jx.eqns:
            for var in eqn.outvars:
                yield getattr(var.aval, "shape", ())
            for sub in subjaxprs(eqn.params):
                yield from walk(sub)

    shapes = list(walk(jaxpr.jaxpr))
    # Scan internals may carry [chunk, V] blocks; anything with BOTH a
    # full token axis and a full vocab axis (incl. padded variants,
    # anywhere in nested scan/remat jaxprs) is the HBM sink this op
    # exists to remove.
    offenders = [s for s in shapes
                 if len(s) >= 2 and s[-2] >= t and s[-1] >= v]
    assert not offenders, offenders


class TestVocabParallel:
    """tp_vocab_cross_entropy inside shard_map vs the dense NLL."""

    def _mesh(self, n):
        from horovod_tpu import parallel as par
        return par.make_mesh({"tp": n}, devices=jax.devices()[:n])

    @pytest.mark.parametrize("t,chunk", [(32, 8), (28, 8)])
    def test_loss_and_grads_match_dense(self, t, chunk):
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(4)
        key = jax.random.PRNGKey(7)
        e, v = 16, 64  # v_local = 16 per rank
        h = jax.random.normal(key, (t, e), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (e, v),
                              jnp.float32)
        targets = jax.random.randint(jax.random.fold_in(key, 2), (t,),
                                     0, v)

        from horovod_tpu.ops.xent import tp_vocab_cross_entropy

        def loss_vp(h, w):
            fn = jax.shard_map(
                lambda hh, ww: tp_vocab_cross_entropy(
                    hh, ww, targets, "tp", chunk),
                mesh=mesh, in_specs=(P(), P(None, "tp")), out_specs=P())
            return fn(h, w)

        ld, (gdh, gdw) = jax.value_and_grad(_dense_nll, argnums=(0, 1))(
            h, w, targets)
        lv, (vdh, vdw) = jax.value_and_grad(loss_vp, argnums=(0, 1))(h, w)

        np.testing.assert_allclose(float(lv), float(ld), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(vdh), np.asarray(gdh),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vdw), np.asarray(gdw),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("t,chunk", [(32, 8), (28, 8)])
    def test_loss_and_grads_match_dense_in_region(self, t, chunk):
        """The op's supported gradient convention on EVERY runtime: a
        ``jax.grad`` taken INSIDE the shard_map region (how
        models/parallel_lm.py's fused vocab-parallel loss differentiates
        it) yields the assembled dh (axis-invariant) and the rank-local
        dw slice — exactly the dense gradients."""
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(4)
        key = jax.random.PRNGKey(7)
        e, v = 16, 64  # v_local = 16 per rank
        h = jax.random.normal(key, (t, e), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (e, v),
                              jnp.float32)
        targets = jax.random.randint(jax.random.fold_in(key, 2), (t,),
                                     0, v)

        from horovod_tpu.ops.xent import tp_vocab_cross_entropy

        def region(hh, ww):
            def loss_fn(hh_, ww_):
                return tp_vocab_cross_entropy(hh_, ww_, targets, "tp",
                                              chunk)
            loss, (dh, dw) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(hh, ww)
            return loss, dh, dw

        fn = jax.shard_map(region, mesh=mesh,
                           in_specs=(P(), P(None, "tp")),
                           out_specs=(P(), P(), P(None, "tp")))
        lv, vdh, vdw = fn(h, w)

        ld, (gdh, gdw) = jax.value_and_grad(_dense_nll, argnums=(0, 1))(
            h, w, targets)
        np.testing.assert_allclose(float(lv), float(ld), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(vdh), np.asarray(gdh),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vdw), np.asarray(gdw),
                                   rtol=1e-5, atol=1e-6)

    def test_loss_identical_on_every_rank(self):
        """The op's contract: the returned scalar is axis-invariant
        (same value on every tp rank) — out_specs=P() above would fail
        loudly on mismatch, but pin it explicitly via a per-rank
        output."""
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(4)
        key = jax.random.PRNGKey(9)
        t, e, v = 16, 8, 32
        h = jax.random.normal(key, (t, e), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (e, v),
                              jnp.float32)
        targets = jax.random.randint(jax.random.fold_in(key, 2), (t,),
                                     0, v)

        from horovod_tpu.ops.xent import tp_vocab_cross_entropy

        fn = jax.shard_map(
            lambda hh, ww: tp_vocab_cross_entropy(
                hh, ww, targets, "tp", 8)[None],
            mesh=mesh, in_specs=(P(), P(None, "tp")),
            out_specs=P("tp"))
        per_rank = np.asarray(fn(h, w))
        np.testing.assert_allclose(per_rank, per_rank[0], rtol=0)
