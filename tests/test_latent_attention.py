"""The latent attention layer (``models/decoder.py`` ``LatentAttention``,
``--lm-layer-types latent``) and the flash kernels at key and value widths
that differ (``ops/attention.py``), at a small size with seeded weights on
the CPU: the layer and the lane's step against the plain reference
``benchmarks/reference/moonlight.py``, the kernels in interpret mode against
the dense path.

Tolerances and why:

* float32 program against the float32 reference: 2e-5 on every gap, as for
  the sparse decoder (``test_sparse_decoder.py``): the same function in the
  same precision; what is left is the order of additions (the reference
  takes the scores of the key's two parts as two sums and the batch a row at
  a time), some 1e-6 as read.
* the layer alone, float32, outputs of size one: 2e-5 absolute and relative
  (read: 2e-6).
* flash kernels (interpreted) against masked dense attention, float32: 2e-5
  absolute on outputs and 3e-5 on gradients of size one (online softmax
  against a plain one, and a rope key's gradient is a sum over the heads;
  read: 4e-6).
* each of the reference's three planted faults moves the layer's output by
  over 1e-2: four orders above the tolerance.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import compare, run  # noqa: E402
from benchmarks.reference import common, moonlight  # noqa: E402
from horovod_tpu.models import decoder  # noqa: E402
from horovod_tpu.ops.attention import (  # noqa: E402
    FLASH_SLAB,
    attend,
    attention_plan,
    dot_product_attention,
    flash_attention,
)

HYPER = {"heads": 4, "nope_dim": 16, "rope_dim": 8, "value_dim": 16,
         "latent_dim": 32, "rms_norm_eps": 1e-5, "rope_theta": 50000.0,
         "layers": 3, "experts": 8, "first_expert": 4, "top_k": 3,
         "route_scale": 2.446, "embed_scale": False,
         "load_balance_coeff": 0.001, "rotate_key": True,
         "latent_norm": True, "score_width": 24,
         "optimizer": {"name": "adam", "lr": 0.0001, "b1": 0.9, "b2": 0.999,
                       "eps": 1e-08}}
BENCH_ARGS = [
    "--model", "moe_lm", "--lm-layers", "3", "--lm-dim", "64", "--lm-heads",
    "4", "--lm-head-dim", "16", "--lm-rope-dim", "8", "--lm-value-dim", "16",
    "--lm-latent-dim", "32", "--lm-rope-base", "50000.0", "--lm-layer-types",
    "latent,latent,latent", "--no-lm-output-norms", "--no-lm-embed-scale",
    "--lm-ffn", "96", "--lm-dense-layers", "1", "--moe-experts", "8",
    "--moe-experts-held", "4", "--moe-first-expert", "4", "--moe-top-k", "3",
    "--moe-width", "32", "--moe-shared", "2", "--moe-route-scale", "2.446",
    "--vocab", "128"]
CONFIG = {
    "bench_args": BENCH_ARGS, "kernel_gain": 1.0,
    "int_ranges": {"tokens": 128},
    "draws": {"experts_gate": {"mean": 0.0, "std": 0.125},
              "experts_up": {"mean": 0.0, "std": 0.125},
              "experts_down": {"mean": 0.0, "std": 0.177}},
    "first_moment": {"field": "mu", "scale": 10.0},
    "reference": {"file": "reference/moonlight.py", "hyper": HYPER}}
CELL = {"name": "toy", "chips": 1, "compare_steps": 3,
        "bench_args": ["--batch-size", "2", "--seq-len", "32", "--remat"],
        "reference_rows_per_block": 1}


@pytest.fixture(scope="module")
def programs(hvd):
    """The lane ``bench.build_lane`` makes of the arguments, float32, with
    dense and with flash attention, as ``run.py`` drives it."""
    made = {}

    def get(attention):
        if attention not in made:
            config = dict(CONFIG, bench_args=BENCH_ARGS + [
                "--fp32", "--attention", attention])
            made[attention] = run.Program(
                config, dict(CELL, chips=hvd.size()))
        return made[attention]

    return get


@pytest.mark.parametrize("attention, seed", [
    ("dense", 3), ("dense", 2 ** 31 + 5), ("flash", 3)])
def test_three_adam_steps_match_the_reference(programs, attention, seed):
    """Loss of each step, every leaf's first gradient and every leaf's change
    over three Adam steps, through the lane's own call."""
    program = programs(attention)
    assert isinstance(program.lane.model, decoder.SparseDecoderLM)
    state, batch = program.start(seed)
    state, prog = program.first_steps(state, batch, seed)
    ref = program.reference(seed, jax.devices()[0])
    for name, (gap, where) in compare.gaps(prog, ref).items():
        assert gap < 2e-5, (name, gap, where)
    assert sorted(prog["grad_norms"]) == sorted(ref["grad_norms"])
    block = state["params"]["DecoderBlock_1"]
    assert set(block) == {"norm_attn", "attn", "norm_ffn", "moe"}  # two norms
    assert set(block["attn"]) == {"q", "kv_a", "kv_norm", "kv_b", "out"}
    assert block["attn"]["kv_a"]["kernel"].shape == (64, 32 + 8)
    assert block["attn"]["kv_b"]["kernel"].shape == (32, 4 * (16 + 16))


def _layer(attention="dense"):
    layer = decoder.LatentAttention(
        heads=4, nope_dim=16, rope_dim=8, value_dim=16, latent_dim=32,
        rope_base=50000.0, dtype=jnp.float32, attention=attention)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 24))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2), a.shape),
        params)
    return layer, x, params


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_the_layer_is_the_references_forward_and_gradients(attention):
    layer, x, params = _layer(attention)
    einsum = common.make_einsum("float32")
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def ours(x, params):
        return jnp.sum(layer.apply({"params": params}, x) * w)

    def theirs(x, params, **fault):
        return jnp.sum(moonlight._attention(
            x, params, hyper=dict(HYPER, **fault), einsum=einsum) * w)

    np.testing.assert_allclose(
        layer.apply({"params": params}, x),
        moonlight._attention(x, params, hyper=HYPER, einsum=einsum),
        rtol=2e-5, atol=2e-5)
    got = jax.grad(ours, (0, 1))(x, params)
    want = jax.grad(theirs, (0, 1))(x, params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    # what the reference's three planted faults turn is in the layer
    sound = layer.apply({"params": params}, x)
    for fault in ({"rotate_key": False}, {"latent_norm": False},
                  {"score_width": 16}):
        bad = moonlight._attention(x, params, hyper=dict(HYPER, **fault),
                                   einsum=einsum)
        assert float(jnp.abs(bad - sound).max()) > 1e-2, fault


def _operands(heads=4, own=16, shared=8, value=16, length=64):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    return (jax.random.normal(ks[0], (2, length, heads, own + shared)),
            jax.random.normal(ks[1], (2, length, heads, own)),
            jax.random.normal(ks[2], (2, length, heads, value)),
            jax.random.normal(ks[3], (2, length, shared)),
            jax.random.normal(ks[4], (2, length, heads, value)))


@pytest.mark.parametrize("bq, bk, bwd, truncate", [
    (16, 8, "pallas", None),        # the packed grid, bq > bk
    (8, 16, "pallas", None),        # bq < bk
    (16, 16, "pallas", False),      # the full grid: compute skips
    (16, 8, "scan", None),          # the scan backward
    (16, 8, "fused", None),         # one backward kernel, the packed grid
    (8, 16, "fused", None),
    (16, 16, "fused", False),       # ... and the full one
])
def test_flash_with_a_shared_rope_key_matches_dense(bq, bk, bwd, truncate):
    """Keys of 24 (16 a head's own, 8 one vector a token for all heads) and
    values of 16, a scale that is the caller's: outputs of the values'
    width, ``dq`` of the queries', the shared key's gradient summed over the
    heads."""
    q, k, v, shared, w = _operands()
    flash = functools.partial(flash_attention, causal=True, block_q=bq,
                              block_k=bk, bwd_impl=bwd, truncate=truncate,
                              scale=0.3)
    dense = functools.partial(dot_product_attention, causal=True, scale=0.3)
    out = flash(q, k, v, k_shared=shared)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, dense(q, k, v, k_shared=shared),
                               atol=2e-5)
    got = jax.grad(lambda q, k, v, s: jnp.sum(
        flash(q, k, v, k_shared=s) * w), (0, 1, 2, 3))(q, k, v, shared)
    want = jax.grad(lambda q, k, v, s: jnp.sum(
        dense(q, k, v, k_shared=s) * w), (0, 1, 2, 3))(q, k, v, shared)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-5)


@pytest.mark.parametrize("bq, bk, truncate", [
    (16, 16, None), (16, 8, None), (8, 32, None), (16, 16, False)])
def test_the_fused_backward_equals_the_split_with_a_shared_key(bq, bk,
                                                               truncate):
    """Keys of 24 beside values of 16 and the shared rope key, four or more
    k-blocks under the last q-block: the one kernel's ``dq`` (its own columns
    and the shared ones), ``dk``, ``dv`` and the shared key's gradient are
    the two kernels' to the last bit."""
    q, k, v, shared, w = _operands()

    def grads(bwd):
        return jax.grad(lambda q, k, v, s: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, bwd_impl=bwd,
            truncate=truncate, scale=0.3, k_shared=s) * w), (0, 1, 2, 3))(
                q, k, v, shared)

    for a, b in zip(grads("fused"), grads("pallas")):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("bwd", ["pallas", "fused"])
@pytest.mark.parametrize("heads, kv_heads, window", [
    (4, 4, None), (4, 2, None), (4, 2, 24)])
def test_flash_at_key_and_value_widths_that_differ(heads, kv_heads, window,
                                                   bwd):
    """No shared key: keys of 24 and values of 16, also grouped and under a
    window, against the dense path."""
    q, _, _, _, w = _operands(heads)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    k = jax.random.normal(ks[0], (2, 64, kv_heads, 24))
    v = jax.random.normal(ks[1], (2, 64, kv_heads, 16))
    flash = functools.partial(flash_attention, causal=True, block_q=16,
                              block_k=8, window=window, bwd_impl=bwd)
    dense = functools.partial(dot_product_attention, causal=True,
                              window=window)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-5)


def test_attend_hands_the_widths_the_scale_and_the_key_to_both_paths():
    q, k, v, shared, _ = _operands()
    want = dot_product_attention(q, k, v, causal=True, scale=0.2,
                                 k_shared=shared)
    for impl in ("dense", "flash"):
        np.testing.assert_allclose(
            attend(q, k, v, k_shared=shared, scale=0.2, impl=impl,
                   **({"block_q": 16, "block_k": 16} if impl == "flash"
                      else {})), want, atol=2e-5)
    # the default scale is the keys' width's, not the values'
    np.testing.assert_allclose(
        attend(q, k, v, k_shared=shared, impl="dense"),
        dot_product_attention(q, k, v, causal=True, scale=24 ** -0.5,
                              k_shared=shared), atol=1e-6)
    with pytest.raises(ValueError, match="keys of 16"):
        flash_attention(q, v[..., :8], v, causal=True, k_shared=shared)


def test_the_plan_takes_the_two_widths():
    """One width, as every caller before, or ``(keys', values')``: the sweep
    at 192 beside 128 chose the blocks it had chosen at 64 and 128, so the
    answer is the lengths' alone."""
    assert attention_plan(4096, 4096, 32, 4, 128, 2048, backend="tpu") \
        == ("flash", 1024, 1024, "fused", 1, FLASH_SLAB)
    assert attention_plan(8192, 8192, 16, 16, (192, 128), backend="tpu",
                          shared_key=True) \
        == ("flash", 1024, 1024, "fused", 1,    # one head a program
            FLASH_SLAB)
    assert attention_plan(8192, 8192, 16, 16, (192, 128),
                          backend="cpu").impl == "dense"
