"""The control of a training cell, at a size a test run can hold: the plain
reference computed in the precision below the one the configuration states
(fp8 for bfloat16) and put in the program's place comes out as not correct
under the toy cell's limits, while the reference in bfloat16 (what the
configuration states) passes them. Once for the LM and once for ResNet-50,
each on three seeds, and each with half of the batch left out beside it."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

import toy
from benchmarks import compare, weights
from benchmarks.reference import gpt2, resnet50

HYPER = toy.TOY_LM["reference"]["hyper"]
LIMITS = toy.LIMITS_LM


def _readings(seed, precision, **kw):
    import jax
    import jax.numpy as jnp

    key = weights.run_key(seed)
    shapes = {
        "Embed_0": {"embedding": (512, 64)}, "Embed_1": {"embedding": (64, 64)},
        "LayerNorm_0": {"scale": (64,), "bias": (64,)},
        "lm_head": {"kernel": (64, 512)}}
    for i in range(2):
        shapes[f"TransformerBlock_{i}"] = {
            "LayerNorm_0": {"scale": (64,), "bias": (64,)},
            "LayerNorm_1": {"scale": (64,), "bias": (64,)},
            "Dense_0": {"kernel": (64, 192)},
            "Dense_1": {"kernel": (64, 64), "bias": (64,)},
            "Dense_2": {"kernel": (64, 256), "bias": (256,)},
            "Dense_3": {"kernel": (256, 64), "bias": (64,)}}
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    params = weights.draw_params(key, shapes, 1.0)
    batch = weights.draw_batch(
        key, {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)},
        {"tokens": 512})
    return gpt2.train_steps(params, batch, HYPER, steps=3,
                            precision=precision, loss_rows=8,
                            rows_per_block=4, **kw)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 5])
def test_fp8_in_the_programs_place_is_not_correct(seed):
    ref = _readings(seed, "float32")
    stated, _ = compare.decide(
        compare.gaps(_readings(seed, "bfloat16"), ref), LIMITS)
    control, rows = compare.decide(
        compare.gaps(_readings(seed, "fp8"), ref), LIMITS)
    assert stated
    assert not control, rows


def test_half_the_batch_in_the_references_place_is_not_correct():
    ref = _readings(3, "float32")
    ok, rows = compare.decide(
        compare.gaps(_readings(3, "float32", use_rows=4), ref), LIMITS)
    assert not ok, rows


def _resnet_readings(seed, precision, **kw):
    import jax
    import jax.numpy as jnp

    config = toy.TOY_RESNET
    hyper = config["reference"]["hyper"]
    key = weights.run_key(seed)
    params = weights.draw_params(key, toy.resnet_shapes(hyper["stages"]),
                                 config["kernel_gain"], config["draws"])
    batch = weights.draw_batch(
        key, {"image": jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.float32),
              "label": jax.ShapeDtypeStruct((8,), jnp.int32)},
        config["int_ranges"])
    return resnet50.train_steps(params, batch, hyper, steps=3,
                                precision=precision, loss_rows=8, **kw)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 5])
def test_resnet_fp8_and_half_the_batch_are_not_correct(seed):
    ref = _resnet_readings(seed, "float32")
    stated, rows = compare.decide(
        compare.gaps(_resnet_readings(seed, "bfloat16"), ref),
        toy.LIMITS_RESNET)
    assert stated, rows
    control, rows = compare.decide(
        compare.gaps(_resnet_readings(seed, "fp8"), ref), toy.LIMITS_RESNET)
    assert not control, rows
    over = {name for name, gap, limit, _ in rows
            if limit is not None and gap > limit}
    assert over >= {"stat_gap", "stat_median_gap", "loss2_gap"}
    half, rows = compare.decide(
        compare.gaps(_resnet_readings(seed, "float32", use_rows=4), ref),
        toy.LIMITS_RESNET)
    assert not half, rows
    over = {name for name, gap, limit, _ in rows
            if limit is not None and gap > limit}
    assert over >= {"grad_gap", "delta_gap"}
