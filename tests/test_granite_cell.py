"""The Mamba-2 / attention hybrid (``models/decoder.py`` ``Mamba2Mixer``,
``--lm-layer-types mamba``) and the cell ``granite_h_micro_seq16384_1chip``
as the benchmark finds it, at a small size with seeded weights on the CPU:
the mixer and the lane's step against the plain reference
``benchmarks/reference/granite.py`` (whose scan is the recurrence token by
token), the tied head and the three multipliers each caught where left out,
the blocks of the other decoder configurations as they were, the
configuration's file against the catalog, ``flops_ssm.py`` against
hand-worked figures, the new readers on made-up records, the rehearsal of
the cell through ``benchmarks/run.py`` and the four planted faults read
false.

Tolerances and why:

* float32 program against the float32 reference: 2e-5 on every gap, as for
  the sparse decoder: the same function in the same precision; what is left
  is the order of additions (the chunked scan against the recurrence, read
  under 1e-6 in ``test_ssd.py``).
* the mixer alone, float32: 2e-5 absolute and relative on outputs of size
  one, 2e-4 on gradients (sums over 64 tokens).
* each of the reference's planted faults, and each multiplier or the tied
  head left out of the model, moves a result by over 1e-3: two orders above
  the tolerance.
"""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

import toy_cell  # noqa: E402
from benchmarks import (  # noqa: E402
    check_manifest, compare, flops_ssm, plant, run)
from benchmarks.reference import common, granite  # noqa: E402
from horovod_tpu import models  # noqa: E402
from horovod_tpu.models import decoder  # noqa: E402

CELL_NAME = "granite_h_micro_seq16384_1chip"
GPT2_CELL = "gpt2m_seq4096_flash_1chip"
NEW_METRICS = ["ssd_ms_per_step.tok", "ssd_roofline_pct.tok",
               "ssd_state_mib_per_step.tok"]
# Granite-4.0-H-Micro's published config.json, as the model-configs catalog
# holds it: every number has to stand in the file unchanged unless `reduced`
# names its key
PUBLISHED = {
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "max_position_embeddings": 131072,
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "vocab_size": 100352}
NOT_NUMBERS = {"attention_bias": False, "hidden_act": "silu",
               "mamba_conv_bias": True, "mamba_proj_bias": False,
               "model_type": "granitemoehybrid",
               "normalization_function": "rmsnorm",
               "position_embedding_type": "nope", "rope_scaling": None,
               "tie_word_embeddings": True}

HYPER = {"layers": 3, "heads": 4, "kv_heads": 2, "head_dim": 16,
         "attn_scale": 0.0625, "ssm_heads": 4, "ssm_head_dim": 16,
         "ssm_state": 32, "chunk": 16, "rms_norm_eps": 1e-05,
         "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
         "logits_scaling": 8.0, "carry_state": True, "skip_d": True,
         "gate_before_norm": True, "conv_causal": True,
         "optimizer": {"name": "adam", "lr": 0.0001, "b1": 0.9, "b2": 0.999,
                       "eps": 1e-08}}
SWAP = {"--lm-layers": "3", "--lm-dim": "64", "--lm-heads": "4",
        "--lm-kv-heads": "2", "--lm-head-dim": "16",
        "--lm-layer-types": "mamba,full,mamba", "--lm-attn-scale": "0.0625",
        "--ssm-heads": "4", "--ssm-head-dim": "16", "--ssm-state": "32",
        "--ssm-chunk": "16", "--lm-ffn": "96", "--lm-dense-layers": "3",
        "--vocab": "128"}
TOY_FLOPS = {"layer_types": ["mamba", "full_attention", "mamba"],
             "d_model": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
             "ffn": 96, "ssm_heads": 4, "ssm_head_dim": 16, "ssm_state": 32,
             "chunk": 16, "vocab": 128}


def _config():
    return run.load_json(REPO, "benchmarks", "configs",
                         "granite-4.0-h-micro.json")


def _toy_config():
    config = copy.deepcopy(_config())
    args = config["bench_args"]
    config["bench_args"] = [SWAP.get(args[i - 1], a) if i else a
                            for i, a in enumerate(args)]
    config["int_ranges"] = {"tokens": 128}
    config["reference"]["hyper"] = copy.deepcopy(HYPER)
    config["flops"]["args"] = TOY_FLOPS
    return config


TOY_CELL = {"name": "toy", "chips": 1, "compare_steps": 3,
            "bench_args": ["--batch-size", "2", "--seq-len", "48",
                           "--remat", "--fused-ce"],
            "reference_rows_per_block": 1}


# ------------------------------------------------------------ manifest


def test_manifest_is_well_formed_and_names_both_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        text = f.read()
    manifest = json.loads(text)
    assert check_manifest.check(manifest, REPO, len(text.encode())) == []
    assert len(manifest["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    manifest, cell, config = run.load_cell(CELL_NAME)
    assert cell["chips"] == 1 and cell["bench_args"] == [
        "--batch-size", "1", "--seq-len", "16384", "--remat", "--fused-ce"]
    reported = {m["name"] for m in run.metrics_of(manifest, CELL_NAME,
                                                  "per_layer")}
    assert set(NEW_METRICS) <= reported
    assert {"step_mfu_pct.tok", "device_step_ms.tok", "peak_hbm_gib.tok",
            "device_idle_pct.tok", "setup_lane_build_s",
            "recomputed_applications_per_step.tok"} <= reported
    # of the flash or expert lists it joins the slab counter's alone (its
    # attention layer walks its diagonal blocks in slabs)
    assert {m for m in reported if m.startswith(("flash", "moe", "mla"))} \
        == {"flash_diagonal_slab_calls_per_step.tok"}
    assert {m["name"] for m in run.metrics_of(manifest, CELL_NAME,
                                              "end_to_end")} \
        == {"tok_per_s_per_chip", "setup_s"}
    for name in reported:
        assert callable(run.load_reader(name))
    # the queued GPT-2 cell: the policy's flash kernels at 4,096 keys, the
    # fused loss, the two flash counters and no roofline reader
    manifest, cell, config = run.load_cell(GPT2_CELL)
    assert cell["bench_args"] == ["--batch-size", "2", "--seq-len", "4096",
                                  "--remat", "--fused-ce"]
    reported = {m["name"] for m in run.metrics_of(manifest, GPT2_CELL,
                                                  "per_layer")}
    assert {"flash_fused_bwd_calls_per_step.tok",
            "flash_paired_calls_per_step.tok", "step_mfu_pct.tok"} \
        <= reported
    assert not {"flash_roofline_pct.tok", "flash_ms_per_step.tok"} & reported
    assert not set(NEW_METRICS) & reported


def test_configuration_keeps_every_published_width():
    config = _config()
    reduced = set(config["reduced"])
    assert reduced == {"num_layers", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    for key, value in NOT_NUMBERS.items():
        assert config[key] == value and type(config[key]) is type(value), key
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert (config["num_layers"], config["vocab_size"]) == (10, 12544)
    assert len(config["layer_types"]) == 40
    assert config["layer_types_held"] == config["layer_types"][:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert config["deployment"]["parameters"]["all"] == 772_160_448
    assert {"mixer", "no_dt_clamp", "gate_before_norm", "mlp_split",
            "head", "optimizer"} <= set(config["assumed"])
    # the lane's arguments, the reference's hyper and the operation count
    # say the same sizes, and those are the file's
    import bench

    a = bench.build_parser().parse_args(config["bench_args"])
    hyper, flops = config["reference"]["hyper"], config["flops"]["args"]
    assert a.lm_dim == config["hidden_size"] == flops["d_model"]
    assert a.lm_heads == config["num_attention_heads"] == hyper["heads"] \
        == flops["heads"]
    assert a.lm_kv_heads == config["num_key_value_heads"] \
        == hyper["kv_heads"] == flops["kv_heads"]
    assert a.lm_head_dim == config["hidden_size"] // a.lm_heads \
        == hyper["head_dim"] == flops["head_dim"]
    assert a.ssm_heads == config["mamba_n_heads"] == hyper["ssm_heads"] \
        == flops["ssm_heads"]
    assert a.ssm_head_dim == config["mamba_d_head"] \
        == hyper["ssm_head_dim"] == flops["ssm_head_dim"]
    assert config["mamba_n_heads"] * config["mamba_d_head"] \
        == config["mamba_expand"] * config["hidden_size"]
    assert a.ssm_state == config["mamba_d_state"] == hyper["ssm_state"] \
        == flops["ssm_state"]
    assert a.ssm_conv == config["mamba_d_conv"]
    assert a.ssm_chunk == config["mamba_chunk_size"] == hyper["chunk"] \
        == flops["chunk"]
    assert a.lm_attn_scale == config["attention_multiplier"] \
        == hyper["attn_scale"]
    assert a.lm_embed_multiplier == config["embedding_multiplier"] \
        == hyper["embedding_multiplier"]
    assert a.lm_residual_scale == config["residual_multiplier"] \
        == hyper["residual_multiplier"]
    assert a.lm_logit_divisor == config["logits_scaling"] \
        == hyper["logits_scaling"]
    assert a.lm_ffn == config["shared_intermediate_size"] == flops["ffn"]
    assert a.lm_layers == config["num_layers"] == a.lm_dense_layers \
        == hyper["layers"] == len(flops["layer_types"])
    assert a.vocab == config["vocab_size"] == config["int_ranges"]["tokens"] \
        == flops["vocab"]
    held = ["full" if t == "attention" else t
            for t in config["layer_types_held"]]
    assert a.lm_layer_types == ",".join(held)
    assert a.lm_tie_head and not (a.lm_qk_norm or a.lm_attn_gate
                                  or a.lm_output_norms or a.lm_embed_scale)
    assert all(hyper[k] is True for k in ("carry_state", "skip_d",
                                          "gate_before_norm", "conv_causal"))


def test_the_program_holds_the_stated_parameters():
    import bench

    config = _config()
    args = bench.build_parser().parse_args(config["bench_args"])
    model = models.build("moe_lm", vocab_size=args.vocab,
                         **bench.lm_model_args(args, "dense"))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 772_160_448
    assert "lm_head" not in shapes
    mamba = shapes["DecoderBlock_0"]["mamba"]
    assert mamba["in_proj"]["kernel"].shape == (2048, 4096 + 4352 + 64)
    assert mamba["conv1d_kernel"].shape == (4, 4352)
    assert mamba["out_proj"]["kernel"].shape == (4096, 2048)
    assert shapes["DecoderBlock_5"]["attn"]["k"]["kernel"].shape \
        == (2048, 512)


def test_operation_counts_are_the_hand_worked_ones():
    args = _config()["flops"]["args"]
    assert flops_ssm.matmul_params_per_token(**args) == 771_883_008
    assert flops_ssm.scan_macs_per_token(**args) == 9 * 1_591_360
    assert flops_ssm.attention_macs_per_token(**args, seq_len=16384) \
        == 32 * 128 * 8192.5
    assert flops_ssm.per_token(**args, seq_len=16384) == 4_918_570_368
    ops, nbytes = flops_ssm.ssd_work(fwd_calls=18, bwd_calls=9,
                                     tokens_per_step=16384, **args)
    t, n, p, h = 256, 128, 64, 64
    assert ops == 2 * 64 * (18 * (t * t * n + h * (t * t * p + 2 * t * n * p))
                            + 9 * (3 * t * t * n + h * (2 * t * t * p
                                                        + 4 * t * n * p)))
    states = 64 * h * n * p * 4
    assert states == 128 * 2 ** 20
    fwd = 16384 * (4352 + 4096) * 2 + 2 * 16384 * 64 * 4 + states
    bwd = fwd + 16384 * 4096 * 2 + 2 * 16384 * 128 * 4 + 2 * 16384 * 64 * 4
    assert nbytes == 18 * fwd + 9 * bwd


def _record(device_ops, steps=6):
    return {"trace": {"steps": steps, "device_ops": device_ops},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "window": {"units_per_step_per_chip": 16384},
            "config": _config(), "cell": {"flops_args": {"seq_len": 16384}}}


def test_readers_on_made_up_records(monkeypatch):
    from benchmarks.metrics import program_spans

    read = {name: run.load_reader(name) for name in NEW_METRICS}
    ops = [["fusion bf16[1,16384,8192]", 0.5], ["hvd_ssd_scan", 0.18]]
    record = _record(ops)
    gauges = {"hvd.ssd.fwd_calls": 18, "hvd.ssd.state_bytes": 18 * 2 ** 27}
    monkeypatch.setattr(program_spans, "step_gauge", gauges.get)
    assert read["ssd_ms_per_step.tok"](record) == pytest.approx(30.0)
    ops_, nbytes = flops_ssm.ssd_work(
        fwd_calls=18, bwd_calls=9, tokens_per_step=16384,
        **_config()["flops"]["args"])
    least = max(ops_ / 197e12, nbytes / 819e9)
    assert read["ssd_roofline_pct.tok"](record) == pytest.approx(
        100 * least / 30e-3)
    assert read["ssd_state_mib_per_step.tok"]({}) == 18 * 128.0
    # a family outside the ten largest, no trace, no gauge (the parent's
    # program): nothing to read, and no error
    missing = _record([op for op in ops if op[0] != "hvd_ssd_scan"])
    assert read["ssd_ms_per_step.tok"](missing) is None
    assert read["ssd_roofline_pct.tok"](missing) is None
    assert read["ssd_ms_per_step.tok"](dict(record, trace=None)) is None
    monkeypatch.setattr(program_spans, "step_gauge", lambda name: None)
    assert read["ssd_roofline_pct.tok"](record) is None
    assert read["ssd_state_mib_per_step.tok"]({}) is None


# ------------------------------------------------------ model, reference


@pytest.fixture(scope="module")
def program(hvd):
    """The toy configuration's lane as ``run.py`` builds it, float32,
    dense attention, one sequence a chip."""
    config = _toy_config()
    config["bench_args"] += ["--fp32", "--attention", "dense"]
    return run.Program(config, dict(TOY_CELL, chips=hvd.size()))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_three_adam_steps_match_the_reference(program, seed):
    """Loss of each step, every leaf's first gradient (the embedding's
    through both of its uses) and every leaf's change over three Adam steps,
    through the lane's own call."""
    assert isinstance(program.lane.model, decoder.SparseDecoderLM)
    state, batch = program.start(seed)
    state, prog = program.first_steps(state, batch, seed)
    ref = program.reference(seed, jax.devices()[0])
    for name, (gap, where) in compare.gaps(prog, ref).items():
        assert gap < 2e-5, (name, gap, where)
    assert sorted(prog["grad_norms"]) == sorted(ref["grad_norms"])
    params = state["params"]
    assert set(params) == {"embed", "final_norm", "DecoderBlock_0",
                           "DecoderBlock_1", "DecoderBlock_2"}
    assert set(params["DecoderBlock_0"]) == {"norm_attn", "mamba",
                                             "norm_ffn", "mlp"}
    assert set(params["DecoderBlock_1"]["attn"]) == {"q", "k", "v", "out"}


def _mixer():
    mixer = decoder.Mamba2Mixer(heads=4, head_dim=16, state=32, chunk=16,
                                dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 24))
    params = mixer.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2), a.shape),
        params)
    # slow decays, so that the state carried from chunk to chunk counts
    params = dict(params, A_log=jnp.zeros(4), dt_bias=jnp.full((4,), -3.0))
    return mixer, x, params


def test_the_mixer_is_the_references_forward_and_gradients():
    mixer, x, params = _mixer()
    einsum = common.make_einsum("float32")
    w = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 24))

    def theirs(x, params, **fault):
        return granite._mamba(x, params, hyper=dict(HYPER, **fault),
                              einsum=einsum)

    sound = mixer.apply({"params": params}, x)
    np.testing.assert_allclose(sound, theirs(x, params), rtol=2e-5,
                               atol=2e-5)
    got = jax.grad(lambda x, p: jnp.sum(mixer.apply({"params": p}, x) * w),
                   (0, 1))(x, params)
    want = jax.grad(lambda x, p: jnp.sum(theirs(x, p) * w), (0, 1))(x,
                                                                   params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    # what the reference's four planted faults turn is in the mixer
    for fault in ({"carry_state": False}, {"skip_d": False},
                  {"gate_before_norm": False}, {"conv_causal": False}):
        bad = theirs(x, params, **fault)
        assert float(jnp.abs(bad - sound).max()) > 1e-3, fault


def _toy_model(**fields):
    import bench

    args = bench.build_parser().parse_args(_toy_config()["bench_args"])
    kw = dict(bench.lm_model_args(args, "dense"), **fields)
    return models.build("moe_lm", vocab_size=128, dtype=jnp.float32, **kw)


def test_the_tied_head_and_each_multiplier_are_caught_if_left_out():
    """The model's logits and first gradient against the reference's: each
    of the three multipliers left out moves the logits, and a head of its
    own (the embedding's transpose copied into it) gives the same logits but
    another gradient of the embedding, which then has one use, not two."""
    from benchmarks import weights

    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 48), 0, 128)
    model = _toy_model()
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               tokens))["params"]
    params = weights.draw_params(jax.random.PRNGKey(5), shapes, 1.0,
                                 _config()["draws"])
    einsum = common.make_einsum("float32")

    def ref_loss(p):
        return granite._nll_rows(p, tokens, hyper=HYPER,
                                 einsum=einsum).sum() / 47

    def loss(model, p):
        logits = model.apply({"params": p}, tokens)
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))

    np.testing.assert_allclose(loss(model, params), ref_loss(params),
                               rtol=2e-5)
    want = jax.grad(ref_loss)(params)

    def worst_leaf(got):        # compare.py's measure of a gradient's gap
        norms = [(float(jnp.linalg.norm(a)), float(jnp.linalg.norm(b)))
                 for a, b in zip(jax.tree_util.tree_leaves(got),
                                 jax.tree_util.tree_leaves(want))]
        return max(abs(a - b) / b for a, b in norms)

    assert worst_leaf(jax.grad(lambda p: loss(model, p))(params)) < 1e-4
    for fault in ({"embed_multiplier": None}, {"residual_scale": 1.0},
                  {"logit_divisor": 1.0}):
        faulty = _toy_model(**fault)
        assert worst_leaf(jax.grad(lambda p: loss(faulty, p))(params)) \
            > 1e-2, fault
    untied = _toy_model(tie_head=False)
    own = dict(params, lm_head={"kernel": params["embed"]["embedding"].T})
    np.testing.assert_allclose(loss(untied, own), ref_loss(params),
                               rtol=2e-5)
    want = want["embed"]["embedding"]
    tied = jax.grad(lambda p: loss(model, p))(params)["embed"]["embedding"]
    alone = jax.grad(lambda p: loss(untied, p))(own)["embed"]["embedding"]
    np.testing.assert_allclose(tied, want, rtol=2e-4, atol=1e-7)
    assert float(jnp.abs(alone - want).max()) > 1e-2 * float(
        jnp.abs(want).max())


# The parameter trees of the other configurations' blocks at toy widths, as
# they stood before a grouped layer's choices became the model's fields.
TRINITY_TOY = dict(
    vocab_size=128, embed_dim=64, layer_types=("sliding_attention",
                                               "full_attention"),
    heads=4, kv_heads=2, head_dim=16, window=16, dense_layers=1,
    dense_width=96, experts=8, experts_held=4, top_k=2, expert_width=32)
MOONLIGHT_TOY = dict(
    vocab_size=128, embed_dim=64, layer_types=("latent_attention",) * 2,
    heads=4, kv_heads=4, head_dim=16, rope_dim=8, value_dim=16,
    latent_dim=32, window=0, dense_layers=1, dense_width=96, experts=8,
    experts_held=4, top_k=3, expert_width=32, shared_experts=2,
    norm_outputs=False, embed_scale=False)
# The parameter trees and logits of those toy models at the parent commit of
# PR 38 (my CPU run, PR 38): the change reads them to the last bit.
TREES = {
    "trinity": {
        "DecoderBlock_0/attn/gate/kernel": (64, 64),
        "DecoderBlock_0/attn/k/kernel": (64, 32),
        "DecoderBlock_0/attn/k_norm/scale": (16,),
        "DecoderBlock_0/attn/out/kernel": (64, 64),
        "DecoderBlock_0/attn/q/kernel": (64, 64),
        "DecoderBlock_0/attn/q_norm/scale": (16,),
        "DecoderBlock_0/attn/v/kernel": (64, 32),
        "DecoderBlock_0/mlp/down/kernel": (96, 64),
        "DecoderBlock_0/mlp/gate/kernel": (64, 96),
        "DecoderBlock_0/mlp/up/kernel": (64, 96),
        "DecoderBlock_0/norm_attn/scale": (64,),
        "DecoderBlock_0/norm_attn_out/scale": (64,),
        "DecoderBlock_0/norm_ffn/scale": (64,),
        "DecoderBlock_0/norm_ffn_out/scale": (64,),
        "DecoderBlock_1/attn/gate/kernel": (64, 64),
        "DecoderBlock_1/attn/k/kernel": (64, 32),
        "DecoderBlock_1/attn/k_norm/scale": (16,),
        "DecoderBlock_1/attn/out/kernel": (64, 64),
        "DecoderBlock_1/attn/q/kernel": (64, 64),
        "DecoderBlock_1/attn/q_norm/scale": (16,),
        "DecoderBlock_1/attn/v/kernel": (64, 32),
        "DecoderBlock_1/moe/experts_down": (4, 32, 64),
        "DecoderBlock_1/moe/experts_gate": (4, 64, 32),
        "DecoderBlock_1/moe/experts_up": (4, 64, 32),
        "DecoderBlock_1/moe/router": (64, 8),
        "DecoderBlock_1/moe/shared/down/kernel": (32, 64),
        "DecoderBlock_1/moe/shared/gate/kernel": (64, 32),
        "DecoderBlock_1/moe/shared/up/kernel": (64, 32),
        "DecoderBlock_1/norm_attn/scale": (64,),
        "DecoderBlock_1/norm_attn_out/scale": (64,),
        "DecoderBlock_1/norm_ffn/scale": (64,),
        "DecoderBlock_1/norm_ffn_out/scale": (64,),
        "embed/embedding": (128, 64),
        "final_norm/scale": (64,),
        "lm_head/kernel": (64, 128),
    },
    "moonlight": {
        "DecoderBlock_0/attn/kv_a/kernel": (64, 40),
        "DecoderBlock_0/attn/kv_b/kernel": (32, 128),
        "DecoderBlock_0/attn/kv_norm/scale": (32,),
        "DecoderBlock_0/attn/out/kernel": (64, 64),
        "DecoderBlock_0/attn/q/kernel": (64, 96),
        "DecoderBlock_0/mlp/down/kernel": (96, 64),
        "DecoderBlock_0/mlp/gate/kernel": (64, 96),
        "DecoderBlock_0/mlp/up/kernel": (64, 96),
        "DecoderBlock_0/norm_attn/scale": (64,),
        "DecoderBlock_0/norm_ffn/scale": (64,),
        "DecoderBlock_1/attn/kv_a/kernel": (64, 40),
        "DecoderBlock_1/attn/kv_b/kernel": (32, 128),
        "DecoderBlock_1/attn/kv_norm/scale": (32,),
        "DecoderBlock_1/attn/out/kernel": (64, 64),
        "DecoderBlock_1/attn/q/kernel": (64, 96),
        "DecoderBlock_1/moe/experts_down": (4, 32, 64),
        "DecoderBlock_1/moe/experts_gate": (4, 64, 32),
        "DecoderBlock_1/moe/experts_up": (4, 64, 32),
        "DecoderBlock_1/moe/router": (64, 8),
        "DecoderBlock_1/moe/shared/down/kernel": (64, 64),
        "DecoderBlock_1/moe/shared/gate/kernel": (64, 64),
        "DecoderBlock_1/moe/shared/up/kernel": (64, 64),
        "DecoderBlock_1/norm_attn/scale": (64,),
        "DecoderBlock_1/norm_ffn/scale": (64,),
        "embed/embedding": (128, 64),
        "final_norm/scale": (64,),
        "lm_head/kernel": (64, 128),
    }}
LOGITS = {  # sum, sum of magnitudes, logits[0, 5, 7]
    "trinity": (-94.65361022949219, 3248.39501953125, 0.3218030035495758),
    "moonlight": (19.702800750732422, 3248.1982421875, -0.6692562699317932)}


@pytest.mark.parametrize("name, sizes", [("trinity", TRINITY_TOY),
                                         ("moonlight", MOONLIGHT_TOY)])
def test_the_other_decoders_blocks_are_as_they_were(name, sizes):
    """Trinity's and Moonlight's parameter trees, leaf for leaf, and their
    logits as the parent commit computed them (the q/k norms and the gate
    where ``afmoe`` has them; nothing of the new fields enters)."""
    model = models.build("moe_lm", dtype=jnp.float32, attention="dense",
                         **sizes)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 32), 0, 128)
    variables = model.init(jax.random.PRNGKey(1), tokens)
    flat, _ = jax.tree_util.tree_flatten_with_path(variables["params"])
    assert {"/".join(str(k.key) for k in path): tuple(x.shape)
            for path, x in flat} == TREES[name]
    logits = model.apply(variables, tokens)
    np.testing.assert_allclose(
        (float(jnp.sum(logits)), float(jnp.sum(jnp.abs(logits))),
         float(logits[0, 5, 7])), LOGITS[name], rtol=1e-5)
    block = model.block(1)
    assert block.residual_scale == 1.0
    if name == "trinity":
        assert block.attn["qk_norm"] and block.attn["gate"] \
            and "scale" not in block.attn


def test_kept_bytes_of_a_mamba_block():
    """The closed sum a Mamba block keeps when not recomputed, at Granite's
    widths over 16,384 tokens: the stream and its norm (2 x 2,048), the
    in-projection's output (8,512), the conv's (4,352) and y (4,096), all
    bfloat16; Delta twice in float32; the chunk states (64 x 128 x 64
    float32 a chunk of 256: 8,192 bytes a token); the MLP's three operands
    of 8,192 (1.21 of the compiler's count: tests/test_chip_smoke.py)."""
    model = _toy_model().clone(
        embed_dim=2048, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
        ssm_chunk=256, dense_width=8192, dtype=jnp.bfloat16)
    per_token = (2 * 2048 + 8512 + 4352 + 4096 + 3 * 8192) * 2 \
        + 2 * 4 * 64 + 8192
    assert model.block(0).kept_bytes(16384, 2048) == 16384 * per_token


# ------------------------------------------------------------- rehearsal


def _toy_tree(root):
    """A copy of ``benchmarks/`` plus the configuration at a toy size, its
    cell and the manifest's new entries retargeted to it: new files only."""
    config = _toy_config()
    cell = run.load_json(REPO, "benchmarks", "workloads", CELL_NAME + ".json")
    cell.update(config="toy_granite", traffic="toy_1", trace_steps=4,
                bench_args=TOY_CELL["bench_args"], flops_args={"seq_len": 48},
                limits={"loss1_gap": 0.03, "loss2_gap": 0.03,
                        "loss3_gap": 0.03, "grad_median_gap": 0.03,
                        "delta_median_gap": 0.03})
    toy_cell.add_toy_cell(root, "toy_granite", config, cell, NEW_METRICS)
    return config, cell


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_at_a_toy_size(tmp_path, trace):
    root = str(tmp_path)
    _toy_tree(root)
    result, err = toy_cell.drive_toy_cell(root, "toy_granite_1chip",
                                          trace=trace, seed=2 ** 31 + 17)
    assert result["correct"], err[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    if trace:
        # program counters read on the CPU too; a device trace does not.
        # 2 sequences of 48 tokens on the one device, 3 chunks of 16, 4
        # heads, a state of 32 x 16 float32; both Mamba blocks recomputed
        # (the CPU reports no memory limit), so each runs its forward twice
        assert result["metrics"]["ssd_state_mib_per_step.tok"]["value"] \
            == 2 * 2 * (2 * 3 * 4 * 32 * 16 * 4) / 2 ** 20
        assert not {"ssd_ms_per_step.tok", "ssd_roofline_pct.tok",
                    "step_mfu_pct.tok"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"tok_per_s_per_chip", "setup_s"}


@pytest.mark.parametrize("name, setting, planted", [
    ("state_dropped", "carry_state=false", ("carry_state", False)),
    ("d_left_out", "skip_d=false", ("skip_d", False)),
    ("gate_after_norm", "gate_before_norm=false",
     ("gate_before_norm", False)),
    ("conv_sees_ahead", "conv_causal=false", ("conv_causal", False))])
def test_each_planted_fault_reads_false(program, name, setting, planted):
    """``benchmarks/plant.py`` on the toy lane: the reference with one of
    the mechanism's four faults in its ``hyper`` put in the program's place
    reads ``correct`` false under limits that the float32 program passes ten
    times over."""
    plants = plant.parse_plants([f"{name}:{setting}"])
    assert plants == {name: planted}
    program.cell = dict(program.cell, limits={
        "loss1_gap": 2e-4, "loss3_gap": 2e-4, "grad_gap": 2e-4,
        "delta_gap": 2e-3})
    line, = plant.planted(program, jax.devices()[0], [5], plants)
    assert line["kind"] == "fault_" + name and line["correct"] is False
    assert {"loss1_gap", "grad_gap"} & set(line["over"]), line
    hyper = program.config["reference"]["hyper"]
    assert all(hyper[k] is True for k in ("carry_state", "skip_d",
                                          "gate_before_norm", "conv_causal"))
