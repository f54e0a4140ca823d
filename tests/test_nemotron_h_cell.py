"""The single-branch hybrid (``models/decoder.py`` ``MixerBlock``,
``--lm-pattern``: grouped Mamba-2, squared-ReLU experts, grouped attention
with no positions) and the cell ``nemotron3_nano_seq8192_1chip`` as the
benchmark finds it, at a small size with seeded weights on the CPU: the
lane's step against the plain reference ``benchmarks/reference/
nemotron_h.py`` (whose scan is the recurrence token by token), the grouped
mixer against the reference's, the chip's share of an expert layer against
the uncut layer, the configuration's file against the catalog and its
parameter count, ``flops_hybrid_moe.py`` against hand-worked figures, the
new readers on made-up records, the rehearsal of the cell through
``benchmarks/run.py`` and the four planted faults read false.

Tolerances and why:

* float32 program against the float32 reference: 2e-5 on every gap, as for
  the other decoders: the same function in the same precision; what is left
  is the order of additions (the chunked scan against the recurrence, read
  under 1e-6 in ``test_ssd.py``).
* the mixer and the expert layer alone, float32: 2e-5 absolute and relative
  on outputs of size one, 2e-4 on gradients (sums over 48 tokens).
* each of the reference's planted faults moves a result by over 1e-3: two
  orders above the tolerance.
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
    check_manifest, compare, flops_hybrid_moe, plant, run)
from benchmarks.reference import common, nemotron_h  # noqa: E402
from horovod_tpu import models  # noqa: E402
from horovod_tpu.models import decoder  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

CELL_NAME = "nemotron3_nano_seq8192_1chip"
CONFIG = "nemotron-3-nano-30b-a3b"
NEW_METRICS = ["ssd_grouped_roofline_pct.tok",
               "moe_ungated_gmm_roofline_pct.tok"]
# The catalog's config of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16: every number
# has to stand in the file unchanged unless `reduced` names its key
PUBLISHED = {
    "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_num_heads": 64, "max_position_embeddings": 262144,
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "ssm_state_size": 128,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "vocab_size": 131072}
NOT_NUMBERS = {
    "attention_bias": False,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "mamba_hidden_act": "silu", "mamba_proj_bias": False, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "norm_topk_prob": True, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "sliding_window": None,
    "tie_word_embeddings": False, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True}
PATTERN = "MEMEM*EME"
# one layer of each kind: the reference's recurrence compiles in seconds
TOY_PATTERN = "M*E"

HYPER = {"pattern": TOY_PATTERN, "heads": 8, "kv_heads": 2, "head_dim": 8,
         "ssm_heads": 8, "ssm_head_dim": 16, "ssm_state": 16,
         "ssm_groups": 4, "rms_norm_eps": 1e-05, "experts": 16,
         "first_expert": 0, "top_k": 3, "route_scale": 2.5,
         "load_balance_coeff": 0.001, "grouped_bc": True,
         "grouped_norm": True, "squared_relu": True, "shared_gated": False,
         "optimizer": {"name": "adam", "lr": 0.0001, "b1": 0.9, "b2": 0.999,
                       "eps": 1e-08}}
SWAP = {"--lm-layers": "3", "--lm-pattern": TOY_PATTERN, "--lm-dim": "64", "--lm-heads": "8", "--lm-kv-heads": "2",
        "--lm-head-dim": "8", "--ssm-heads": "8", "--ssm-head-dim": "16",
        "--ssm-state": "16", "--ssm-groups": "4", "--ssm-chunk": "16",
        "--moe-experts": "16", "--moe-experts-held": "4", "--moe-top-k": "3",
        "--moe-width": "32", "--moe-shared": "2", "--vocab": "128"}
TOY_FLOPS = {"pattern": TOY_PATTERN, "d_model": 64, "heads": 8, "kv_heads": 2,
             "head_dim": 8, "ssm_heads": 8, "ssm_head_dim": 16,
             "ssm_state": 16, "ssm_groups": 4, "chunk": 16, "experts": 16,
             "experts_held": 4, "top_k": 3, "expert_width": 32,
             "shared_width": 64, "vocab": 128}
# at the toy widths the configuration's draws, which take their deviations
# from the published fan-ins, would leave the experts' part of a layer small
TOY_DRAWS = {"experts_up": {"mean": 0.0, "std": 0.125},
             "experts_down": {"mean": 0.0, "std": 32 ** -0.5},
             "moe/router": {"mean": 0.0, "std": 0.5}}


def _config():
    return run.load_json(REPO, "benchmarks", "configs", CONFIG + ".json")


def _toy_config():
    config = copy.deepcopy(_config())
    args = config["bench_args"]
    config["bench_args"] = [SWAP.get(args[i - 1], a) if i else a
                            for i, a in enumerate(args)]
    config["int_ranges"] = {"tokens": 128}
    config["reference"]["hyper"] = copy.deepcopy(HYPER)
    config["flops"]["args"] = TOY_FLOPS
    config["draws"].update(TOY_DRAWS)
    return config


TOY_CELL = {"name": "toy", "chips": 1, "compare_steps": 3,
            "bench_args": ["--batch-size", "2", "--seq-len", "48",
                           "--remat", "--fused-ce"],
            "reference_rows_per_block": 1}


# ------------------------------------------------------------ manifest


def test_manifest_is_well_formed_and_names_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        text = f.read()
    manifest = json.loads(text)
    assert check_manifest.check(manifest, REPO, len(text.encode())) == []
    assert CELL_NAME in [w["name"] for w in manifest["workloads"]]
    assert CONFIG in [c["name"] for c in manifest["configs"]]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    manifest, cell, config = run.load_cell(CELL_NAME)
    assert cell["chips"] == 1 and cell["bench_args"] == [
        "--batch-size", "2", "--seq-len", "8192", "--remat", "--fused-ce"]
    reported = {m["name"] for m in run.metrics_of(manifest, CELL_NAME,
                                                  "per_layer")}
    assert set(NEW_METRICS) <= reported
    assert {"step_mfu_pct.tok", "device_step_ms.tok", "peak_hbm_gib.tok",
            "device_idle_pct.tok", "setup_lane_build_s",
            "recomputed_applications_per_step.tok", "ssd_ms_per_step.tok",
            "ssd_state_mib_per_step.tok", "moe_gmm_ms_per_step.tok",
            "moe_row_bound_ratio.tok"} <= reported
    # the readers hard-wired to one group or to gated experts, and the dark
    # flash and latent readers, are not listed for it
    assert not {"ssd_roofline_pct.tok", "moe_gmm_roofline_pct.tok",
                "flash_ms_per_step.tok", "flash_roofline_pct.tok",
                "mla_attn_roofline_pct.tok"} & reported
    assert {m["name"] for m in run.metrics_of(manifest, CELL_NAME,
                                              "end_to_end")} \
        == {"tok_per_s_per_chip", "setup_s"}
    for name in reported:
        assert callable(run.load_reader(name))
    # the new metrics are read in this cell alone
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL_NAME]


def test_configuration_keeps_every_published_width():
    config = _config()
    reduced = set(config["reduced"])
    assert reduced == {"num_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    for key, value in NOT_NUMBERS.items():
        assert config[key] == value and type(config[key]) is type(value), key
    assert config["published"] == {"num_hidden_layers": 52,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 8, 16384)
    assert config["pattern_held"] == config["hybrid_override_pattern"][:9] \
        == PATTERN
    deployment = config["deployment"]
    assert (deployment["pipeline_stages"],
            deployment["chips_sharing_a_layer"]) == (6, 16)
    assert deployment["parameters"]["all"] == 666_962_944
    assert {"layer", "mixer", "no_dt_clamp", "grouped_gated_norm",
            "attention", "no_rotary", "experts", "head",
            "optimizer"} <= set(config["assumed"])
    # the lane's arguments, the reference's hyper and the operation count
    # say the same sizes, and those are the file's
    import bench

    a = bench.build_parser().parse_args(config["bench_args"])
    hyper, flops = config["reference"]["hyper"], config["flops"]["args"]
    assert a.lm_dim == config["hidden_size"] == flops["d_model"]
    assert a.lm_pattern == hyper["pattern"] == flops["pattern"] == PATTERN
    assert a.lm_layers == config["num_layers"] == len(PATTERN)
    assert a.lm_heads == config["num_attention_heads"] == hyper["heads"] \
        == flops["heads"]
    assert a.lm_kv_heads == config["num_key_value_heads"] \
        == hyper["kv_heads"] == flops["kv_heads"]
    assert a.lm_head_dim == config["head_dim"] == hyper["head_dim"] \
        == flops["head_dim"]
    assert a.ssm_heads == config["mamba_num_heads"] == hyper["ssm_heads"] \
        == flops["ssm_heads"]
    assert a.ssm_head_dim == config["mamba_head_dim"] \
        == hyper["ssm_head_dim"] == flops["ssm_head_dim"]
    assert a.ssm_state == config["ssm_state_size"] == hyper["ssm_state"] \
        == flops["ssm_state"]
    assert a.ssm_groups == config["n_groups"] == hyper["ssm_groups"] \
        == flops["ssm_groups"]
    assert a.ssm_conv == config["conv_kernel"]
    assert a.ssm_chunk == config["chunk_size"] == flops["chunk"]
    # the inner width is heads x head size, not expand x hidden size
    assert a.ssm_heads * a.ssm_head_dim == 4096 \
        != config["expand"] * config["hidden_size"]
    assert a.moe_experts == config["published"]["n_routed_experts"] \
        == hyper["experts"] == flops["experts"]
    assert a.moe_experts_held == config["n_routed_experts"] \
        == flops["experts_held"]
    assert a.moe_top_k == config["num_experts_per_tok"] == hyper["top_k"] \
        == flops["top_k"]
    assert a.moe_width == config["moe_intermediate_size"] \
        == flops["expert_width"]
    # one shared expert of 3,712 is two of the routed width: relu^2 acts on
    # each column alone
    assert a.moe_shared * a.moe_width \
        == config["moe_shared_expert_intermediate_size"] \
        == flops["shared_width"]
    assert config["n_shared_experts"] == 1
    assert a.moe_route_scale == config["routed_scaling_factor"] \
        == hyper["route_scale"]
    assert a.moe_act == "relu2"
    assert a.vocab == config["vocab_size"] == config["int_ranges"]["tokens"] \
        == flops["vocab"]
    assert not (a.lm_qk_norm or a.lm_attn_gate or a.lm_embed_scale
                or a.lm_tie_head)
    assert hyper["grouped_bc"] and hyper["grouped_norm"] \
        and hyper["squared_relu"] and not hyper["shared_gated"]


def _full_model():
    import bench

    args = bench.build_parser().parse_args(_config()["bench_args"])
    return models.build("moe_lm", vocab_size=args.vocab,
                        **bench.lm_model_args(args, "dense"))


def test_the_program_holds_the_stated_parameters():
    """The pattern builds M E M E M * E M E, one branch a layer, and the
    parameters are the configuration's 666,962,944 at the full widths."""
    model = _full_model()
    assert [model.block(i).kind for i in range(9)] == list(PATTERN)
    assert all(isinstance(model.block(i), decoder.MixerBlock)
               for i in range(9))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 666_962_944
    assert shapes["lm_head"]["kernel"].shape == (2688, 16384)
    mamba = shapes["DecoderBlock_0"]["mamba"]
    assert mamba["in_proj"]["kernel"].shape == (2688, 4096 + 6144 + 64)
    assert mamba["conv1d_kernel"].shape == (4, 6144)
    assert mamba["norm"]["scale"].shape == (4096,)
    assert mamba["out_proj"]["kernel"].shape == (4096, 2688)
    experts = shapes["DecoderBlock_1"]["moe"]
    assert set(experts) == {"router", "experts_up", "experts_down",
                            "shared"}
    assert experts["experts_up"].shape == (8, 2688, 1856)
    assert experts["router"].shape == (2688, 128)
    assert experts["shared"]["up"]["kernel"].shape == (2688, 3712)
    attn = shapes["DecoderBlock_5"]["attn"]
    assert attn["k"]["kernel"].shape == (2688, 256)
    assert attn["q"]["kernel"].shape == (2688, 4096)
    assert set(shapes["DecoderBlock_5"]) == {"norm", "attn"}


def test_operation_counts_are_the_hand_worked_ones():
    args = _config()["flops"]["args"]
    f = flops_hybrid_moe
    assert f.matmul_params_per_token(**args) == 318_431_232
    assert f.scan_macs_per_token(**args) == 4 * 1_378_816
    assert f.attention_macs_per_token(**args, seq_len=8192) \
        == 32 * 256 * 4096.5
    assert f.per_token(**args, seq_len=8192) == 2_145_030_144
    ops, nbytes = f.ssd_work(fwd_calls=6, bwd_calls=4,
                             tokens_per_step=16384, **args)
    t, n, p, h, g = 128, 128, 64, 64, 8
    assert ops == 2 * 128 * (
        6 * (g * t * t * n + h * (t * t * p + 2 * t * n * p))
        + 4 * (3 * g * t * t * n + h * (2 * t * t * p + 4 * t * n * p)))
    states = 128 * h * n * p * 4
    fwd = 16384 * (4096 + 2048) * 2 + 2 * 16384 * 64 * 4 + 16384 * 4096 * 2 \
        + states
    bwd = fwd + 16384 * 4096 * 2 + 2 * 16384 * 1024 * 4 + 2 * 16384 * 64 * 4
    assert nbytes == 6 * fwd + 4 * bwd
    # two products an expert layer by default, each three times a step
    rows = 16384 * 6 * 8 / 128
    assert rows == 6144
    ops, nbytes = f.gmm_work(tokens_per_step=16384, **args)
    assert ops == 3 * 8 * 2 * rows * 2688 * 1856
    assert nbytes == 3 * 8 * (rows * 1856 + rows * 2688 + 8 * 2688 * 1856) * 2
    assert f.gmm_work(tokens_per_step=16384, fwd_products=8, **args) \
        == (ops, nbytes)
    # the plan recomputes one expert layer: its two forward products run
    # twice, the backward's eight products a layer stay as they are
    again = f.gmm_work(tokens_per_step=16384, fwd_products=10, **args)
    assert again == (ops * 26 / 24, nbytes * 26 / 24)


def _record(device_ops, steps=6):
    return {"trace": {"steps": steps, "device_ops": device_ops},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "window": {"units_per_step_per_chip": 16384},
            "config": _config(), "cell": {"flops_args": {"seq_len": 8192}}}


def test_readers_on_made_up_records(monkeypatch):
    from benchmarks.metrics import program_spans

    read = {name: run.load_reader(name) for name in NEW_METRICS}
    ops = [["fusion bf16[2,8192,10304]", 0.5], ["hvd_ssd_scan", 0.12],
           ["ragged-dot-none", 0.09], ["ragged-dot-metadata", 0.003]]
    record = _record(ops)
    gauges = {"hvd.ssd.fwd_calls": 6, "hvd.ssd.chunk": 128,
              "hvd.ssd.groups": 8, "hvd.moe.expert_products": 10}
    monkeypatch.setattr(program_spans, "step_gauge", gauges.get)
    args = _config()["flops"]["args"]
    ops_, nbytes = flops_hybrid_moe.ssd_work(
        fwd_calls=6, bwd_calls=4, tokens_per_step=16384, **args)
    least = max(ops_ / 197e12, nbytes / 819e9)
    assert read["ssd_grouped_roofline_pct.tok"](record) == pytest.approx(
        100 * least / 20e-3)
    ops_, nbytes = flops_hybrid_moe.gmm_work(fwd_products=10,
                                             tokens_per_step=16384, **args)
    least = max(ops_ / 197e12, nbytes / 819e9)
    assert read["moe_ungated_gmm_roofline_pct.tok"](record) \
        == pytest.approx(100 * least / 15.5e-3)
    # the groups and the chunk come from the program: a program with one
    # group reads another share than one with eight
    monkeypatch.setattr(program_spans, "step_gauge",
                        dict(gauges, **{"hvd.ssd.groups": 1}).get)
    assert read["ssd_grouped_roofline_pct.tok"](record) \
        < 100 * least / 20e-3 * 10
    # a family outside the ten largest, no trace, no gauge (the parent's
    # program): nothing to read, and no error
    for name, family in (("ssd_grouped_roofline_pct.tok", "hvd_ssd_scan"),
                         ("moe_ungated_gmm_roofline_pct.tok",
                          "ragged-dot-none")):
        missing = _record([op for op in ops if op[0] != family])
        assert read[name](missing) is None
        assert read[name](dict(record, trace=None)) is None
    monkeypatch.setattr(program_spans, "step_gauge",
                        dict(gauges, **{"hvd.ssd.groups": None}).get)
    assert read["ssd_grouped_roofline_pct.tok"](record) is None
    monkeypatch.setattr(program_spans, "step_gauge", lambda name: None)
    for name in NEW_METRICS:
        assert read[name](record) is None


# ------------------------------------------------------ model, reference


@pytest.fixture(scope="module", autouse=True)
def _token_loop_rolled():
    """The reference's token loop one token an iteration: the same sums in
    the same order, and on the CPU it compiles in about two thirds of the
    time that the chip's unrolled loop takes."""
    unroll, nemotron_h.UNROLL = nemotron_h.UNROLL, 1
    yield
    nemotron_h.UNROLL = unroll


@pytest.fixture(scope="module")
def program(hvd):
    """The toy configuration's lane as ``run.py`` builds it, float32,
    dense attention, one sequence a chip."""
    config = _toy_config()
    config["bench_args"] += ["--fp32", "--attention", "dense"]
    return run.Program(config, dict(TOY_CELL, chips=hvd.size()))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_three_adam_steps_match_the_reference(program, seed):
    """Loss of each step, every leaf's first gradient and every leaf's
    change over three Adam steps (the selection bias moved between them),
    through the lane's own call."""
    assert isinstance(program.lane.model, decoder.SparseDecoderLM)
    state, batch = program.start(seed)
    state, prog = program.first_steps(state, batch, seed)
    ref = program.reference(seed, jax.devices()[0])
    for name, (gap, where) in compare.gaps(prog, ref).items():
        assert gap < 2e-5, (name, gap, where)
    assert sorted(prog["grad_norms"]) == sorted(ref["grad_norms"])
    params = state["params"]
    assert set(params) == {"embed", "final_norm", "lm_head"} | {
        f"DecoderBlock_{i}" for i in range(3)}
    assert set(params["DecoderBlock_0"]) == {"norm", "mamba"}
    assert set(params["DecoderBlock_1"]["attn"]) == {"q", "k", "v", "out"}
    assert set(params["DecoderBlock_2"]) == {"norm", "moe"}
    bias = state["buffers"]["DecoderBlock_2"]["moe"]["selection_bias"]
    # three steps of the balancing rule, each at most 2 x 0.001 centred
    assert 0 < float(jnp.abs(bias).max()) <= 0.006 + 1e-6


def _mixer(**fields):
    mixer = decoder.Mamba2Mixer(heads=8, head_dim=16, state=16, chunk=16,
                                groups=4, dtype=jnp.float32, **fields)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 48, 24))
    params = mixer.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2), a.shape),
        params)
    # slow decays, so that the state carried from chunk to chunk counts
    params = dict(params, A_log=jnp.zeros(8), dt_bias=jnp.full((8,), -3.0))
    return mixer, x, params


def test_the_grouped_mixer_is_the_references_forward_and_gradients():
    mixer, x, params = _mixer()
    einsum = common.make_einsum("float32")
    w = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 24))

    def theirs(x, params, **fault):
        return nemotron_h._mamba(x, params, hyper=dict(HYPER, **fault),
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
    # what the reference's two scan faults turn is in the mixer
    for fault in ({"grouped_bc": False}, {"grouped_norm": False}):
        bad = theirs(x, params, **fault)
        assert float(jnp.abs(bad - sound).max()) > 1e-3, fault


def _expert_params(held, key=7):
    k = jax.random.split(jax.random.PRNGKey(key), 5)
    return {"router": 0.5 * jax.random.normal(k[0], (64, 16)),
            "experts_up": 0.125 * jax.random.normal(k[1], (held, 64, 32)),
            "experts_down": 0.18 * jax.random.normal(k[2], (held, 32, 64)),
            "shared": {"up": {"kernel": 0.125 * jax.random.normal(
                k[3], (64, 64))},
                "down": {"kernel": 0.15 * jax.random.normal(k[4], (64, 64))}}}


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the four shares of 4 experts
    give (``moe.routed_experts`` with each share's ``first``, two grouped
    products an expert), plus the shared expert once (the program's
    ``SquaredReluMLP``), equal what the uncut reference gives for the whole
    layer of 16 experts; and ``SparseExperts`` holding all 16 is that
    too."""
    whole = _expert_params(16)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 64))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    einsum = common.make_einsum("float32")
    want, counts = nemotron_h._experts(x, whole, bias, hyper=HYPER,
                                       einsum=einsum)
    flat = x.reshape(-1, 64)
    parts = []
    for first in range(0, 16, 4):
        y, seen = moe.routed_experts(
            flat, whole["router"],
            {"up": whole["experts_up"][first:first + 4],
             "down": whole["experts_down"][first:first + 4]},
            bias, first=first, top_k=3, route_scale=2.5)
        np.testing.assert_array_equal(seen, counts)
        parts.append(y)
    shared = decoder.SquaredReluMLP(64, jnp.float32).apply(
        {"params": whole["shared"]}, x)
    got = sum(parts).reshape(x.shape) + shared
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    layer = decoder.SparseExperts(
        experts=16, experts_held=16, first_expert=0, top_k=3, width=32,
        route_scale=2.5, shared=2, gated=False, dtype=jnp.float32)
    variables = {"params": whole,
                 "buffers": {"selection_bias": bias,
                             "expert_counts": jnp.zeros(16)}}
    np.testing.assert_allclose(layer.apply(variables, x), want, rtol=2e-5,
                               atol=2e-5)
    # each share alone is not the layer: the absent experts' part is missing
    assert float(jnp.abs(parts[0].reshape(x.shape) + shared - want).max()) \
        > 1e-3
    # what the reference's two expert faults turn is in the layer
    for fault in ({"squared_relu": False}, {"shared_gated": True}):
        bad, _ = nemotron_h._experts(x, whole, bias,
                                     hyper=dict(HYPER, **fault),
                                     einsum=einsum)
        assert float(jnp.abs(bad - want).max()) > 1e-3, fault


@pytest.mark.parametrize("gated, recomputed, products", [
    (False, False, 2), (False, True, 4), (True, False, 3), (True, True, 6)])
def test_a_recomputed_expert_layer_counts_its_products_twice(
        gated, recomputed, products):
    """``hvd.moe.expert_products`` counts the forward grouped products a
    step issues: the backward pass runs a recomputed layer's again."""
    from horovod_tpu.utils import timeline

    params = _expert_params(4)
    experts = {"up": params["experts_up"], "down": params["experts_down"]}
    if gated:
        experts["gate"] = params["experts_up"]
    x = jax.random.normal(jax.random.PRNGKey(8), (48, 64))
    moe.routed_experts(x, params["router"], experts, top_k=3,
                       name="layer", recomputed=recomputed)
    assert timeline.snapshot()["gauges"]["hvd.moe.expert_products"][""] \
        == products


def test_the_other_configurations_blocks_take_no_new_field():
    """A pattern-less model builds ``DecoderBlock`` as before; a gated
    expert layer keeps its three stacks and its gated shared expert, a Mamba
    block its one group."""
    import bench

    config = run.load_json(REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")
    args = bench.build_parser().parse_args(config["bench_args"])
    model = models.build("moe_lm", vocab_size=args.vocab,
                         **bench.lm_model_args(args, "dense"))
    assert model.pattern == "" and model.ssm_groups == 1
    assert isinstance(model.block(0), decoder.DecoderBlock)
    assert "groups" not in model.block(0).attn
    layer = decoder.SparseExperts(experts=8, experts_held=4, first_expert=0,
                                  top_k=2, width=32, dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 64))))["params"]
    assert set(shapes) == {"router", "experts_gate", "experts_up",
                           "experts_down", "shared"}
    assert set(shapes["shared"]) == {"gate", "up", "down"}


def test_kept_bytes_of_the_single_branch_layers():
    """The closed sum each single-branch layer keeps when not recomputed,
    at the full widths over 16,384 tokens, bfloat16: the stream and its norm
    (2 x 2,688) beside, for a Mamba layer, the in-projection's output
    (10,304), the conv's twice (6,144) and y twice (4,096), Delta twice in
    float32 and the chunk states (64 x 128 x 64 float32 a chunk of 128:
    16,384 bytes a token); for the attention layer q and the output (4,096
    each), k and v (256 each) and the log-sum-exp (32 heads x 128 float32);
    for an expert layer the router's scores (3 x 128 float32), a token's
    choices (4 x 6 float32) and the shared expert's up-projection (3,712;
    its square is made again where the down-projection reads it)."""
    model = _full_model()
    kinds = {model.block(i).kind: model.block(i) for i in range(9)}
    stream = 2 * 2688 * 2
    assert kinds["M"].kept_bytes(16384, 2688) == 16384 * (
        stream + (4096 + 6144 + 64 + 6144 + 4096) * 2 + 2 * 4 * 64 + 16384)
    assert kinds["*"].kept_bytes(16384, 2688) == 16384 * (
        stream + (2 * 4096 + 2 * 256) * 2 + 32 * 128 * 4)
    assert kinds["E"].kept_bytes(16384, 2688) == 16384 * (
        stream + 3 * 4 * 128 + 4 * 4 * 6 + 3712 * 2)


# ------------------------------------------------------------- rehearsal


def _toy_tree(root):
    """A copy of ``benchmarks/`` plus the configuration at a toy size, its
    cell and the manifest's new entries retargeted to it: new files only."""
    config = _toy_config()
    cell = run.load_json(REPO, "benchmarks", "workloads", CELL_NAME + ".json")
    cell.update(config="toy_nemotron", traffic="toy_1", trace_steps=4,
                bench_args=TOY_CELL["bench_args"], flops_args={"seq_len": 48},
                limits={"loss1_gap": 0.03, "loss2_gap": 0.03,
                        "loss3_gap": 0.03, "grad_median_gap": 0.03,
                        "delta_median_gap": 0.03})
    toy_cell.add_toy_cell(root, "toy_nemotron", config, cell, NEW_METRICS)
    return config, cell


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_at_a_toy_size(tmp_path, trace):
    root = str(tmp_path)
    _toy_tree(root)
    result, err = toy_cell.drive_toy_cell(root, "toy_nemotron_1chip",
                                          trace=trace, seed=2 ** 31 + 17)
    assert result["correct"], err[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    if trace:
        # program counters read on the CPU too; a device trace does not.
        # 2 sequences of 48 tokens on the one device, 3 chunks of 16, 8
        # heads, a state of 16 x 16 float32; the Mamba block recomputed
        # (the CPU reports no memory limit), so it runs its forward twice
        assert result["metrics"]["ssd_state_mib_per_step.tok"]["value"] \
            == 2 * (2 * 3 * 8 * 16 * 16 * 4) / 2 ** 20
        assert result["metrics"]["recomputed_applications_per_step.tok"][
            "value"] == 3
        assert not {"ssd_ms_per_step.tok", "step_mfu_pct.tok",
                    *NEW_METRICS} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"tok_per_s_per_chip", "setup_s"}


@pytest.fixture(scope="module")
def sound(program):
    """The sound reference's readings of the planted faults' seed, once."""
    return {5: program.reference(5, jax.devices()[0])}


@pytest.mark.parametrize("name, setting, planted", [
    ("every_head_group_0", "grouped_bc=false", ("grouped_bc", False)),
    ("norm_over_all_channels", "grouped_norm=false", ("grouped_norm", False)),
    ("relu_not_squared", "squared_relu=false", ("squared_relu", False)),
    ("shared_expert_gated", "shared_gated=true", ("shared_gated", True))])
def test_each_planted_fault_reads_false(program, sound, name, setting,
                                        planted):
    """``benchmarks/plant.py`` on the toy lane: the reference with one of
    the architecture's four faults in its ``hyper`` put in the program's
    place reads ``correct`` false under limits that the float32 program
    passes ten times over."""
    plants = plant.parse_plants([f"{name}:{setting}"])
    assert plants == {name: planted}
    program.cell = dict(program.cell, limits={
        "loss1_gap": 2e-4, "loss3_gap": 2e-4, "grad_gap": 2e-4,
        "delta_gap": 2e-3})
    line, = plant.planted(program, jax.devices()[0], [5], plants, sound)
    assert line["kind"] == "fault_" + name and line["correct"] is False
    assert {"loss1_gap", "grad_gap"} & set(line["over"]), line
    hyper = program.config["reference"]["hyper"]
    assert hyper["grouped_bc"] and hyper["grouped_norm"] \
        and hyper["squared_relu"] and not hyper["shared_gated"]
