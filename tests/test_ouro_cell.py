"""The looped LM (``bench.py --model looped_lm``: models/decoder.py
``LoopedDecoderLM``, ``exit_loss``) and the cell ``ouro_seq4096_1chip`` as the
benchmark finds it: the lane's model against the plain reference
``benchmarks/reference/ouro.py`` on seeded weights, what the loop means (one
loop is the unlooped stack under the plain loss; a shared leaf's gradient is
the sum over its uses; the exit distribution sums to 1), the manifest and the
configuration's file against the catalog's numbers, ``flops_loop.py`` against
hand-worked figures, the readers on made-up records, and the rehearsal: the
configuration at a toy size run end to end on the CPU through
``benchmarks/run.py``.

Tolerances: the float32 program against the float32 reference reads some
1e-6 on every gap (the same function in the same precision; the program takes
the loss in chunks of rows, the reference a row of the batch at a time; both
take the exit distribution in logarithms): held to 2e-5.
"""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

import toy_cell  # noqa: E402
from benchmarks import check_manifest, compare, flops_loop, flops_moe, run  # noqa: E402
from horovod_tpu import models  # noqa: E402
from horovod_tpu.models import decoder  # noqa: E402
from horovod_tpu.utils import timeline  # noqa: E402

CELL = "ouro_seq4096_1chip"
NEW_METRICS = ["loop_applications_per_step.tok", "exit_live_logits_mib.tok"]
RECOMPUTED = "recomputed_applications_per_step.tok"     # PR 33, Trinity's too
# Ouro-2.6B's published config.json, as the model-configs catalog holds it:
# every number has to stand in the file unchanged (the one cut, the depth
# held here, has a key of its own)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "total_ut_steps": 4, "early_exit_threshold": 1, "vocab_size": 49152}

HYPER = {"heads": 4, "kv_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
         "rope_theta": 1e6, "total_ut_steps": 3, "beta": 0.1,
         "optimizer": {"name": "adam", "lr": 0.0001, "b1": 0.9, "b2": 0.999,
                       "eps": 1e-08}}
BENCH_ARGS = [
    "--model", "looped_lm", "--lm-layers", "2", "--lm-loops", "3",
    "--lm-dim", "64", "--lm-heads", "4", "--lm-kv-heads", "2",
    "--lm-head-dim", "16", "--lm-ffn", "96", "--lm-rope-base", "1000000.0",
    "--lm-exit-beta", "0.1", "--vocab", "128"]
TOY_CONFIG = {
    "bench_args": BENCH_ARGS, "kernel_gain": 1.0,
    "int_ranges": {"tokens": 128},
    "first_moment": {"field": "mu", "scale": 10.0},
    "reference": {"file": "reference/ouro.py", "hyper": HYPER}}
TOY_CELL = {"name": "toy", "chips": 1, "compare_steps": 3,
            "bench_args": ["--batch-size", "2", "--seq-len", "32", "--remat"],
            "reference_rows_per_block": 1}


def _config():
    return run.load_json(REPO, "benchmarks", "configs", "ouro-2.6b.json")


# ------------------------------------------------- the model and its loss

@pytest.fixture(scope="module")
def programs(hvd):
    """The lane ``bench.build_lane`` makes of the arguments, float32, once
    with dense and once with flash attention, as ``run.py`` drives it: data
    parallel over the test mesh's chips (2 sequences each)."""
    made = {}

    def get(attention):
        if attention not in made:
            config = dict(TOY_CONFIG, bench_args=BENCH_ARGS + [
                "--fp32", "--attention", attention])
            made[attention] = run.Program(
                config, dict(TOY_CELL, chips=hvd.size()))
        return made[attention]

    return get


@pytest.mark.parametrize("attention, seed", [
    ("dense", 3), ("dense", 2 ** 31 + 5), ("flash", 3)])
def test_three_adam_steps_match_the_reference(programs, attention, seed):
    """Loss of each step, every leaf's first gradient (each the sum over the
    leaf's three uses) and every leaf's change over three Adam steps."""
    program = programs(attention)
    state, batch = program.start(seed)
    state, prog = program.first_steps(state, batch, seed)
    ref = program.reference(seed, jax.devices()[0])
    for name, (gap, where) in compare.gaps(prog, ref).items():
        assert gap < 2e-5, (name, gap, where)
    assert sorted(prog["grad_norms"]) == sorted(ref["grad_norms"])
    # every weight once: 2 blocks, embedding, final norm, gate, head
    assert len(prog["grad_norms"]) == 2 * 11 + 5


def test_the_step_program_carries_the_loops_gauges(programs):
    """``hvd.loop.applications`` and ``hvd.exit.live_logits_bytes`` of the
    step handle's program; block recomputation traces an application more
    than once and must not count it twice, nor may the trace of ``init``
    or of an earlier program be counted in."""
    program = programs("dense")
    state, batch = program.start(11)
    program.first_steps(state, batch, 11)
    snap = timeline.snapshot()
    step = [s["args"]["program"] for s in snap["spans"]
            if s["name"] == "hvd.spmd.dispatch"
            and s["args"]["handle"] == "step_fn"][-1]
    want = {"hvd.loop.applications": 6,
            # 3 exits x 2 rows x 31 positions are under one chunk of 512
            "hvd.exit.live_logits_bytes": 4 * 3 * 2 * 31 * 128,
            "hvd.attn.dense_calls": 6, "hvd.attn.kv_heads": 2}
    assert {name: snap["gauges"].get(name, {}).get(step)
            for name in want} == want


def test_planted_faults_of_the_loop_are_caught(programs):
    """``benchmarks/plant.py`` on the toy lane: the reference with a loop
    step left out, or with the entropy term left out, put in the program's
    place reads ``correct`` false under limits that the float32 program
    passes a hundred times over; an unknown key is refused."""
    from benchmarks import plant

    program = programs("dense")
    plants = plant.parse_plants(["loop_left_out:total_ut_steps=2",
                                 "no_entropy:beta=0"])
    assert plants == {"loop_left_out": ("total_ut_steps", 2),
                      "no_entropy": ("beta", 0)}
    program.cell = dict(program.cell, limits={
        "loss1_gap": 2e-3, "grad_median_gap": 2e-3, "delta_median_gap": 2e-3})
    try:
        lines = list(plant.planted(program, jax.devices()[0], [7], plants))
        with pytest.raises(SystemExit, match="no 'loops'"):
            list(plant.planted(program, jax.devices()[0], [7],
                               {"x": ("loops", 1)}))
    finally:
        program.cell.pop("limits")
    assert [line["kind"] for line in lines] == ["fault_loop_left_out",
                                                "fault_no_entropy"]
    for line in lines:
        assert line["correct"] is False and "loss1_gap" in line["over"]
    assert program.config["reference"]["hyper"]["total_ut_steps"] == 3


def _small(loops, **more):
    kw = dict(vocab_size=97, embed_dim=32, num_layers=2, loops=loops,
              heads=4, kv_heads=2, head_dim=8, ffn_width=48,
              dtype=jnp.float32, attention="dense")
    model = models.build("looped_lm", **dict(kw, **more))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 97)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    # the draws' spread on every leaf, so that no gate or norm is at its start
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(jax.random.PRNGKey(i), x.shape)
        for i, x in enumerate(leaves)])
    return model, params, tokens


def _step_loss_and_grads(model, params, tokens):
    """What ``make_lm_train_step`` hands its optimizer, and its loss."""
    keep = optax.GradientTransformation(
        lambda p: jax.tree_util.tree_map(jnp.zeros_like, p),
        lambda grads, state, params=None: (
            jax.tree_util.tree_map(jnp.zeros_like, grads), grads))
    state = models.TrainState(params=params, batch_stats={},
                              opt_state=keep.init(params),
                              step=jnp.zeros((), jnp.int32))
    state, loss = jax.jit(models.make_lm_train_step(model, keep))(
        state, {"tokens": tokens})
    return float(loss), state["opt_state"]


def test_one_loop_is_the_unlooped_stack_under_the_plain_loss():
    """With one loop the only exit takes everything (its gate is not read):
    the loss is the mean next-token NLL of the stack's logits, and the
    model's own logits are those of the last exit."""
    model, params, tokens = _small(loops=1)
    logits = model.apply({"params": params}, tokens)
    logp = jax.nn.log_softmax(logits[:, :-1])
    plain = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
    loss, grads = _step_loss_and_grads(model, params, tokens)
    assert loss == pytest.approx(float(plain), rel=1e-6)
    assert not np.asarray(grads["exit_gate"]["kernel"]).any()
    exits, gates = model.apply({"params": params}, tokens,
                               return_hidden=True)
    assert exits.shape == (1, 3, 16, 32) and gates.shape == (1, 3, 16)
    np.testing.assert_allclose(
        exits[0] @ params["lm_head"]["kernel"], logits, atol=1e-5)


def test_a_shared_leafs_gradient_is_the_sum_over_its_uses():
    """Two loops over two blocks against the untied model: four blocks, two
    final norms and two gates with copies of the same values, the same
    loss. Each shared leaf's gradient is the sum of its copies'."""
    model, params, tokens = _small(loops=2)
    loss, tied = _step_loss_and_grads(model, params, tokens)

    def untied_loss(copies):
        h = params["embed"]["embedding"][tokens]
        exits, gates = [], []
        for t in range(2):
            for i in range(2):
                h = decoder.DecoderBlock(
                    dict(heads=4, kv_heads=2, head_dim=8, rope_base=1e6,
                         attention="dense"), 48, None, 1e-6, jnp.float32
                ).apply({"params": copies[t][f"DecoderBlock_{i}"]}, h)
            h = decoder.RMSNorm(1e-6).apply(
                {"params": copies[t]["final_norm"]}, h)
            exits.append(h)
            gates.append((h @ copies[t]["exit_gate"]["kernel"])[..., 0]
                         + copies[t]["exit_gate"]["bias"][0])
        return decoder.exit_loss(jnp.stack(exits), jnp.stack(gates),
                                 params["lm_head"]["kernel"], tokens, 0.1)

    shared = {k: v for k, v in params.items()
              if k not in ("embed", "lm_head")}
    value, (first, second) = jax.value_and_grad(untied_loss)(
        (shared, shared))
    assert loss == pytest.approx(float(value), rel=1e-6)
    summed = jax.tree_util.tree_map(jnp.add, first, second)
    for name, leaf in jax.tree_util.tree_leaves_with_path(summed):
        got = tied
        for key in name:
            got = got[key.key]
        np.testing.assert_allclose(got, leaf, rtol=1e-3, atol=1e-6,
                                   err_msg=str(name))
    # the second gate's own logit is never read: the last step takes the rest
    assert not np.asarray(second["exit_gate"]["kernel"]).any()
    assert np.asarray(first["exit_gate"]["kernel"]).any()


def test_exit_distribution_sums_to_one_and_the_last_takes_the_rest():
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (4, 5, 7))
    p = jnp.exp(decoder.exit_log_distribution(z))
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-4,
        atol=1e-7)
    # a saturated gate gives no log 0, and the last gate is not read
    hard = jnp.array([[40.0], [-40.0], [0.0], [123.0]])
    log_p = decoder.exit_log_distribution(hard)
    assert np.isfinite(np.asarray(log_p)).all()
    np.testing.assert_allclose(jnp.exp(log_p)[:, 0], [1, 0, 0, 0], atol=1e-6)


@pytest.mark.parametrize("bias", [0.0, 40.0, -120.0])
def test_a_saturated_gate_reads_a_finite_loss_on_both_sides(bias):
    """On the chip the gate saturates on some tokens by the third step (a
    logit over 17: ``1 - sigmoid`` rounds to 0 in float32). The reference
    written as products read ``0 * log 0`` there, a NaN loss on 3 seeds of
    31; in logarithms it reads what the lane reads, loss and gradients."""
    from benchmarks.reference import common, ouro

    model, params, tokens = _small(loops=3)
    params = dict(params, exit_gate=dict(
        params["exit_gate"], bias=params["exit_gate"]["bias"] + bias))
    loss, grads = _step_loss_and_grads(model, params, tokens)
    hyper = {"heads": 4, "kv_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-6,
             "rope_theta": 1e6, "total_ut_steps": 3, "beta": 0.1}

    def mean_loss(p):
        rows = ouro._loss_rows(p, tokens, hyper=hyper,
                               einsum=common.make_einsum("float32"))
        return rows.sum() / (tokens.shape[0] * (tokens.shape[1] - 1))

    ref_loss, ref_grads = jax.value_and_grad(mean_loss)(params)
    assert np.isfinite(float(ref_loss))
    assert float(ref_loss) == pytest.approx(loss, rel=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(ref_grads),
                    jax.tree_util.tree_leaves(grads)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def test_the_exit_loss_is_the_dense_composition():
    """Value and gradients (exit states, gate logits, head) of the chunked
    loss against full logits, probabilities as products and a plain
    entropy, with a chunk that does not divide the rows."""
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    exits = jax.random.normal(keys[0], (3, 2, 9, 16))
    gates = jax.random.normal(keys[1], (3, 2, 9))
    head = jax.random.normal(keys[2], (16, 53))
    tokens = jax.random.randint(keys[3], (2, 9), 0, 53)

    def dense(exits, gates, head):
        logp = jax.nn.log_softmax(exits[:, :, :-1] @ head)
        nll = -jnp.take_along_axis(
            logp, jnp.broadcast_to(tokens[None, :, 1:, None],
                                   logp.shape[:-1] + (1,)), -1)[..., 0]
        lam = jax.nn.sigmoid(gates[:, :, :-1])
        p = jnp.stack([lam[0], lam[1] * (1 - lam[0]),
                       (1 - lam[0]) * (1 - lam[1])])
        return jnp.mean(jnp.sum(p * nll + 0.3 * p * jnp.log(p), 0))

    want, want_grads = jax.value_and_grad(dense, argnums=(0, 1, 2))(
        exits, gates, head)
    got, got_grads = jax.value_and_grad(
        lambda e, g, h: decoder.exit_loss(e, g, h, tokens, 0.3, t_chunk=10),
        argnums=(0, 1, 2))(exits, gates, head)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_the_state_holds_each_weight_once_at_the_cells_sizes(monkeypatch):
    """612,438,017 parameters: 8 blocks, embedding, final norm, gate, head;
    shapes only."""
    monkeypatch.syspath_prepend(REPO)
    import bench

    config = _config()
    args = bench.build_parser().parse_args(config["bench_args"] + [
        "--seq-len", "4096"])
    model = models.build(
        args.model, vocab_size=args.vocab, dtype=jnp.bfloat16, remat=True,
        **bench.lm_model_args(args, "dense"))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))["params"]
    sizes = {k: sum(int(np.prod(x.shape))
                    for x in jax.tree_util.tree_leaves(v))
             for k, v in shapes.items()}
    said = config["deployment"]["parameters"]
    assert sizes["DecoderBlock_0"] == 51_380_224 + 8_192
    assert sum(sizes[f"DecoderBlock_{i}"] for i in range(8)) \
        == said["8_blocks"] == 411_107_328
    assert sizes["embed"] == said["embedding"] == 100_663_296
    assert sizes["lm_head"] == said["head"] == 100_663_296
    assert sizes["final_norm"] == 2_048 and sizes["exit_gate"] == 2_049
    assert sum(sizes.values()) == said["all"] == 612_438_017
    assert "q_norm" not in shapes["DecoderBlock_0"]["attn"]
    assert "gate" not in shapes["DecoderBlock_0"]["attn"]


def test_a_second_loss_for_the_looped_model_is_refused():
    model, _, _ = _small(loops=2)
    with pytest.raises(ValueError, match="chunked already"):
        models.make_lm_train_step(model, optax.adam(1e-4), fused_ce=True)


# ------------------------------------------------- the cell's files

def test_manifest_is_well_formed_and_names_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        text = f.read()
    manifest = json.loads(text)
    assert check_manifest.check(manifest, REPO, len(text.encode())) == []
    manifest, cell, config = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["bench_args"] == [
        "--batch-size", "2", "--seq-len", "4096", "--remat"]
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 1
    reported = {m["name"] for m in run.metrics_of(manifest, CELL,
                                                  "per_layer")}
    assert set(NEW_METRICS) | {RECOMPUTED} <= reported
    assert {"step_mfu_pct.tok", "device_step_ms.tok", "peak_hbm_gib.tok",
            "device_idle_pct.tok", "setup_lane_build_s"} <= reported
    # the flash kernels' readers see chip 0's ten largest families, and
    # hvd_flash_dq is this cell's eleventh: nothing to read, so not listed
    assert not {"collective_ms_per_step.tok", "moe_gmm_ms_per_step.tok",
                "moe_row_bound_ratio.tok", "flash_ms_per_step.tok",
                "flash_roofline_pct.tok"} & reported
    assert {m["name"] for m in run.metrics_of(manifest, CELL, "end_to_end")} \
        == {"tok_per_s_per_chip", "setup_s"}
    for name in reported:
        assert callable(run.load_reader(name))
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["layer"] == "looped_stack" and m["workloads"] == [CELL]
    # every compared step's loss is held, and the worst leaf both ways
    assert {"loss1_gap", "loss2_gap", "loss3_gap", "grad_gap",
            "delta_gap"} <= set(cell["limits"]) <= {
        "loss1_gap", "loss2_gap", "loss3_gap", "grad_gap", "grad_median_gap",
        "delta_gap", "delta_median_gap"}


def test_configuration_keeps_every_published_width():
    config = _config()
    assert config["reduced"] == ["num_layers"]
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["num_layers"] == 8
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["tie_word_embeddings"] is False
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    assert config["deployment"]["stages"] \
        * config["deployment"]["layers_a_stage"] == 48
    assert {"loss", "beta", "exit_gate"} <= set(config["assumed"])
    assert any("head" in d for d in config["departures"])
    # the lane's arguments, the reference's hyper and the operation count
    # say the same sizes
    args = dict(zip(config["bench_args"][::2], config["bench_args"][1::2]))
    hyper, flops = config["reference"]["hyper"], config["flops"]["args"]
    assert int(args["--lm-dim"]) == config["hidden_size"] == flops["d_model"]
    assert int(args["--lm-layers"]) == config["num_layers"]
    assert int(args["--lm-loops"]) == config["total_ut_steps"] \
        == hyper["total_ut_steps"] == flops["loops"]
    assert len(flops["layer_types"]) == 4 * 8
    assert int(args["--lm-heads"]) == hyper["heads"] == flops["heads"] == 16
    assert int(args["--lm-kv-heads"]) == hyper["kv_heads"] == 16
    assert int(args["--lm-head-dim"]) == hyper["head_dim"] == 128
    assert int(args["--lm-ffn"]) == config["intermediate_size"] \
        == flops["ffn_width"]
    assert float(args["--lm-rope-base"]) == hyper["rope_theta"] \
        == config["rope_theta"]
    assert float(args["--lm-exit-beta"]) == hyper["beta"] \
        == config["assumed"]["beta"]
    assert hyper["rms_norm_eps"] == config["rms_norm_eps"]
    assert int(args["--vocab"]) == config["vocab_size"] \
        == config["int_ranges"]["tokens"] == flops["vocab"]


def test_the_configurations_draws_reach_the_leaves_they_name():
    """``draws`` (the embedding at 1 an element, the norm after each branch
    at 0.1: PERF.md section 6, PR 32) name leaves of the model's tree by the
    end of their names, and leave every other leaf to the harness's rule."""
    from benchmarks import weights

    config = _config()
    assert set(config["draws"]) == {"embed/embedding", "norm_attn_out/scale",
                                    "norm_ffn_out/scale"}
    assert config["draws_why"]
    model, params, _ = _small(loops=2, vocab_size=512, embed_dim=128)
    drawn = weights.draw_params(weights.run_key(1572355686),
                                weights.shapes_of(params),
                                config["kernel_gain"], config["draws"])
    leaves = {weights.leaf_name(path): np.asarray(leaf) for path, leaf in
              jax.tree_util.tree_flatten_with_path(drawn)[0]}
    for end in config["draws"]:
        assert any(name.endswith(end) for name in leaves), end
    assert leaves["embed/embedding"].std() == pytest.approx(1.0, rel=0.02)
    for name, leaf in leaves.items():
        if name.endswith(("norm_attn_out/scale", "norm_ffn_out/scale")):
            assert leaf.mean() == pytest.approx(0.1, abs=0.005), name
            assert leaf.std() == pytest.approx(0.01, rel=0.3), name
        elif name.endswith("scale"):
            assert leaf.mean() == pytest.approx(1.0, abs=0.05), name


def test_operation_counts_are_the_hand_worked_ones():
    args = _config()["flops"]["args"]
    assert flops_loop.matmul_params_per_token(**args) \
        == 32 * 51_380_224 + 4 * 100_663_296 == 2_046_820_352
    assert flops_loop.attention_macs_per_token(**args, seq_len=4096) \
        == 32 * 8_390_656 == 268_500_992
    assert flops_loop.per_token(**args, seq_len=4096) == 13_891_928_064
    # the attention kernels' work over all 32 applications
    ops, nbytes = flops_moe.flash_work(tokens_per_step=8192, seq_len=4096,
                                       **args)
    assert ops == 7 * 2 * 128 * 16 * 8192 * 32 * 2048.5
    assert nbytes == 32 * 8192 * 128 * 2 * (6 * 16 + 6 * 16)


def _dispatched(program, **gauges):
    """A process whose step handle is ``program``, with these gauges."""
    timeline.reset()
    for call in range(3):
        with timeline.span("hvd.spmd.dispatch", handle="step_fn",
                           program=program, call=call):
            pass
    for name, value in gauges.items():
        timeline.gauge(name, value, key=program)


def test_readers_on_made_up_records():
    read = {name: run.load_reader(name) for name in NEW_METRICS}
    _dispatched("step_fn#0", **{"hvd.loop.applications": 32,
                                "hvd.exit.live_logits_bytes": 512 * 49152 * 4})
    assert read["loop_applications_per_step.tok"]({}) == 32
    assert read["exit_live_logits_mib.tok"]({}) == 96.0
    # of the applications, those the plan has the backward pass run again
    recomputed = run.load_reader(RECOMPUTED)
    assert recomputed({}) is None           # the parent sets no such gauge
    timeline.gauge("hvd.remat.recomputed", 26, key="step_fn#0")
    timeline.gauge("hvd.remat.recomputed", 5, key="step_fn#7")
    assert recomputed({}) == 26
    # a program that has no loop (the parent's, or another model's): nothing
    # to read, and no error
    _dispatched("step_fn#1", **{"hvd.attn.flash_calls": 24})
    for name in NEW_METRICS:
        assert read[name]({}) is None
    timeline.reset()
    for name in NEW_METRICS:
        assert read[name]({}) is None
    # the flash kernels' readers with this configuration's arguments
    record = {"trace": {"steps": 4, "device_ops": [
                  ["hvd_flash_fwd", 1.0], ["hvd_flash_dkv", 0.6],
                  ["hvd_flash_dq", 0.4]]},
              "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              "window": {"units_per_step_per_chip": 8192},
              "config": _config(), "cell": {"flops_args": {"seq_len": 4096}}}
    assert run.load_reader("flash_ms_per_step.tok")(record) \
        == pytest.approx(500.0)
    # 15.397 TFLOP over 197 TFLOP/s is 78.16 ms (12.9 GB would take 15.7)
    assert run.load_reader("flash_roofline_pct.tok")(record) \
        == pytest.approx(100 * 78.158 / 500.0, rel=1e-4)


# ------------------------------------------------------------- rehearsal

def _toy_tree(root):
    """A copy of ``benchmarks/`` plus the configuration at a toy size, its
    cell and the manifest's new entries retargeted to it: new files only."""
    config = copy.deepcopy(_config())
    config["bench_args"] = BENCH_ARGS
    config["int_ranges"] = {"tokens": 128}
    config["reference"]["hyper"] = HYPER
    config["flops"]["args"] = {
        "layer_types": ["full_attention"] * 6, "loops": 3, "d_model": 64,
        "heads": 4, "kv_heads": 2, "head_dim": 16, "window": None,
        "ffn_width": 96, "vocab": 128}
    cell = run.load_json(REPO, "benchmarks", "workloads", CELL + ".json")
    cell.update(config="toy_ouro", traffic="toy_1",
                bench_args=["--batch-size", "2", "--seq-len", "32",
                            "--remat"], flops_args={"seq_len": 32},
                limits={"loss1_gap": 0.03, "loss2_gap": 0.03,
                        "loss3_gap": 0.03, "grad_median_gap": 0.03,
                        "delta_median_gap": 0.03})
    toy_cell.add_toy_cell(root, "toy_ouro", config, cell,
                          NEW_METRICS + [RECOMPUTED])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_at_a_toy_size(tmp_path, trace):
    root = str(tmp_path)
    _toy_tree(root)
    result, err = toy_cell.drive_toy_cell(root, "toy_ouro_1chip",
                                          trace=trace, seed=2 ** 31 + 17)
    assert result["correct"], err[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["compared"]) >= {"loss1_gap", "grad_median_gap",
                                       "delta_median_gap",
                                       "compiles_in_window"}
    if trace:
        # a program counter reads on the CPU too; a device trace does not
        assert result["metrics"]["loop_applications_per_step.tok"]["value"] \
            == 6
        # the CPU reports no memory limit: every application is recomputed
        assert result["metrics"][RECOMPUTED]["value"] == 6
        # 3 exits x 2 rows x 31 positions of 128 float32 logits
        assert result["metrics"]["exit_live_logits_mib.tok"]["value"] \
            == 4 * 186 * 128 / 2 ** 20
        assert not {"flash_ms_per_step.tok", "flash_roofline_pct.tok",
                    "step_mfu_pct.tok", "moe_row_bound_ratio.tok"} \
            & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"tok_per_s_per_chip", "setup_s"}
