"""The training lane the benchmark drives (``bench.build_parser`` /
``bench.build_lane``) and the language-model step it runs
(``models.make_lm_train_step``): every cell's arguments parse, what was
taken off the parser stays off, each family's lane builds and steps at toy
size, the LM step is the loss it says it is, and the verifier's LM program
is that step."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import models
from horovod_tpu.utils import timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]

#: Off the parser since PR 30: each selected a duplicate path or the
#: protocol of the benchmark before the ledger, and no cell passed one.
REMOVED = (
    ["--flash-attention"], ["--flash-bwd", "pallas"], ["--flash-full-grid"],
    ["--steps-per-dispatch", "2"], ["--overlap", "on"],
    ["--hierarchical", "on"], ["--compression", "bf16"], ["--zero"],
    ["--scan-layers"], ["--bf16-momentum"], ["--mesh", "dp=8"],
    ["--snapshot-every", "100"], ["--d-model", "1024"],
    ["--num-warmup-batches", "2"], ["--num-batches-per-iter", "2"],
    ["--num-iters", "2"], ["--compile-only"], ["--probe-only"])

TOY = {
    "resnet50": ["--model", "resnet50", "--image-size", "32",
                 "--batch-size", "2"],
    "transformer_lm": ["--model", "transformer_lm", "--lm-layers", "2",
                       "--lm-dim", "64", "--lm-heads", "4", "--vocab", "128",
                       "--batch-size", "2", "--seq-len", "32", "--remat"],
    "moe_lm": ["--model", "moe_lm", "--lm-layers", "2", "--lm-dim", "64",
               "--lm-heads", "4", "--lm-kv-heads", "2", "--lm-head-dim", "16",
               "--lm-window", "16", "--lm-layer-types", "sliding,full",
               "--lm-ffn", "96", "--lm-dense-layers", "1", "--moe-experts",
               "8", "--moe-experts-held", "4", "--moe-top-k", "2",
               "--moe-width", "32", "--vocab", "128", "--batch-size", "2",
               "--seq-len", "32", "--remat"],
    # latent attention layers, two norms a block, no multiplier on the
    # embedding: the lane's third kind of decoder layer
    "moe_lm/latent": ["--model", "moe_lm", "--lm-layers", "2", "--lm-dim",
                      "64", "--lm-heads", "4", "--lm-head-dim", "16",
                      "--lm-rope-dim", "8", "--lm-value-dim", "16",
                      "--lm-latent-dim", "32", "--lm-layer-types",
                      "latent,latent", "--no-lm-output-norms",
                      "--no-lm-embed-scale", "--lm-ffn", "96",
                      "--lm-dense-layers", "1", "--moe-experts", "8",
                      "--moe-experts-held", "4", "--moe-top-k", "2",
                      "--moe-width", "32", "--moe-shared", "2", "--vocab",
                      "128", "--batch-size", "2", "--seq-len", "32",
                      "--remat"],
    "looped_lm": ["--model", "looped_lm", "--lm-layers", "2", "--lm-loops",
                  "3", "--lm-dim", "64", "--lm-heads", "4", "--lm-kv-heads",
                  "2", "--lm-head-dim", "16", "--lm-ffn", "96",
                  "--lm-rope-base", "1000000.0", "--vocab", "128",
                  "--batch-size", "2", "--seq-len", "32", "--remat"],
}


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench

    return bench


def _cell_args(name):
    from benchmarks import run

    _, cell, config = run.load_cell(name)
    return list(config["bench_args"]) + list(cell["bench_args"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_arguments_parse(bench, cell):
    """A flag a cell passes cannot leave the parser unnoticed on the CPU."""
    argv = _cell_args(cell)
    args = bench.build_parser().parse_args(argv)
    assert args.model in ("resnet50",) + bench.LM_MODELS
    for flag, value in zip(argv, argv[1:]):
        if flag.startswith("--") and not value.startswith("--"):
            assert str(getattr(args, flag[2:].replace("-", "_"))) == value


def test_removed_arguments_are_usage_errors(bench, capsys):
    parser = bench.build_parser()
    for argv in REMOVED:
        with pytest.raises(SystemExit) as stop:
            parser.parse_args(argv)
        assert stop.value.code == 2, argv
        assert "unrecognized arguments" in capsys.readouterr().err, argv
    # less --help; --lm-pattern, --ssm-groups and --moe-act since
    # Nemotron-3-Nano's cell
    assert len(parser._actions) - 1 == 50


def _spans(name=None):
    return [s for s in timeline.snapshot()["spans"]
            if name is None or s["name"] == name]


@pytest.mark.parametrize("family", sorted(TOY))
def test_lane_builds_and_steps(hvd, bench, family):
    timeline.reset()
    args = bench.build_parser().parse_args(TOY[family])
    lane = bench.build_lane(args, lambda *a, **k: None)
    build, = _spans("hvd.lane.build")
    assert build["args"]["model"] == family.split("/")[0]
    for child in ("hvd.lane.model_init", "hvd.lane.train_state",
                  "hvd.lane.place"):
        found = _spans(child)
        assert found and all(s["parent"] == build["id"] for s in found)
    assert not _spans("hvd.lane.audit")
    per_chip = 2 if family == "resnet50" else 2 * 32
    assert lane.units_per_step == per_chip
    assert lane.stamp == ({} if family == "resnet50"
                          else {"attention": "dense"})
    first = jax.tree_util.tree_leaves(lane.state["params"])[0]
    state, losses = lane.state, []
    for _ in range(2):
        state, out = lane.run_step(state, lane.batch)
        losses.append(float(out["loss"] if isinstance(out, dict) else out))
    assert first.is_deleted()                       # the state was donated
    assert int(state["step"]) == 2
    assert np.isfinite(losses).all() and losses[1] < losses[0], losses


@pytest.mark.parametrize("argv, heads", [
    (["--model", "transformer_lm", "--lm-dim", "1024", "--lm-heads", "16",
      "--seq-len", "1024"], (16, 16, 64)),
    (["--model", "moe_lm", "--lm-dim", "2048", "--lm-heads", "32",
      "--lm-kv-heads", "4", "--lm-head-dim", "128", "--seq-len", "4096"],
     (32, 4, 128)),
    (["--model", "looped_lm", "--lm-dim", "2048", "--lm-heads", "16",
      "--lm-kv-heads", "16", "--lm-head-dim", "128", "--seq-len", "4096"],
     (16, 16, 128)),
    (["--model", "looped_lm", "--lm-dim", "64", "--lm-heads", "4",
      "--seq-len", "2048"], (4, 4, 16)),
    # latent layers: every head its own key, keys wider than values
    (["--model", "moe_lm", "--lm-dim", "2048", "--lm-heads", "16",
      "--lm-head-dim", "128", "--lm-rope-dim", "64", "--lm-value-dim", "128",
      "--lm-layer-types", "latent", "--lm-layers", "1", "--seq-len", "8192"],
     (16, 16, (192, 128)))])
def test_the_attention_policy_is_asked_with_each_familys_heads(
        bench, monkeypatch, argv, heads):
    """``resolve_attention`` hands ``attention_plan`` KV heads and the size of
    a head for every family that has them, and the defaults elsewhere."""
    from horovod_tpu.ops import attention

    asked = []

    def plan(seq_q, seq_k, heads, kv_heads, head_dim, **kw):
        asked.append((seq_q, seq_k, heads, kv_heads, head_dim))
        return attention.AttentionPlan("dense", None, None, "pallas")

    monkeypatch.setattr(attention, "attention_plan", plan)
    args = bench.build_parser().parse_args(argv)
    assert bench.resolve_attention(args) == "dense"
    assert asked == [(args.seq_len, args.seq_len) + heads]


def _lm(fused_ce):
    model = models.TransformerLM(vocab_size=97, num_layers=2, num_heads=2,
                                 embed_dim=32, max_len=16, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 97)
    # an "optimizer" that keeps the gradient it was given as its state
    keep = optax.GradientTransformation(
        lambda params: jax.tree_util.tree_map(jnp.zeros_like, params),
        lambda grads, state, params=None: (
            jax.tree_util.tree_map(jnp.zeros_like, grads), grads))
    state, optimizer = models.create_train_state(
        jax.random.PRNGKey(0), model, keep, tokens[:1], distributed=False)
    step = models.make_lm_train_step(model, optimizer, fused_ce=fused_ce)
    return model, tokens, state, step


@pytest.mark.parametrize("fused_ce", [False, True],
                         ids=["unfused", "fused_ce"])
def test_lm_step_equals_the_plain_loss(fused_ce):
    """The mean next-token negative log-likelihood over the shard, in
    float32, and its gradient: the formula, written out."""
    model, tokens, state, step = _lm(fused_ce)

    def plain(params):
        logits = model.apply({"params": params}, tokens, train=False)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
        return -picked.mean()

    want, want_grads = jax.value_and_grad(plain)(state["params"])
    params = state["params"]
    new, loss = jax.jit(step)(state, {"tokens": tokens})
    assert loss.shape == () and loss.dtype == jnp.float32
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert abs(float(want) - np.log(97)) < 0.5      # near uniform at init
    for got, ref in zip(jax.tree_util.tree_leaves(new["opt_state"]),
                        jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-7)
    assert int(new["step"]) == 1
    for a, b in zip(jax.tree_util.tree_leaves(new["params"]),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)         # the updates were zeros


def test_lm_step_carries_buffers():
    """A sparse layer's state goes in with the forward pass and comes out
    with the step's counts and the bias the balancing rule made of them."""
    model = models.build(
        "moe_lm", vocab_size=64, embed_dim=32, layer_types=("full_attention",
                                                            "full_attention"),
        heads=2, kv_heads=1, head_dim=16, window=8, dense_layers=1,
        dense_width=48, experts=8, experts_held=8, top_k=2, expert_width=16,
        attention="dense", dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    state, optimizer = models.create_train_state(
        jax.random.PRNGKey(0), model, optax.adam(1e-3), tokens[:1],
        distributed=False)
    (layer, before), = state["buffers"].items()     # one expert layer
    assert not np.asarray(before["moe"]["selection_bias"]).any()
    step = jax.jit(models.make_lm_train_step(model, optimizer,
                                             bias_coeff=0.01))
    new, loss = step(state, {"tokens": tokens})
    assert np.isfinite(float(loss))
    after = new["buffers"][layer]["moe"]
    counts = np.asarray(after["expert_counts"])
    assert counts.sum() == tokens.size * 2          # top 2, nothing dropped
    bias = np.asarray(after["selection_bias"])
    want = 0.01 * np.sign(counts.mean() - counts)
    np.testing.assert_allclose(bias, want - want.mean(), atol=1e-7)
    assert bias.any() and abs(bias.sum()) < 1e-6
    # a second step reads the moved bias and moves it again
    again, _ = step(new, {"tokens": tokens})
    assert np.abs(np.asarray(
        again["buffers"][layer]["moe"]["selection_bias"]) - bias).max() > 0


@pytest.mark.parametrize("fused_ce", [False, True],
                         ids=["unfused", "fused_ce"])
def test_the_verifier_checks_the_step_the_lane_runs(hvd, bench, fused_ce):
    """``gate.transformer_lm`` / ``_fused_ce`` and the lane at the
    registry's shapes: the same collectives by kind, count and bytes."""
    from tools.hvdverify import abstractify, audit_collectives
    from tools.hvdverify.registry import programs

    name = "gate.transformer_lm_fused_ce" if fused_ce else "gate.transformer_lm"
    program, = programs(names=[name])
    fn, abstract = program.build()
    registry = audit_collectives(fn, *abstract)
    args = bench.build_parser().parse_args(
        ["--model", "transformer_lm", "--lm-layers", "4", "--lm-dim", "256",
         "--lm-heads", "4", "--vocab", "1024", "--seq-len", "256",
         "--batch-size", "1", "--attention", "dense"]
        + (["--fused-ce"] if fused_ce else []))
    lane = bench.build_lane(args, lambda *a, **k: None)
    assert jax.tree_util.tree_map(
        lambda x: (x.shape, x.dtype), abstractify((lane.state, lane.batch))
    ) == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), abstract)
    ran = audit_collectives(lambda s, b: lane.run_step(s, b),
                            abstractify(lane.state), abstractify(lane.batch))
    assert ran["count"] > 0
    for field in ("count", "bytes", "by_kind"):
        assert ran[field] == registry[field], field
