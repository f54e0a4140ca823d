"""Recomputation as a plan (``models/decoder.py``): ``recompute_plan`` is
arithmetic over bytes, so a table of cases; ``plan_recomputation`` at the two
cells' real shapes (shapes only) answers what PERF.md quotes; the two models
recompute exactly the applications their ``remat`` says, with the same
gradients whichever those are (the tolerance of ``tests/test_models.py``'s
remat test: a scheduling choice, not a numerical one), and say so in the
gauges ``hvd.remat.*``; ``bench.resolve_remat`` is the one place that fills
the field, and fills "every application" where the backend reports no
memory limit."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from horovod_tpu import models  # noqa: E402
from horovod_tpu.models import decoder  # noqa: E402
from horovod_tpu.models.decoder import RecomputePlan, recompute_plan  # noqa: E402
from horovod_tpu.utils import device, timeline  # noqa: E402

from test_lane import bench  # noqa: E402,F401 (fixture: bench.py on the CPU)

V5E = 16_909_336_064        # `bytes_limit` of a v5e: 15.75 GiB

# five applications: what each keeps, the gradients its backward pass leaves,
# a recomputed one's input, what is there throughout, the loss's working set
KEPT, GRADS, CARRIED, RESIDENT, HEAD = [9, 7, 7, 7, 7], [2] * 5, 1, 100, 20


def _plan(limit, margin=0, kept=KEPT, grads=GRADS, head=HEAD):
    return recompute_plan(kept, grads, CARRIED, RESIDENT, head, limit,
                          margin)


@pytest.mark.parametrize("limit, margin, want", [
    # no reported limit: every application, and nothing said of bytes
    (None, 0, RecomputePlan(5, 0, 0)),
    (0, 0, RecomputePlan(5, 0, 0)),
    # below what the step takes with everything recomputed (the loss, its
    # inputs and the last application's working set beside its gradients:
    # 100 + 5 + 20 = 125 at the loss, 100 + 5 + 7 + 2 = 114 in the backward
    # pass): every application, and no room
    (100, 0, RecomputePlan(5, 0, 0)),
    (124, 0, RecomputePlan(5, 0, 0)),
    # the last application costs its bytes at the loss and saves its input:
    # 100 + 4 + 20 + 7 = 131
    (125, 0, RecomputePlan(5, 0, 0)),
    (130, 0, RecomputePlan(5, 0, 5)),
    (131, 0, RecomputePlan(4, 7, 7)),
    (137, 0, RecomputePlan(3, 14, 14)),
    (143, 0, RecomputePlan(2, 21, 21)),
    (149, 0, RecomputePlan(1, 28, 28)),
    (156, 0, RecomputePlan(1, 28, 35)),
    # the first, the dearest: 100 + 20 + 37 = 157
    (157, 0, RecomputePlan(0, 37, 37)),
    (10 ** 6, 0, RecomputePlan(0, 37, 10 ** 6 - 157 + 37)),
    # the margin comes off the limit
    (157, 8, RecomputePlan(1, 28, 28)),
    (165, 8, RecomputePlan(0, 37, 37)),
    (131, 131, RecomputePlan(5, 0, 0)),
])
def test_plan_is_arithmetic(limit, margin, want):
    assert _plan(limit, margin) == want


def test_plan_is_monotone_in_the_limit_and_in_the_margin():
    """More memory never recomputes more; more margin never less."""
    answers = [_plan(limit).recomputed for limit in range(90, 200)]
    assert answers == sorted(answers, reverse=True)
    assert answers[0] == 5 and answers[-1] == 0
    assert set(answers) == {0, 1, 2, 3, 4, 5}
    by_margin = [_plan(160, margin).recomputed for margin in range(0, 80)]
    assert by_margin == sorted(by_margin)
    for limit in range(90, 200):
        plan = _plan(limit)
        assert plan.kept_bytes == sum(KEPT[plan.recomputed:])
        assert plan.kept_bytes <= plan.budget_bytes or plan.recomputed == 5


def test_the_peak_may_stand_in_the_backward_pass():
    """Where the gradients are large beside the loss's working set, the
    fullest moment is an application's backward pass: what is kept up to
    it and every gradient from it on. Kept applications behind it are free
    by then, which is why the last ones are the ones kept, and why the sum
    is not additive in them."""
    grads = [30] * 5
    # everything recomputed: at the first application's backward pass all
    # five gradients live beside its working set: 100 + 5 + 9 + 150 = 264
    assert _plan(258, grads=grads, head=0) == RecomputePlan(5, 0, 0)
    # every application kept: its 9 bytes beside them, and no input of a
    # recomputed one: 100 + 9 + 150 = 259; the later ones' bytes are freed
    # as the gradients come (at application 1: 16 + 120, at the loss 37)
    assert _plan(259, grads=grads, head=0) == RecomputePlan(0, 37, 37)
    # a weight shared by applications: its gradient lives from the last use.
    # The last two kept: 100 + 3 + 9 + 120 = 232 at the first, 103 + 7 + 120
    # at the fourth; a third kept stands beside both gradients: 102 + 14 +
    # 120 = 236
    shared = [0, 0, 0, 60, 60]
    assert _plan(231, grads=shared, head=0) == RecomputePlan(5, 0, 0)
    assert _plan(232, grads=shared, head=0) == RecomputePlan(3, 14, 14)
    assert _plan(235, grads=shared, head=0) == RecomputePlan(3, 14, 17)
    assert _plan(236, grads=shared, head=0) == RecomputePlan(2, 21, 21)
    assert _plan(250, grads=shared, head=0) == RecomputePlan(0, 37, 37)


# ------------------------------------------- the two cells' real shapes

def _cell_model(bench, cell_name):
    with open(os.path.join(REPO, "benchmarks", "workloads",
                           cell_name + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    args = bench.build_parser().parse_args(
        config["bench_args"] + cell["bench_args"])
    assert args.remat
    model = models.build(
        args.model, vocab_size=args.vocab, dtype=jnp.bfloat16, remat=True,
        **bench.lm_model_args(args, "flash"))
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))["params"]
    return args, model, params


@pytest.mark.parametrize("cell, applications, recomputed, kept_gib", [
    # PERF.md section 6, PR 33
    ("trinity_mini_seq4096_1chip", 5, 1, 2.83),
    ("ouro_seq4096_1chip", 32, 27, 3.16),
    # PR 34: the last two expert blocks of six kept
    ("moonlight_seq8192_1chip", 6, 4, 1.67),
])
def test_the_cells_real_shapes_on_a_v5e(bench, cell, applications,
                                        recomputed, kept_gib):
    args, model, params = _cell_model(bench, cell)
    assert len(model.applications()) == applications
    # Adam: the parameters and two moments
    state_bytes = 3 * sum(x.size * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(params))
    tokens = args.batch_size * args.seq_len
    rows = models.lm_logits_rows(model, tokens, args.fused_ce)
    assert rows == (512 if cell.startswith("ouro") else tokens)

    def plan(limit):
        return decoder.plan_recomputation(
            model, params, args.batch_size, args.seq_len, state_bytes, rows,
            limit)

    assert plan(None) == RecomputePlan(applications, 0, 0)
    on_chip = plan(V5E)
    assert on_chip.recomputed == recomputed
    assert on_chip.kept_bytes / 2 ** 30 == pytest.approx(kept_gib, abs=0.01)
    assert on_chip.kept_bytes <= on_chip.budget_bytes
    # the estimate of the whole step stays under the limit less the margin
    assert plan(V5E // 2).recomputed == applications
    assert plan(4 * V5E).recomputed == 0


def test_a_blocks_kept_bytes_are_a_closed_sum():
    """Ouro's block over the cell's 8,192 tokens, by hand: six copies of the
    stream (4,096 B a token each), q, k, v and the kernel's output (4,096
    each), 16 heads' log-sum-exp in lane rows of 512 B, and the feed-forward's
    gate, up and product (11,264 each)."""
    block = models.build(
        "looped_lm", vocab_size=8, embed_dim=2048, num_layers=1, loops=1,
        heads=16, kv_heads=16, head_dim=128, ffn_width=5632).block(0)
    per_token = 6 * 4096 + 4 * 4096 + 16 * 512 + 3 * 11264
    assert block.kept_bytes(8192, 2048) == 8192 * per_token == 679_477_248
    # float32 compute doubles what is held in the compute type
    wide = block.clone(dtype=jnp.float32).kept_bytes(8192, 2048)
    assert wide == 8192 * (2 * (per_token - 16 * 512) + 16 * 512)


def test_a_latent_blocks_kept_bytes_are_a_closed_sum():
    """Moonlight's expert block over the cell's 16,384 tokens, by hand: the
    stream before each branch (two norms a block: 4,096 B a token each), the
    compressed row with the rope key before its norm and after (576 x 2 B
    each), q of 192 a head, the expanded k and v of 128, the kernels' output
    of 128 (16 heads x 576 x 2 B), 16 heads' log-sum-exp in lane rows of 512
    B, the router's float32 scores and a token's choices, and the shared
    experts' gate, up and product (2,816 x 2 B each)."""
    model = models.build(
        "moe_lm", vocab_size=8, embed_dim=2048,
        layer_types=(decoder.LATENT, decoder.LATENT), heads=16, kv_heads=16,
        head_dim=128, rope_dim=64, value_dim=128, latent_dim=512, window=0,
        dense_layers=1, dense_width=11264, experts=64, experts_held=8,
        top_k=6, expert_width=1408, shared_experts=2, norm_outputs=False)
    attention = 2 * 576 * 2 + 16 * 576 * 2 + 16 * 512
    expert = 2 * 4096 + attention + 3 * 4 * 64 + 4 * 4 * 6 + 3 * 2816 * 2
    assert model.block(1).kept_bytes(16384, 2048) == 16384 * expert \
        == 899_153_920
    dense = 2 * 4096 + attention + 3 * 11264 * 2
    assert model.block(0).kept_bytes(16384, 2048) == 16384 * dense
    # a norm after each branch keeps its input and the stream's normed copy
    normed = model.clone(norm_outputs=True).block(1)
    assert normed.kept_bytes(16384, 2048) == 16384 * (expert + 4 * 4096)


# ----------------------------------------- what the models do with a plan

def _sparse(remat):
    model = models.build(
        "moe_lm", vocab_size=97, embed_dim=32,
        layer_types=(decoder.SLIDING, decoder.SLIDING, decoder.FULL),
        heads=4, kv_heads=2, head_dim=8, window=8, dense_layers=1,
        dense_width=48, experts=8, experts_held=4, top_k=2, expert_width=16,
        dtype=jnp.float32, attention="dense", remat=remat)
    return model, 3


def _looped(remat):
    model = models.build(
        "looped_lm", vocab_size=97, embed_dim=32, num_layers=2, loops=3,
        heads=4, kv_heads=2, head_dim=8, ffn_width=48, dtype=jnp.float32,
        attention="dense", remat=remat)
    return model, 6


def _variables(model):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0, 97)
    variables = model.init(jax.random.PRNGKey(0), tokens[:1])
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(jax.random.PRNGKey(i), x.shape)
        for i, x in enumerate(leaves)])
    return dict(variables, params=params), tokens


def _loss_and_grads(model, variables, tokens):
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        out = model.apply({"params": params, **rest}, tokens)
        return jnp.mean(jnp.square(out.astype(jnp.float32)))

    return jax.jit(jax.value_and_grad(loss))(variables["params"])


@pytest.mark.parametrize("make", [_sparse, _looped])
def test_gradients_agree_whichever_applications_are_recomputed(
        make, monkeypatch):
    model, total = make(False)
    variables, tokens = _variables(model)
    want, want_grads = _loss_and_grads(model, variables, tokens)
    shapes = jax.tree_util.tree_map(jnp.shape, variables["params"])
    wrapped, again = [], decoder._apply_again
    monkeypatch.setattr(
        decoder, "_apply_again",
        lambda block, h: wrapped.append(block.name) or again(block, h))
    names = [f"DecoderBlock_{i}" for i in model.applications()]
    for remat in (True, total - 1, 1, RecomputePlan(2, 10, 20), total + 3):
        other, _ = make(remat)
        # the same leaves whichever applications are wrapped: a looped
        # model's weight is one leaf, not one a loop step
        assert jax.tree_util.tree_map(
            lambda x: x.shape, jax.eval_shape(
                other.init, jax.random.PRNGKey(0),
                tokens[:1])["params"]) == shapes
        del wrapped[:]
        got, got_grads = _loss_and_grads(other, variables, tokens)
        count = getattr(remat, "recomputed", remat)
        count = total if count is True else min(count, total)
        # the first ones are run again, the last ones kept
        assert wrapped == names[:count], remat
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                        jax.tree_util.tree_leaves(want_grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("make, remat", [
    (_sparse, True), (_sparse, RecomputePlan(1, 4096, 8192)),
    (_looped, False), (_looped, 2), (_looped, RecomputePlan(5, 7, 9))])
def test_gauges_say_what_the_plan_answered(hvd, make, remat):
    """Through an ``hvd.spmd_fn`` handle, as a lane's step is traced: the
    gauges are the handle's program's."""
    model, total = make(remat)
    variables, tokens = _variables(model)
    state, optimizer = models.create_train_state(
        jax.random.PRNGKey(0), model, optax.adam(1e-3), tokens[:1])
    spec = models.state_partition_specs(state)
    n = hvd.size()
    batch = {"tokens": jnp.tile(tokens[:1], (n, 1))}
    timeline.reset()
    step = hvd.spmd_fn(models.make_lm_train_step(model, optimizer,
                                                 bias_coeff=0.001),
                       in_specs=(spec, P("hvd")), out_specs=(spec, P()))
    _, loss = step(state, batch)
    assert np.isfinite(float(loss))
    snap = timeline.snapshot()
    (program,) = {s["args"]["program"] for s in snap["spans"]
                  if s["name"] == timeline.DISPATCH}
    plan = remat if isinstance(remat, RecomputePlan) else RecomputePlan(
        {True: total, False: 0}.get(remat, remat))
    read = {name: snap["gauges"][name][program] for name in (
        "hvd.remat.applications", "hvd.remat.recomputed",
        "hvd.remat.kept_bytes", "hvd.remat.budget_bytes")}
    assert read == {"hvd.remat.applications": total,
                    "hvd.remat.recomputed": plan.recomputed,
                    "hvd.remat.kept_bytes": plan.kept_bytes,
                    "hvd.remat.budget_bytes": plan.budget_bytes}
    if make is _looped:
        assert snap["gauges"]["hvd.loop.applications"][program] == total


# ------------------------------------------------- the lane fills the field

MOE = ("--model moe_lm --lm-layers 3 --lm-dim 64 --lm-heads 4 --lm-kv-heads 2 "
       "--lm-head-dim 16 --lm-window 16 --lm-layer-types sliding,sliding,full "
       "--lm-ffn 96 --lm-dense-layers 1 --moe-experts 8 --moe-experts-held 4 "
       "--moe-top-k 2 --moe-width 32 --vocab 128 --batch-size 2 --seq-len 32")
LOOPED = ("--model looped_lm --lm-layers 2 --lm-loops 3 --lm-dim 64 "
          "--lm-heads 4 --lm-kv-heads 2 --lm-head-dim 16 --lm-ffn 96 --vocab "
          "128 --batch-size 2 --seq-len 32")
DENSE = ("--model transformer_lm --lm-layers 2 --lm-dim 64 --lm-heads 4 "
         "--vocab 128 --batch-size 2 --seq-len 32")


def _lane(bench, line):
    return bench.build_lane(bench.build_parser().parse_args(line.split()),
                            lambda *a, **k: None)


@pytest.mark.parametrize("line, total", [(MOE, 3), (LOOPED, 6)],
                         ids=["moe_lm", "looped_lm"])
def test_the_lane_keeps_what_the_reported_limit_holds(
        hvd, bench, monkeypatch, line, total):
    """The CPU reports no memory limit: ``--remat`` is every application, the
    program the flag has always built here. A device that says how much it
    offers: the lane asks the plan with its own state and shapes, and the
    model it steps carries the answer."""
    assert device.memory_limit() is None
    assert _lane(bench, line).model.remat is False
    assert _lane(bench, line + " --remat").model.remat \
        == RecomputePlan(total, 0, 0)
    asked = []

    def plan_recomputation(model, params, batch, length, state_bytes,
                           logits_rows, limit):
        asked.append((batch, length, state_bytes, logits_rows, limit))
        return real(model, params, batch, length, state_bytes, logits_rows,
                    limit)

    real = decoder.plan_recomputation
    monkeypatch.setattr(decoder, "plan_recomputation", plan_recomputation)
    monkeypatch.setattr(decoder, "RECOMPUTE_MARGIN", 0)
    answers = []
    for limit in (1, 1 << 40):
        monkeypatch.setattr(device, "memory_limit", lambda: limit)
        lane = _lane(bench, line + " --remat")
        answers.append(lane.model.remat.recomputed)
        state_bytes = sum(x.nbytes for x in
                          jax.tree_util.tree_leaves(lane.state))
        # 64 tokens: all of them, or under one chunk of 512
        assert asked[-1] == (2, 32, state_bytes, 64, limit)
        timeline.reset()
        _, loss = lane.run_step(lane.state, lane.batch)
        assert np.isfinite(float(loss))
        snap = timeline.snapshot()
        (program,) = {s["args"]["program"] for s in snap["spans"]
                      if s["name"] == timeline.DISPATCH}
        assert snap["gauges"]["hvd.remat.recomputed"][program] == answers[-1]
        assert snap["gauges"]["hvd.remat.applications"][program] == total
    assert answers == [total, 0]


def test_the_dense_lm_keeps_its_flag_as_it_is(hvd, bench, monkeypatch):
    """``TransformerLM``'s ``remat`` stays the bool it was, whatever the
    device offers (ROADMAP D18)."""
    monkeypatch.setattr(device, "memory_limit", lambda: 1 << 40)
    assert _lane(bench, DENSE + " --remat").model.remat is True
    assert _lane(bench, DENSE).model.remat is False
