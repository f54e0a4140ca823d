"""The sparse decoder LM (``bench.py --model moe_lm``: models/decoder.py,
parallel/moe.py, the windowed grouped flash kernels) against the plain
reference ``benchmarks/reference/trinity.py``, at a small size with seeded
weights on the CPU.

Tolerances and why:

* float32 program against the float32 reference: 2e-5 on every gap. Both
  compute the same function in the same precision; what is left is the order
  of additions (the reference adds experts up in a loop and takes the batch a
  row at a time, the program sorts rows and sums a token's pairs), some 1e-6
  as read.
* the expert layer alone, float32: 1e-5 absolute on outputs of size one.
* flash kernels (interpreted) against masked dense attention, float32: 2e-5
  absolute on outputs and gradients of size one (online softmax against a
  plain one; read: 1e-6).
* a reference that drops past a capacity is seen to fail the layer's 1e-5 by
  orders of magnitude (a dropped token loses a whole expert's output).
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
from benchmarks.reference import common, trinity  # noqa: E402
from horovod_tpu.ops.attention import (  # noqa: E402
    _causal_step_tables,
    dot_product_attention,
    flash_attention,
    flash_grid_info,
)
from horovod_tpu.parallel import moe  # noqa: E402

TYPES = ["sliding_attention"] * 4 + ["full_attention"]
HYPER = {"heads": 4, "kv_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-5,
         "rope_theta": 10000.0, "sliding_window": 16, "layer_types": TYPES,
         "experts": 16, "first_expert": 4, "top_k": 3, "route_scale": 2.826,
         "embed_scale": True, "load_balance_coeff": 0.001,
         "optimizer": {"name": "adam", "lr": 0.0001, "b1": 0.9, "b2": 0.999,
                       "eps": 1e-08}}
BENCH_ARGS = [
    "--model", "moe_lm", "--lm-layers", "5", "--lm-dim", "64", "--lm-heads",
    "4", "--lm-kv-heads", "2", "--lm-head-dim", "16", "--lm-window", "16",
    "--lm-layer-types", "sliding,sliding,sliding,sliding,full", "--lm-ffn",
    "96", "--lm-dense-layers", "1", "--moe-experts", "16",
    "--moe-experts-held", "4", "--moe-first-expert", "4", "--moe-top-k", "3",
    "--moe-width", "32", "--moe-route-scale", "2.826", "--vocab", "128"]
CONFIG = {
    "bench_args": BENCH_ARGS, "kernel_gain": 1.0,
    "int_ranges": {"tokens": 128},
    "draws": {"experts_gate": {"mean": 0.0, "std": 0.125},
              "experts_up": {"mean": 0.0, "std": 0.125},
              "experts_down": {"mean": 0.0, "std": 0.177}},
    "first_moment": {"field": "mu", "scale": 10.0},
    "reference": {"file": "reference/trinity.py", "hyper": HYPER}}
CELL = {"name": "toy", "chips": 1, "compare_steps": 3,
        "bench_args": ["--batch-size", "2", "--seq-len", "32", "--remat"],
        "reference_rows_per_block": 1}


@pytest.fixture(scope="module")
def programs(hvd):
    """The lane ``bench.build_lane`` makes of the arguments, float32, once
    with dense and once with flash attention, as ``run.py`` drives it: data
    parallel over the test mesh's chips (2 sequences each), so the gradients
    and the experts' counts of the step are also summed over chips."""
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
    over three Adam steps (the selection bias moving after each)."""
    program = programs(attention)
    state, batch = program.start(seed)
    state, prog = program.first_steps(state, batch, seed)
    bias = [np.asarray(layer["moe"]["selection_bias"])
            for layer in state["buffers"].values()]
    ref = program.reference(seed, jax.devices()[0])
    gaps = compare.gaps(prog, ref)
    for name, (gap, where) in gaps.items():
        assert gap < 2e-5, (name, gap, where)
    assert sorted(prog["grad_norms"]) == sorted(ref["grad_norms"])
    assert len(bias) == 4
    for b in bias:          # three steps of 0.001, centred
        assert 0 < np.abs(b).max() < 0.0065 and abs(b.sum()) < 1e-6


def _layer(seed, tokens=64, d=16, f=8, experts=16, k=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (tokens, d))
    params = {"router": jax.random.normal(ks[1], (d, experts)),
              "experts_gate": jax.random.normal(ks[2], (experts, d, f)) * .3,
              "experts_up": jax.random.normal(ks[3], (experts, d, f)) * .3,
              "experts_down": jax.random.normal(ks[4], (experts, f, d)) * .3}
    bias = 0.1 * jax.random.normal(ks[5], (experts,))
    return x, params, bias


def _share(x, params, bias, first, held, k=3):
    stacked = {n: params["experts_" + n][first:first + held]
               for n in ("gate", "up", "down")}
    return moe.routed_experts(x, params["router"], stacked, bias,
                              first=first, top_k=k, route_scale=2.0)


def _reference_share(x, params, bias, first, held, k=3, capacity=None):
    """``trinity._experts`` over the held experts; ``capacity`` plants the
    fault: an expert's tokens past so many are dropped."""
    hyper = {"top_k": k, "first_expert": first, "route_scale": 2.0}
    p = {n: (v[first:first + held] if n != "router" else v)
         for n, v in params.items()}
    y, counts = trinity._experts(x, p, bias, hyper=hyper,
                                 einsum=common.make_einsum("float32"))
    if capacity is None:
        return y, counts
    scores = jax.nn.sigmoid(x @ params["router"])
    _, chosen = jax.lax.top_k(scores + bias, k)
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = 2.0 * picked / picked.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(first, first + held):
        mine = (chosen == e).any(-1)
        kept = mine & (jnp.cumsum(mine) <= capacity)
        w = jnp.where(chosen == e, weights, 0).sum(-1) * kept
        h = jax.nn.silu(x @ params["experts_gate"][e]) \
            * (x @ params["experts_up"][e])
        y = y + w[:, None] * (h @ params["experts_down"][e])
    return y, counts


@pytest.mark.parametrize("routing", ["as_drawn", "all_to_held",
                                     "none_to_held"])
def test_expert_layer_drops_nothing_under_any_routing(routing):
    """Every token to held experts (the sorted buffer full to its last row),
    no token to them (every group empty), and the draw: equal to the
    reference, which drops nothing; and a reference with a capacity fails."""
    x, params, bias = _layer(1)
    first, held, k = 4, 4, 3
    if routing != "as_drawn":
        # a bias of +-100 on the held experts decides the top k alone
        lift = 100.0 if routing == "all_to_held" else -100.0
        bias = bias.at[first:first + held].add(lift)
    got, counts = _share(x, params, bias, first, held, k)
    want, ref_counts = _reference_share(x, params, bias, first, held, k)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(counts, ref_counts)
    assert float(counts.sum()) == x.shape[0] * k
    in_held = float(counts[first:first + held].sum())
    if routing == "all_to_held":
        assert in_held == x.shape[0] * k            # the bound, reached
    elif routing == "none_to_held":
        assert in_held == 0 and float(jnp.abs(got).max()) == 0.0
    if routing != "none_to_held":
        dropped, _ = _reference_share(x, params, bias, first, held, k,
                                      capacity=8)
        assert float(jnp.abs(dropped - want).max()) > 1e-2


def test_expert_layer_gradients_match_the_reference():
    x, params, bias = _layer(2)

    def loss(fn, x, params):
        return jnp.sum(fn(x, params, bias, 4, 4)[0] ** 2)

    got = jax.grad(functools.partial(loss, _share), (0, 1))(x, params)
    want = jax.grad(functools.partial(loss, _reference_share), (0, 1))(
        x, params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("experts, k, scale, shared_width", [
    (16, 3, 2.0, 8),        # Trinity-Mini's: 8 shares of 2 of 16, one shared
    (64, 6, 2.446, 16),     # Moonlight's: 8 shares of 8 of 64, top 6, the
])                          # two shared experts as one MLP of twice the width
def test_the_eight_shares_add_up_to_the_uncut_layer(experts, k, scale,
                                                    shared_width):
    """The share test: the routed parts of all eight shares, plus what every
    chip computes alike (the shared experts) counted once, add up to what the
    reference gives for the whole layer."""
    x, params, bias = _layer(3, experts=experts, k=k)
    held = experts // 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    w = shared_width
    shared = {"gate": {"kernel": jax.random.normal(ks[0], (16, w)) * .3},
              "up": {"kernel": jax.random.normal(ks[1], (16, w)) * .3},
              "down": {"kernel": jax.random.normal(ks[2], (w, 16)) * .3}}
    einsum = common.make_einsum("float32")
    whole, _ = trinity._experts(
        x, dict(params, shared=shared), bias, einsum=einsum,
        hyper={"top_k": k, "first_expert": 0, "route_scale": scale})

    def share(first):
        stacked = {n: params["experts_" + n][first:first + held]
                   for n in ("gate", "up", "down")}
        return moe.routed_experts(x, params["router"], stacked, bias,
                                  first=first, top_k=k, route_scale=scale)[0]

    shares = sum(share(first) for first in range(0, experts, held))
    once = trinity._mlp(x, shared, einsum)
    np.testing.assert_allclose(shares + once, whole, atol=1e-5)
    # and a share is not the whole: the cut leaves something out
    assert float(jnp.abs(share(0) + once - whole).max()) > 1e-2


def test_selection_bias_rule():
    counts = jnp.array([0., 4., 4., 8.])
    new = moe.update_selection_bias(jnp.zeros(4), counts, 0.001)
    np.testing.assert_allclose(new, [0.001, 0.0, 0.0, -0.001], atol=1e-9)
    lopsided = moe.update_selection_bias(jnp.zeros(4),
                                         jnp.array([0., 0., 0., 16.]), 0.001)
    np.testing.assert_allclose(lopsided, [0.0005] * 3 + [-0.0015], atol=1e-9)


@pytest.mark.parametrize("heads, kv_heads, window, bq, bk, bwd, truncate", [
    (4, 2, 24, 16, 8, "pallas", None),      # window, grouped, bq > bk
    (4, 1, 20, 8, 16, "pallas", None),      # one KV head, bq < bk
    (2, 2, 33, 16, 16, "pallas", None),     # window no multiple of a block
    (4, 2, None, 16, 16, "pallas", None),   # grouped, no window
    (4, 2, 24, 16, 8, "pallas", False),     # the full grid: compute skips
    (2, 1, 1, 8, 8, "pallas", None),        # a window of the token itself
    (4, 2, 24, 16, 8, "scan", None),        # the scan backward
    (4, 2, 24, 16, 8, "fused", None),       # one backward kernel: window,
    (4, 1, 20, 8, 16, "fused", None),       # grouped, both block orders,
    (2, 2, 33, 16, 16, "fused", None),      # a window no multiple of a block
    (4, 2, 24, 16, 8, "fused", False),      # and the full grid
    (2, 1, 1, 8, 8, "fused", None),
])
def test_windowed_grouped_flash_matches_masked_dense(heads, kv_heads, window,
                                                     bq, bk, bwd, truncate):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, 64, heads, 16))
    k = jax.random.normal(ks[1], (2, 64, kv_heads, 16))
    v = jax.random.normal(ks[2], (2, 64, kv_heads, 16))
    w = jax.random.normal(ks[3], q.shape)
    flash = functools.partial(flash_attention, causal=True, window=window,
                              block_q=bq, block_k=bk, bwd_impl=bwd,
                              truncate=truncate)
    dense = functools.partial(dot_product_attention, causal=True,
                              window=window)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("heads, kv_heads, window, bq, bk, truncate", [
    (4, 2, 24, 16, 8, None), (4, 1, 20, 8, 16, None), (2, 2, 33, 16, 16, None),
    (4, 2, None, 16, 16, None), (4, 2, 24, 16, 8, False)])
def test_the_fused_backward_equals_the_split_under_a_window(
        heads, kv_heads, window, bq, bk, truncate):
    """Window and grouped heads: the q-blocks' resident ``dq`` rows start at
    the band's first k-block and not at block 0, and a KV head's ``dk`` /
    ``dv`` are summed over its query heads outside the kernel, in float32,
    as the split's are. Equal to the last bit."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (2, 64, heads, 16))
    k = jax.random.normal(ks[1], (2, 64, kv_heads, 16))
    v = jax.random.normal(ks[2], (2, 64, kv_heads, 16))
    w = jax.random.normal(ks[3], q.shape)

    def grads(bwd):
        return jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, window=window, block_q=bq, block_k=bk,
            bwd_impl=bwd, truncate=truncate) * w), (0, 1, 2))(q, k, v)

    for a, b in zip(grads("fused"), grads("pallas")):
        np.testing.assert_array_equal(a, b)


def test_band_tables_hold_exactly_the_live_blocks():
    """Every (q-block, k-block) pair with a visible (query, key) pair is in
    the tables once, in both orders, and no other."""
    nq, nk, bq, bk, window = 16, 8, 256, 512, 2048
    exact = {(qi, kb) for qi in range(nq) for kb in range(nk)
             if np.any((np.arange(qi * bq, qi * bq + bq)[:, None]
                        >= np.arange(kb * bk, kb * bk + bk)[None])
                       & (np.arange(qi * bq, qi * bq + bq)[:, None]
                          - np.arange(kb * bk, kb * bk + bk)[None] < window))}
    for k_major in (False, True):
        qi_tab, kb_tab = _causal_step_tables(nq, nk, bq, bk, k_major, window)
        pairs = list(zip(qi_tab.tolist(), kb_tab.tolist()))
        assert len(pairs) == len(set(pairs)) and set(pairs) == exact
    info = flash_grid_info(4096, 4096, causal=True, window=2048,
                           block_q=256, block_k=256)
    assert info["steps"] == 108 and info["steps_full"] == 256   # 136 causal
    info = flash_grid_info(4096, 4096, causal=True, window=2048)
    assert (info["block_q"], info["block_k"]) == (1024, 1024)  # the plan's
    assert info["steps"] == 9 and info["steps_full"] == 16     # 10 causal


def test_a_window_needs_the_plain_causal_square():
    q = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match="KV heads"):
        flash_attention(q, jnp.zeros((1, 16, 3, 8)), jnp.zeros((1, 16, 3, 8)),
                        causal=True)


AFMOE = dict(qk_norm=True, gate=True)


@pytest.mark.parametrize("fields, leaves", [
    # afmoe's layers, as SparseDecoderLM sets them: the tree PR 28 drew
    (dict(window=16, rotary=True, **AFMOE),
     {"q", "k", "v", "gate", "out", "q_norm", "k_norm"}),
    (dict(window=None, rotary=False, **AFMOE),
     {"q", "k", "v", "gate", "out", "q_norm", "k_norm"}),
    # ouro's: rotary positions on full attention, no q/k norm, no gate
    (dict(window=None, rotary=True), {"q", "k", "v", "out"}),
    (dict(window=None, rotary=False, qk_norm=True),
     {"q", "k", "v", "out", "q_norm", "k_norm"})])
def test_rotary_norms_and_gate_are_the_layers_own(fields, leaves):
    """Each of the three is a field of the layer, tied to nothing else: the
    parameters are those of what is on, and the result equals the layer
    written out."""
    from horovod_tpu.models import decoder

    layer = decoder.GroupedAttention(heads=4, kv_heads=2, head_dim=8,
                                     dtype=jnp.float32, attention="dense",
                                     **fields)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 24))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == leaves
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(2), a.shape),
        params)
    got = layer.apply({"params": params}, x)

    def heads(name, n):
        return (x @ params[name]["kernel"]).reshape(2, 32, n, 8)

    q, k, v = heads("q", 4), heads("k", 2), heads("v", 2)
    if fields.get("qk_norm"):
        q = trinity._rms(q, params["q_norm"], 1e-5)
        k = trinity._rms(k, params["k_norm"], 1e-5)
    if fields["rotary"]:
        q, k = trinity._rotate(q, 10000.0), trinity._rotate(k, 10000.0)
    out = dot_product_attention(q, k, v, causal=True,
                                window=fields["window"]).reshape(2, 32, 32)
    if fields.get("gate"):
        out = out * jax.nn.sigmoid(x @ params["gate"]["kernel"])
    np.testing.assert_allclose(got, out @ params["out"]["kernel"],
                               rtol=2e-5, atol=2e-5)


def test_the_sparse_lm_keeps_its_layers_choices():
    """``SparseDecoderLM`` sets the three for ``afmoe``: a window layer
    rotates, a full layer does not, both have the norms and the gate."""
    from horovod_tpu.models import decoder

    seen = []
    real = decoder.GroupedAttention.__call__

    def spy(self, x):
        seen.append((self.window, self.rotary, self.qk_norm, self.gate))
        return real(self, x)

    model = decoder.SparseDecoderLM(
        vocab_size=64, embed_dim=32, layer_types=(decoder.SLIDING,
                                                  decoder.FULL),
        heads=4, kv_heads=2, head_dim=8, window=8, dense_layers=2,
        dense_width=48, experts=4, experts_held=4, top_k=2, expert_width=16,
        dtype=jnp.float32, attention="dense")
    decoder.GroupedAttention.__call__ = spy
    try:
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 16), jnp.int32)))
    finally:
        decoder.GroupedAttention.__call__ = real
    assert seen == [(8, True, True, True), (None, False, True, True)]


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_the_step_program_carries_the_layers_gauges(programs, attention):
    """``hvd.moe.*`` and ``hvd.attn.*`` of the step handle's program, as the
    exchange's gauges are keyed; block recomputation traces a layer more
    than once and must not count it twice. Five attention calls, by the
    implementation that ran, and the kernels' blocks."""
    from horovod_tpu.utils import timeline

    program = programs(attention)
    state, batch = program.start(11)
    program.first_steps(state, batch, 11)
    snap = timeline.snapshot()
    dispatched = [s["args"]["program"] for s in snap["spans"]
                  if s["name"] == "hvd.spmd.dispatch"
                  and s["args"]["handle"] == "step_fn"]
    step = dispatched[-1]
    want = {"hvd.moe.layers": 4, "hvd.moe.experts": 16,
            "hvd.moe.experts_held": 4, "hvd.moe.top_k": 3,
            "hvd.moe.tokens": 64, "hvd.moe.row_bound": 192,
            "hvd.moe.expected_rows": 48.0, "hvd.moe.cut_rows": 96,
            "hvd.attn.window": 16,
            "hvd.attn.kv_heads": 2,
            "hvd.attn.dense_calls": 5 * (attention == "dense"),
            "hvd.attn.flash_calls": 5 * (attention == "flash")}
    if attention == "flash":
        want.update({"hvd.attn.block_q": 32, "hvd.attn.block_k": 32})
    got = {name: snap["gauges"].get(name, {}).get(step) for name in want}
    assert got == want
