"""The causal kernels compute what the mask lets through
(``ops.attention``, ``slab``): a diagonal block is walked in row slabs, each
against the keys up to its own last row, and a block the causal edge does not
cross applies no mask.

Interpreted on the CPU against the dense reference and against the split
backward (``bwd_impl="pallas"``, whose kernels keep every block's whole
masked square). Tolerances are the kernels' parity tests'
(tests/test_parallel.py::TestFlashAttention): float32 2e-5 absolute on
outputs, 2e-5 absolute and 2e-4 relative on gradients; bfloat16 6e-2.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import attention
from horovod_tpu.ops.attention import (
    FLASH_SLAB,
    attention_plan,
    dot_product_attention,
    flash_attention,
)
from horovod_tpu.utils import timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16

# name -> (q [B, L, H, Dk], KV heads, values' width, shared key's width,
# window, block, slab rows, dtype)
CASES = {
    "one_block": ((1, 32, 2, 16), 2, 16, 0, None, 32, 8, F32),
    "two_blocks": ((1, 64, 2, 16), 2, 16, 0, None, 32, 16, F32),
    "four_blocks": ((1, 128, 2, 16), 2, 16, 0, None, 32, 8, F32),
    # the window's lower edge crosses some blocks and not others
    "a_window": ((1, 128, 2, 16), 2, 16, 0, 40, 32, 8, F32),
    # narrower than a slab: the edge crosses the diagonal block too
    "a_window_inside_a_slab": ((1, 128, 2, 16), 2, 16, 0, 6, 32, 16, F32),
    "grouped_kv_heads": ((2, 64, 4, 16), 2, 16, 0, None, 32, 8, F32),
    # keys of 24 (16 a head's own, 8 one vector a token for all heads)
    # beside values of 16: a latent layer's 192 = 128 + 64 and 128, scaled
    "wide_keys_and_a_shared_key": ((1, 64, 2, 24), 2, 16, 8, None, 32, 8,
                                   F32),
    "bfloat16": ((1, 128, 2, 16), 2, 16, 0, None, 32, 8, BF16),
    "bfloat16_window_grouped": ((1, 128, 4, 16), 1, 16, 0, 40, 32, 16, BF16),
}


def _tolerances(dtype):
    if dtype == F32:
        return dict(atol=2e-5), dict(atol=2e-5, rtol=2e-4)
    return dict(atol=6e-2, rtol=6e-2), dict(atol=6e-2, rtol=6e-2)


@pytest.mark.parametrize("case", sorted(CASES))
def test_slabs_equal_the_reference_and_the_split(case):
    """Output and the gradients of q, k, v (and of the shared key) of the
    kernels with the diagonal walked in slabs, against the dense reference
    in float32 and against the split backward, which computes every block
    whole under the mask."""
    shape, kv_heads, value, shared, window, block, slab, dtype = CASES[case]
    B, L, H, D = shape
    key = jax.random.PRNGKey(39)

    def draw(i, s):
        return jax.random.normal(jax.random.fold_in(key, i), s, dtype)

    args = (draw(0, shape), draw(1, (B, L, kv_heads, D - shared)),
            draw(2, (B, L, kv_heads, value))) \
        + ((draw(3, (B, L, shared)),) if shared else ())
    do = draw(4, (B, L, H, value))

    def flash(**kw):
        return lambda q, k, v, *kr: flash_attention(
            q, k, v, k_shared=kr[0] if kr else None, causal=True,
            window=window, block_q=block, block_k=block, **kw)

    def dense(q, k, v, *kr):
        return dot_product_attention(q, k, v, causal=True, window=window,
                                     k_shared=kr[0] if kr else None)

    def run(fn, inputs, cotangent):
        out, vjp = jax.vjp(fn, *inputs)
        return (out, *vjp(cotangent))

    got = run(flash(slab=slab), args, do)
    split = run(flash(bwd_impl="pallas"), args, do)
    ref = run(dense, [a.astype(F32) for a in args], do.astype(F32))
    assert len(got) == 4 + bool(shared)
    on_out, on_grads = _tolerances(dtype)
    for want in (ref, split):
        for i, (a, b) in enumerate(zip(got, want)):
            assert a.dtype == dtype
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                **(on_grads if i else on_out))


@pytest.mark.parametrize("whole_projection", [False, True],
                         ids=["separate", "fused_projection"])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_two_heads_a_program_walk_the_slabs_as_one(dtype, whole_projection):
    """Two heads of 64 a program (PR 37's layout, read where a projection
    wrote it) walk the diagonal in the same slabs as one head a program: o,
    dQ, dK and dV equal the one-head programs' and the dense reference
    within the tolerances, at four blocks a side. (Interpreted, a slab's
    products are small enough for the CPU to sum a 64-wide and a 128-wide
    contraction in other orders: a last bit apart in float32.)"""
    B, L, H, D = 2, 128, 4, 64
    key = jax.random.PRNGKey(37)
    q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i),
                                     (B, L, H, D), dtype) for i in range(4))
    kw = dict(causal=True, block_q=32, block_k=32, slab=8)

    def flat(t):
        return t.reshape(B, L, H * D)

    def two(q, k, v):
        if whole_projection:
            out = flash_attention(
                jnp.concatenate([flat(q), flat(k), flat(v)], -1), heads=H,
                **kw)
        else:
            out = flash_attention(flat(q), flat(k), flat(v), heads=H, **kw)
        return out.reshape(B, L, H, D)

    one_out, one_vjp = jax.vjp(lambda *a: flash_attention(*a, **kw),
                               q, k, v)
    two_out, two_vjp = jax.vjp(two, q, k, v)
    got = (two_out, *two_vjp(do))
    ref_out, ref_vjp = jax.vjp(
        lambda *a: dot_product_attention(*a, causal=True),
        *(t.astype(F32) for t in (q, k, v)))
    on_out, on_grads = _tolerances(dtype)
    for want in ((one_out, *one_vjp(do)), (ref_out, *ref_vjp(do.astype(F32)))):
        for i, (a, b) in enumerate(zip(want, got)):
            assert b.dtype == dtype
            np.testing.assert_allclose(
                np.asarray(b, np.float32), np.asarray(a, np.float32),
                **(on_grads if i else on_out))


# cell -> the attention_plan arguments of its attention calls
CELLS = {
    "gpt2m_seq1024": ((1024, 1024, 16, 16, 64), {}),
    "trinity_mini_sliding": ((4096, 4096, 32, 4, 128, 2048), {}),
    "trinity_mini_full": ((4096, 4096, 32, 4, 128), {}),
    "ouro": ((4096, 4096, 16, 16, 128), {}),
    "moonlight_latent": ((8192, 8192, 16, 16, (192, 128)),
                         {"shared_key": True}),
    "granite_attention": ((16384, 16384, 32, 8, 64), {}),
    "gpt2m_seq4096": ((4096, 4096, 16, 16, 64), {}),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_plan_walks_the_cells_diagonals_in_slabs(cell):
    """At every language cell's shapes the plan answers the kernels, blocks
    of 1,024 and the one-kernel backward, and so slabs of
    :data:`FLASH_SLAB` rows: the same on every backend."""
    shape, keywords = CELLS[cell]
    for backend in ("tpu", "cpu"):
        plan = attention_plan(*shape, backend=backend, **keywords)
        assert (plan.block_q, plan.block_k, plan.bwd) == (1024, 1024,
                                                          "fused")
        assert plan.slab_rows == FLASH_SLAB


@pytest.mark.parametrize("asked, keywords", [
    ((1024, 1024, 16, 16, 64), {"q_offset": 1024}),        # an offset
    ((1024, 2048, 16, 16, 64), {}),                        # rectangular
    ((131072, 131072, 2, 2, 64), {}),                      # the split
    ((2 * FLASH_SLAB - 8, 2 * FLASH_SLAB - 8, 2, 2, 64), {}),  # one slab
], ids=["offset", "rectangular", "split_backward", "block_of_one_slab"])
def test_the_plan_answers_no_slab_off_the_packed_square_fused_path(
        asked, keywords):
    assert attention_plan(*asked, backend="tpu", **keywords).slab_rows \
        is None


def _grad_jaxpr(**kw):
    """The program of the gradients of a call at 1,024 tokens, blocks of
    :data:`FLASH_SLAB` x 2 or more: traced only."""
    length = 4 * FLASH_SLAB
    q = jax.ShapeDtypeStruct((1, length, 1, 8), F32)
    keys = jax.ShapeDtypeStruct((1, kw.pop("keys", length), 1, 8), F32)
    return str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, **kw).sum(),
        argnums=(0, 1, 2)))(q, keys, keys))


@pytest.mark.parametrize("keywords", [
    dict(causal=True, q_offset=4 * FLASH_SLAB, keys=8 * FLASH_SLAB),
    dict(causal=True, block_q=2 * FLASH_SLAB, block_k=4 * FLASH_SLAB),
    dict(causal=False),
    dict(causal=True, truncate=False),
    dict(causal=True, bwd_impl="pallas"),
    dict(causal=True, bwd_impl="scan"),
], ids=["offset", "rectangular_blocks", "not_causal", "full_grid",
        "split_backward", "scan_backward"])
def test_calls_off_the_path_trace_the_whole_square_body(keywords):
    """Where the plan answers no slab a call traces the program of ``slab=0``
    (every block's whole square, masked where causal), and pinning slabs
    there is refused; on the packed square path the slabs change the
    program."""
    assert _grad_jaxpr(**keywords) == _grad_jaxpr(slab=0, **keywords)
    with pytest.raises(ValueError, match="row slabs"):
        _grad_jaxpr(slab=FLASH_SLAB, **keywords)
    assert _grad_jaxpr(causal=True) != _grad_jaxpr(causal=True, slab=0)


def test_a_pin_is_refused_where_the_block_holds_one_slab():
    q = jnp.ones((1, 32, 1, 8))
    with pytest.raises(ValueError, match="row slabs"):
        flash_attention(q, q, q, causal=True, block_q=16, block_k=16, slab=16)
    with pytest.raises(ValueError, match="row slabs"):
        flash_attention(q, q, q, causal=True, block_q=16, block_k=16, slab=6)


def test_attend_counts_the_calls_that_walk_slabs():
    """``hvd.attn.diagonal_slab_calls`` counts the kernels' calls of the
    traced program whose diagonal blocks are walked in slabs; ``.slab_rows``
    is their rows (0 for a call without)."""
    q = jnp.ones((1, 64, 2, 8))
    timeline.reset()
    with timeline.span("hvd.spmd.dispatch", handle="step_fn",
                       program="step_fn#0", call=0):
        attention.attend(q, q, q, impl="flash", block_q=32, block_k=32,
                         slab=8)
        attention.attend(q, q, q, impl="flash", block_q=32, block_k=32,
                         slab=8, window=24)
        attention.attend(q, q, q, impl="flash", block_q=32, block_k=32,
                         bwd_impl="pallas")
        attention.attend(q, q, q, impl="dense")
    gauges = timeline.snapshot()["gauges"]
    assert gauges["hvd.attn.diagonal_slab_calls"]["step_fn#0"] == 2
    assert gauges["hvd.attn.flash_calls"]["step_fn#0"] == 3
    assert gauges["hvd.attn.slab_rows"]["step_fn#0"] == 0   # the last's
    timeline.reset()


def test_a_traced_two_layer_step_counts_its_slab_calls(hvd, monkeypatch):
    """A two-layer GPT-2-shaped lane pinned to the kernels, at 64 tokens in
    one block of 64 and slabs of 16 rows (the plan's rows set for this
    length): ``hvd.attn.diagonal_slab_calls`` of the step's program reads
    the layer count, ``.slab_rows`` 16, and the loss is the one of the same
    lane without slabs."""
    monkeypatch.syspath_prepend(REPO)
    import bench

    def step(rows):
        monkeypatch.setattr(attention, "FLASH_SLAB", rows)
        timeline.reset()
        args = bench.build_parser().parse_args(
            ["--model", "transformer_lm", "--lm-layers", "2", "--lm-dim",
             "128", "--lm-heads", "2", "--vocab", "64", "--batch-size", "1",
             "--seq-len", "64", "--attention", "flash"])
        lane = bench.build_lane(args, lambda *a, **k: None)
        _, loss = lane.run_step(lane.state, lane.batch)     # donates
        program = next(
            s["args"]["program"] for s in timeline.snapshot()["spans"]
            if s["name"] == "hvd.spmd.dispatch"
            and s["args"]["handle"] == "step_fn")
        gauges = timeline.snapshot()["gauges"]
        return float(loss), {name: gauges[f"hvd.attn.{name}"][program]
                             for name in ("diagonal_slab_calls", "slab_rows",
                                          "flash_calls")}

    with_slabs, counted = step(16)
    assert counted == {"diagonal_slab_calls": 2, "slab_rows": 16,
                       "flash_calls": 2}
    whole, counted = step(64)           # a block of one slab: none
    assert counted == {"diagonal_slab_calls": 0, "slab_rows": 0,
                       "flash_calls": 2}
    np.testing.assert_allclose(with_slabs, whole, rtol=1e-5)
    timeline.reset()
