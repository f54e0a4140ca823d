"""The one attention policy (``ops.attention.attention_plan``): which
implementation, blocks and backward a causal attention call gets from its
static shapes, and that everything that used to decide for itself (the
kernels' default blocks, ``flash_grid_info``, ``attend``, the models' blocks)
reads it.

The table is the v5e's (``tools/tpu_flash_check.py --block-sweep``, PERF.md
PR 29), asked for with ``backend="tpu"``: no kernel runs here. Tolerances of
the one numerical test: float32 inputs, the kernels interpreted against the
masked dense reference, 2e-5 absolute on outputs and gradients of size one
(online softmax against a plain one; read: 2e-6).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import attention
from horovod_tpu.ops.attention import (
    FLASH_BWD,
    FLASH_SLAB,
    AttentionPlan,
    attend,
    attention_plan,
    dot_product_attention,
    flash_attention,
    flash_grid_info,
)

BF16, F32 = jnp.bfloat16, jnp.float32


def _slabs(block):
    """The plan's slab rows for square blocks of ``block`` on the packed
    path with the one-kernel backward: two slabs or more, or none."""
    return FLASH_SLAB if block >= 2 * FLASH_SLAB else None


# (Lq, Lk, heads, KV heads, head width, window, dtype, backend) -> plan: the
# implementation, blocks, backward, heads a kernel program serves and the
# rows of a diagonal block's slabs
TABLE = {
    "gpt2_medium_cell": ((1024, 1024, 16, 16, 64, None, BF16, "tpu"),
                         ("flash", 1024, 1024, "fused", 2, _slabs(1024))),
    "trinity_sliding_layer": ((4096, 4096, 32, 4, 128, 2048, BF16, "tpu"),
                              ("flash", 1024, 1024, "fused", 1,
                               _slabs(1024))),
    "trinity_full_layer": ((4096, 4096, 32, 4, 128, None, BF16, "tpu"),
                           ("flash", 1024, 1024, "fused", 1, _slabs(1024))),
    "heads_of_64_at_2048": ((2048, 2048, 16, 16, 64, None, BF16, "tpu"),
                            ("flash", 1024, 1024, "fused", 2, _slabs(1024))),
    "float32_inputs": ((4096, 4096, 8, 8, 64, None, F32, "tpu"),
                       ("flash", 1024, 1024, "fused", 2, _slabs(1024))),
    "blocks_of_512_divide": ((1536, 1536, 16, 16, 64, None, BF16, "tpu"),
                             ("flash", 512, 512, "fused", 2, _slabs(512))),
    # the layout is the shapes' alone, as the backward is: a dense answer
    # names it too, and the slabs
    "only_256_divides": ((1280, 1280, 16, 16, 64, None, BF16, "tpu"),
                         ("dense", 256, 256, "fused", 2, _slabs(256))),
    "rectangular": ((512, 768, 4, 4, 64, None, BF16, "tpu"),
                    ("dense", 512, 256, "fused", 1, None)),
    "below_the_measured_lengths": ((512, 512, 16, 16, 64, None, BF16, "tpu"),
                                   ("dense", 512, 512, "fused", 2,
                                    _slabs(512))),
    "no_block_divides": ((100, 100, 4, 4, 64, None, BF16, "tpu"),
                         ("dense", None, None, "fused", 2, None)),
    "cpu_backend": ((1024, 1024, 16, 16, 64, None, BF16, "cpu"),
                    ("dense", 1024, 1024, "fused", 2, _slabs(1024))),
    "this_platform": ((4096, 4096, 32, 4, 128, 2048, BF16, None),
                      ("dense", 1024, 1024, "fused", 1, _slabs(1024))),
    # the one-kernel backward's resident dQ: 65,536 queries of 128 are the
    # budget, a Ulysses shard of 131,072 is past it (and its split keeps
    # every block's whole square)
    "longest_side_measured": ((65536, 65536, 2, 2, 128, None, BF16, "tpu"),
                              ("flash", 1024, 1024, "fused", 1,
                               _slabs(1024))),
    "query_side_past_the_budget": ((131072, 131072, 2, 2, 128, None, BF16,
                                    "tpu"),
                                   ("flash", 1024, 1024, "pallas", 1, None)),
}


@pytest.mark.parametrize("case", sorted(TABLE))
def test_the_plan_of_a_shape(case):
    asked, want = TABLE[case]
    assert attention_plan(*asked) == AttentionPlan(*want)


def test_the_plan_answers_the_backward_from_the_shapes():
    """One kernel where a (batch, head) program's float32 dQ, with its
    output block, fits the VMEM budget beside what the split holds: at
    the five language cells' shapes (16 MiB plus 1, 1, 4, 4 and 16 MiB).
    The split where it does not, whatever else the call looks like; a
    pin is a pin; and ``attend`` counts the calls it hands the one
    kernel."""
    from horovod_tpu.utils import timeline

    cells = [(1024, 16, 16, 64, None),             # both GPT-2 cells
             (4096, 32, 4, 128, 2048),             # Trinity-Mini
             (4096, 16, 16, 128, None),            # Ouro
             (8192, 16, 16, (192, 128), None)]     # Moonlight
    for length, heads, kv_heads, width, window in cells:
        assert attention.attention_plan(
            length, length, heads, kv_heads, width, window,
            backend="tpu")[:4] == ("flash", 1024, 1024, "fused")
    mib = 1 << 20
    assert attention.fused_bwd_vmem_bytes(1024, 64, 2) == 17 * mib
    assert attention.fused_bwd_vmem_bytes(4096, 128, 2) == 20 * mib
    assert attention.fused_bwd_vmem_bytes(8192, 192, 2) == 32 * mib
    assert attention.fused_bwd_vmem_bytes(65536, 128, 2) \
        == attention.FLASH_FUSED_VMEM_BUDGET == 80 * mib
    for length, width, dtype, bwd in [
            (65536, 128, jnp.bfloat16, "fused"),
            (65536 + 1024, 128, jnp.bfloat16, "pallas"),
            (65536, 192, jnp.bfloat16, "pallas"),   # two lanes of 128
            (65536, 128, jnp.float32, "pallas"),    # a wider gradient
            (32768, 128, jnp.float32, "fused"),
            (131072, 64, jnp.bfloat16, "pallas")]:
        assert attention.attention_plan(
            length, length, 2, 2, width, dtype=dtype,
            backend="tpu").bwd == bwd, (length, width, dtype)
        # the CPU's dense answer names the same backward: shapes alone
        assert attention.attention_plan(
            length, length, 2, 2, width, dtype=dtype,
            backend="cpu").bwd == bwd

    q = jnp.ones((1, 32, 2, 8))
    for pin, counted in [(None, 1), ("fused", 1), ("pallas", 0),
                         ("scan", 0)]:
        attention.attend(q, q, q, impl="flash", block_q=8, block_k=8,
                         **({"bwd_impl": pin} if pin else {}))
        gauges = timeline.snapshot()["gauges"]
        program = timeline.tracing_program()[0]
        assert gauges["hvd.attn.fused_bwd_calls"][program] == counted
        assert gauges["hvd.attn.flash_calls"][program] == 1
    attention.attend(q, q, q, impl="dense")
    gauges = timeline.snapshot()["gauges"]
    assert gauges["hvd.attn.fused_bwd_calls"][program] == 0
    with pytest.raises(ValueError, match="auto|scan|pallas|fused"):
        flash_attention(q, q, q, causal=True, bwd_impl="split")


PAIRS = {   # (Lq, Lk, heads, KV heads, width), keywords -> heads a program
    "heads_of_64": ((1024, 1024, 16, 16, 64), {}, 2),
    "two_heads_of_64": ((1024, 1024, 2, 2, 64), {}, 2),
    "under_a_window": ((4096, 4096, 8, 8, 64), {"window": 2048}, 2),
    "keys_and_values_of_64_said_apart": ((1024, 1024, 16, 16, (64, 64)), {},
                                         2),
    "heads_of_128": ((4096, 4096, 16, 16, 128), {}, 1),
    "heads_of_32": ((1024, 1024, 16, 16, 32), {}, 1),
    "an_odd_number_of_heads": ((1024, 1024, 15, 15, 64), {}, 1),
    "grouped_kv_heads": ((1024, 1024, 16, 4, 64), {}, 1),
    "keys_wider_than_values": ((8192, 8192, 16, 16, (192, 128)), {}, 1),
    "values_of_64_under_wider_keys": ((1024, 1024, 16, 16, (128, 64)), {},
                                      1),
    "a_shared_key": ((1024, 1024, 16, 16, 64), {"shared_key": True}, 1),
    "an_offset": ((1024, 1024, 16, 16, 64), {"q_offset": 1024}, 1),
    "rectangular": ((1024, 2048, 16, 16, 64), {}, 1),
    # past the one-kernel backward's budget the split runs, one head a program
    "the_split_backward": ((131072, 131072, 2, 2, 64), {}, 1),
}


@pytest.mark.parametrize("case", sorted(PAIRS))
def test_the_plan_pairs_heads_of_64_and_nothing_else(case):
    """Two heads a program exactly where two heads fill a 128-lane tile and
    the kernels can read it where a projection wrote it: keys and values
    both 64 wide, an even number of heads, a KV head a query head, no shared
    key, the causal square call with no offset, the one-kernel backward.
    The answer is the shapes' alone: the same on every backend."""
    shape, keywords, want = PAIRS[case]
    for backend in ("tpu", "cpu"):
        assert attention_plan(*shape, backend=backend,
                              **keywords).heads_per_program == want


@pytest.mark.parametrize("fused_projection", [False, True])
def test_attend_counts_the_calls_whose_programs_serve_two_heads(
        fused_projection):
    """``hvd.attn.paired_calls`` reads the kernels' calls of the traced
    program that run two heads a program: heads of 64 do, from separate q, k
    and v as from a fused projection; a pinned split backward, a dense call
    and heads of another width do not."""
    from horovod_tpu.utils import timeline

    def call(width, heads=2, **kw):
        q = jnp.ones((1, 32, heads, width))
        if fused_projection:
            return attend(jnp.ones((1, 32, 3 * heads * width)), heads=heads,
                          **kw).shape == (1, 32, heads * width)
        return attend(q, q, q, **kw).shape == q.shape

    blocks = dict(block_q=8, block_k=8)
    timeline.reset()
    with timeline.span("hvd.spmd.dispatch", handle="step_fn",
                       program="step_fn#0", call=0):
        assert call(64, impl="flash", **blocks)
        assert call(64, 4, impl="flash", **blocks)
        assert call(64, impl="flash", bwd_impl="pallas", **blocks)
        assert call(64, impl="dense")
        assert call(64)                     # the plan's: dense on the CPU
        assert call(16, impl="flash", **blocks)
    gauges = timeline.snapshot()["gauges"]
    got = {name.rsplit(".", 1)[1]: by_program["step_fn#0"]
           for name, by_program in gauges.items()
           if name.startswith("hvd.attn.") and name.endswith("_calls")}
    assert got == {"flash_calls": 4, "dense_calls": 2, "fused_bwd_calls": 3,
                   "paired_calls": 2, "diagonal_slab_calls": 0}
    with pytest.raises(ValueError, match="fused projection"):
        attend(jnp.ones((1, 32, 3 * 2 * 64)))           # heads not said
    timeline.reset()


def test_the_plan_refuses_heads_no_group_divides():
    with pytest.raises(ValueError, match="KV heads"):
        attention_plan(1024, 1024, 16, 3, 64)


def _kernel_grids(fn, *args):
    """The grids of the Pallas calls in ``fn``'s program."""
    grids = []

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                visit(sub)

    visit(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("length, window, blocks", [
    (1024, None, None), (4096, 2048, None), (1536, None, None),
    (384, None, None), (2048, None, (512, 1024))])
def test_grid_info_agrees_with_the_grid_the_kernels_run(length, window,
                                                        blocks):
    """``flash_grid_info`` and a ``flash_attention`` call with no blocks
    given read the same plan: the accounting's blocks are the plan's, and
    its grid is the grid of the forward and the backward kernel traced (of
    the forward, dQ and dK/dV kernels where the split is pinned)."""
    given = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}
    info = flash_grid_info(length, length, causal=True, window=window,
                           batch_heads=2, **given)
    plan = attention_plan(length, length, 2, 1, 8, window, F32)
    assert (info["block_q"], info["block_k"]) == (
        blocks or (plan.block_q, plan.block_k))
    q = jax.ShapeDtypeStruct((1, length, 2, 8), F32)
    kv = jax.ShapeDtypeStruct((1, length, 1, 8), F32)
    for pin, kernels in (({}, 2), ({"bwd_impl": "pallas"}, 3)):
        grids = _kernel_grids(
            jax.grad(lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window, **given, **pin).sum(),
                argnums=(0, 1, 2)), q, kv, kv)
        assert grids == [tuple(info["grid"])] * kernels


def test_flash_equals_dense_at_heads_of_64_and_blocks_of_1024():
    """The GPT-2 cell's tile (heads of 64, one 1,024 x 1,024 block a step of
    the packed grid, here 3 of a 2 x 2 grid) interpreted, against the dense
    reference: values and the three gradients."""
    key = jax.random.PRNGKey(29)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (1, 2048, 1, 64), F32) for i in range(3))
    weight = jax.random.normal(jax.random.fold_in(key, 3), q.shape, F32)
    info = flash_grid_info(2048, 2048, causal=True)
    assert (info["block_q"], info["block_k"], info["steps"]) == (1024, 1024, 3)

    def run(attention):
        return jax.value_and_grad(
            lambda *a: jnp.sum(attention(*a) * weight), argnums=(0, 1, 2))

    flash, got = run(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    dense, want = run(lambda *a: dot_product_attention(*a, causal=True))(
        q, k, v)
    np.testing.assert_allclose(flash, dense, rtol=1e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("impl", [None, "dense", "flash"])
def test_attend_runs_what_the_plan_or_the_caller_says(impl):
    """``attend`` on this platform: the plan's dense reference, or the pinned
    side; both equal the masked reference, window and grouping included."""
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (2, 64, 4, 8), F32)
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, 64, 2, 8), F32)
            for i in (1, 2))
    want = dot_product_attention(q, k, v, causal=True, window=24)
    got = attend(q, k, v, window=24, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    kernels = _kernel_grids(lambda *a: attend(*a, window=24, impl=impl),
                            q, k, v)
    assert len(kernels) == (impl == "flash")
    with pytest.raises(ValueError, match="dense|flash"):
        attend(q, k, v, impl="auto")


def test_on_a_tpu_attend_traces_the_kernels_but_not_under_an_offset(
        monkeypatch):
    """What the chip gets at GPT-2-medium's shape (traced only: nothing is
    compiled or run): one forward kernel over a (batch x heads / 2, 1) grid,
    two heads of 64 a program, from separate q, k and v as from the block's
    fused projection, and over (batch x heads, 1) at heads of 128; an offset
    mask is outside what the policy was measured on and stays dense."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((2, 1024, 16, 64), BF16)
    assert _kernel_grids(attend, q, q, q) == [(16, 1)]
    assert _kernel_grids(lambda qkv: attend(qkv, heads=16),
                         jax.ShapeDtypeStruct((2, 1024, 3 * 1024), BF16)) \
        == [(16, 1)]
    wide = jax.ShapeDtypeStruct((2, 1024, 16, 128), BF16)
    assert _kernel_grids(attend, wide, wide, wide) == [(32, 1)]
    assert not _kernel_grids(lambda *a: attend(*a, q_offset=1024), q, q, q)
    assert FLASH_BWD == "pallas"


FUSED_CALLS = "flash_fused_bwd_calls_per_step.tok"
PAIRED_CALLS = "flash_paired_calls_per_step.tok"
SLAB_CALLS = "flash_diagonal_slab_calls_per_step.tok"
LANGUAGE_CELLS = ("gpt2m_seq1024_1chip", "gpt2m_seq1024_dp4",
                  "trinity_mini_seq4096_1chip", "ouro_seq4096_1chip",
                  "moonlight_seq8192_1chip", "gpt2m_seq4096_flash_1chip")
# metric -> (the gauge it reads, the cells that list it)
CALL_COUNTERS = {
    FUSED_CALLS: ("hvd.attn.fused_bwd_calls", LANGUAGE_CELLS),
    PAIRED_CALLS: ("hvd.attn.paired_calls",
                   LANGUAGE_CELLS[:2] + LANGUAGE_CELLS[-1:]),
    SLAB_CALLS: ("hvd.attn.diagonal_slab_calls",
                 LANGUAGE_CELLS + ("granite_h_micro_seq16384_1chip",)),
}


# the state-space cell runs the kernels too, and lists the slab counter alone
@pytest.mark.parametrize("cell", LANGUAGE_CELLS + (
    "resnet50_bs128_1chip", "granite_h_micro_seq16384_1chip"))
@pytest.mark.parametrize("metric", sorted(CALL_COUNTERS))
def test_the_kernels_call_counters_are_metrics_of_their_cells(metric, cell):
    """``flash_fused_bwd_calls_per_step.tok`` (``BENCHMARK.json``; the reader
    ``benchmarks/metrics/``) is listed by six of the cells that train
    through the kernels (the state-space cell, whose one attention layer
    runs them, lists no flash metric) and reads the gauge ``hvd.attn.fused_bwd_calls`` of the step
    handle's program: nothing on a program that sets no such gauge (the
    parent of PR 35) or whose calls all take the split.
    ``flash_paired_calls_per_step.tok`` is listed by the three cells whose
    heads are 64 wide, one a KV head, and reads ``hvd.attn.paired_calls``
    the same way: nothing at the parent of PR 37 or where every call runs
    one head a program. ``flash_diagonal_slab_calls_per_step.tok`` is
    listed by all seven cells that train through the kernels and reads
    ``hvd.attn.diagonal_slab_calls``: nothing at the parent of PR 39 or
    where no call walks its diagonal in slabs."""
    import json
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks import run
    from horovod_tpu.utils import timeline

    gauge, cells = CALL_COUNTERS[metric]
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    reported = {m["name"]: m for m in run.metrics_of(manifest, cell,
                                                     "per_layer")}
    assert (metric in reported) == (cell in cells)
    if cell not in cells:
        return
    entry = reported[metric]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("calls", "higher", "program_counter",
                                "training_kernels", "tok_per_s_per_chip")
    read = run.load_reader(metric)
    timeline.reset()
    for call in range(3):
        with timeline.span("hvd.spmd.dispatch", handle="step_fn",
                           program="step_fn#0", call=call):
            pass
    timeline.gauge("hvd.attn.flash_calls", 24, key="step_fn#0")
    assert read({}) is None             # the parent: no such gauge
    timeline.gauge(gauge, 0, key="step_fn#0")
    assert read({}) is None             # no call of the kind
    timeline.gauge(gauge, 24, key="step_fn#0")
    timeline.gauge(gauge, 3, key="other#1")
    assert read({}) == 24
    timeline.reset()
