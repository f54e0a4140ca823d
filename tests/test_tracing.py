"""The program's tracing module (``horovod_tpu/utils/timeline.py``): spans,
counters, the compile listener, the exchange's plan as gauges, the device
scopes in the lowered step, and the Chrome writer as an exporter of spans."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.common import state as _state
from horovod_tpu.utils import timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = (timeline.FORWARD, timeline.LOSS, timeline.EXCHANGE,
          timeline.UPDATE, timeline.METRICS)


def _spans(name=None, snap=None):
    """The records called ``name``; with no name all of them but the
    collections of the heap, which come when they will."""
    found = (snap or timeline.snapshot())["spans"]
    return [s for s in found
            if (s["name"] != timeline.GC if name is None
                else s["name"] == name)]


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("hvd",))


def test_spans_nest_and_carry_their_parent(hvd):
    timeline.reset()
    with timeline.span("outer", why="test") as outer:
        with timeline.span("inner") as inner:
            assert timeline.enclosing("outer") is outer
            assert timeline.enclosing("inner") is inner
        assert timeline.enclosing("inner") is None
    inner_rec, outer_rec = _spans()         # a span is recorded as it closes
    assert (inner_rec["name"], outer_rec["name"]) == ("inner", "outer")
    assert inner_rec["parent"] == outer_rec["id"] and outer_rec["parent"] == 0
    assert outer_rec["args"] == {"why": "test"}
    assert outer_rec["start_ns"] <= inner_rec["start_ns"] \
        <= inner_rec["end_ns"] <= outer_rec["end_ns"]


def test_reset_forgets_a_trace_that_still_waited(hvd):
    """A jaxpr trace with nothing lowered after it (``jax.eval_shape``, the
    verifier's audits) waits on its thread for an outer trace to replace it;
    ``reset`` forgets it with the rest, so it cannot surface in the records
    of whatever runs next (``test_lane.py`` before this file did that)."""
    timeline.reset()
    jax.eval_shape(jax.jit(lambda x: x * 2.0), jnp.zeros((3,)))
    timeline.reset()
    with timeline.span("after"):
        pass
    with timeline.span("after"):      # a close is what hands traces over
        pass
    assert [s["name"] for s in _spans()] == ["after", "after"]


def test_a_span_closes_when_its_body_raises(hvd):
    timeline.reset()
    with pytest.raises(KeyError):
        with timeline.span("fails"):
            raise KeyError("x")
    assert [s["name"] for s in _spans()] == ["fails"]
    assert timeline.enclosing("fails") is None


def test_dispatch_spans_carry_handle_and_call(hvd):
    timeline.reset()

    def counted_step(x):
        return hvd.allreduce(x, name="t")

    run = hvd.spmd_fn(counted_step, in_specs=P("hvd"), out_specs=P("hvd"))
    x = jnp.ones((8, 4), jnp.float32)
    with timeline.span("loop") as loop:
        for _ in range(3):
            run(x)
    calls = _spans(timeline.DISPATCH)
    assert [(s["args"]["handle"], s["args"]["call"]) for s in calls] == [
        ("counted_step", i) for i in range(3)]
    assert {s["parent"] for s in calls} == {loop.id}
    # the call that compiled says so in its record, and so does what it
    # lies in; a call that only dispatched carries the step clock's readings
    # and nothing more
    first, outer = calls[0]["args"], _spans("loop")[0]["args"]
    assert first["programs"] >= 1 and first["compile_s"] > 0
    assert outer["programs"] == first["programs"]
    assert outer["compile_s"] == pytest.approx(first["compile_s"])
    assert all(set(s["args"]) - {"runq_ms"} == {
        "handle", "program", "call", "period_ms", "cpu_ms", "vol", "invol",
        "majflt"} for s in calls[1:])
    assert len({s["args"]["program"] for s in calls}) == 1


def test_two_handles_of_one_name_are_two_programs(hvd):
    """A train and an eval ``step_fn``, or a handle rebuilt for another
    mesh: their calls and their exchange plans stay apart."""
    from horovod_tpu.jax import fusion

    timeline.reset()

    def step_fn(x):
        return fusion.fused_reduce([x], name="g")[0]

    x = jnp.ones((8, 4), jnp.float32)
    small = hvd.spmd_fn(step_fn, mesh=_mesh(2), in_specs=P(), out_specs=P())
    large = hvd.spmd_fn(step_fn, mesh=_mesh(4), in_specs=P(), out_specs=P())
    small(x), large(jnp.concatenate([x, x])), small(x)
    calls = _spans(timeline.DISPATCH)
    assert [s["args"]["handle"] for s in calls] == ["step_fn"] * 3
    first, second = _program("step_fn", 0), _program("step_fn", 1)
    assert first != second
    assert [(s["args"]["program"], s["args"]["call"]) for s in calls] == [
        (first, 0), (second, 0), (first, 1)]
    nbytes = timeline.snapshot()["gauges"]["hvd.exchange.bytes"]
    assert nbytes[first] == x.nbytes and nbytes[second] == 2 * x.nbytes


def test_compile_records_lie_under_the_call_that_compiled(hvd):
    """Call 0 holds trace, lowering and backend compile; a later call that
    meets a new shape shows its re-trace under itself, and no other does."""
    timeline.reset()

    def reshaped_step(x):
        return hvd.allreduce(x * 2.0, name="t")

    run = hvd.spmd_fn(reshaped_step, in_specs=P("hvd"), out_specs=P("hvd"))
    before = timeline.snapshot()["counters"].get("hvd.compile.programs", 0)
    run(jnp.ones((8, 4), jnp.float32))
    run(jnp.ones((8, 4), jnp.float32))
    run(jnp.ones((8, 6), jnp.float32))      # forces a second trace
    snap = timeline.snapshot()
    calls = {s["args"]["call"]: s["id"] for s in snap["spans"]
             if s["name"] == timeline.DISPATCH}
    compiles = [s for s in snap["spans"]
                if s["name"].startswith("hvd.compile.")]
    by_call = {c: sorted({s["name"] for s in compiles if s["parent"] == i})
               for c, i in calls.items()}
    whole = ["hvd.compile.backend", "hvd.compile.lower", "hvd.compile.trace"]
    assert by_call[0] == whole and by_call[2] == whole
    assert by_call[1] == []
    counters = snap["counters"]
    assert counters["hvd.compile.programs"] - before >= 2
    assert counters["hvd.compile.seconds"] > 0
    spans = {s["args"]["call"]: s for s in snap["spans"]
             if s["name"] == timeline.DISPATCH}
    for call in (0, 2):         # the span's own sum is its records' sum
        under = [s for s in compiles if s["parent"] == calls[call]]
        assert spans[call]["args"]["compile_s"] == pytest.approx(
            sum(s["end_ns"] - s["start_ns"] for s in under) / 1e9)
        assert spans[call]["args"]["programs"] == sum(
            s["name"] == "hvd.compile.backend" for s in under)
    # only the outermost trace of a program is kept: the seconds add up
    for parent in (calls[0], calls[2]):
        traces = [s for s in compiles if s["parent"] == parent
                  and s["name"] == "hvd.compile.trace"]
        ordered = sorted(traces, key=lambda s: s["start_ns"])
        assert all(a["end_ns"] <= b["start_ns"]
                   for a, b in zip(ordered, ordered[1:]))


def test_the_ring_drops_the_oldest_and_counts_it(hvd):
    timeline.reset()
    extra = 5
    for i in range(timeline.RING + extra):
        with timeline.span("tick", i=i):
            pass
    snap = timeline.snapshot()
    ticks = _spans(snap=snap)
    assert len(ticks) == timeline.RING
    assert snap["dropped"] == extra
    assert ticks[0]["args"] == {"i": extra}
    assert ticks[-1]["args"] == {"i": timeline.RING + extra - 1}
    timeline.reset()
    # what is left is the detector's own: its counters at 0, which say that
    # it is installed, and the collections since the reset
    snap = timeline.snapshot()
    assert snap.pop("counters").keys() == {
        "hvd.host.stalls", "hvd.host.stall_s", "hvd.host.gc_collections",
        "hvd.host.gc_s"}
    assert all(s["name"] == timeline.GC for s in snap.pop("spans"))
    assert snap == {"dropped": 0, "dropped_compiles": 0, "dropped_gcs": 0,
                    "gauges": {}}


def test_a_flood_of_compile_records_pushes_out_no_span(hvd):
    """Compile records have a ring of their own: an eager phase of hundreds
    of small programs costs older compile records, never a span."""
    timeline.reset()
    with timeline.span("kept"):
        pass
    for _ in range(timeline.RING + 3):
        timeline._on_duration("/jax/core/compile/backend_compile_duration",
                              1e-6)
    snap = timeline.snapshot()
    assert snap["dropped_compiles"] == 3 and snap["dropped"] == 0
    assert [s["name"] for s in _spans(snap=snap)
            if not s["name"].startswith("hvd.compile.")] == ["kept"]
    assert snap["counters"]["hvd.compile.programs"] == timeline.RING + 3
    timeline.reset()


def test_counters_add_gauges_set_and_dump_writes_json(hvd, tmp_path):
    timeline.reset()
    timeline.count("n")
    timeline.count("n", 2)
    timeline.gauge("g", 5)
    timeline.gauge("g", 7)
    timeline.gauge("g", 1, key="program")
    with timeline.span("s", k="v"):
        pass
    path = tmp_path / "snapshot.json"
    timeline.dump(str(path))
    snap = json.loads(path.read_text())
    assert {k: v for k, v in snap["counters"].items()
            if not k.startswith("hvd.host.")} == {"n": 3}
    assert snap["gauges"] == {"g": {"": 7, "program": 1}}
    assert [s["name"] for s in snap["spans"]] == ["s"]
    assert snap == json.loads(json.dumps(timeline.snapshot()))


def _program(handle, nth=-1):
    """The ``program`` id of the ``nth`` handle called ``handle`` that was
    dispatched since the reset."""
    seen = dict.fromkeys(s["args"]["program"]
                         for s in _spans(timeline.DISPATCH)
                         if s["args"]["handle"] == handle)
    return list(seen)[nth]


def _gauges_of(handle, nth=-1):
    program = _program(handle, nth)
    return {k: v[program] for k, v in timeline.snapshot()["gauges"].items()
            if program in v}


def _grad_leaves():
    rng = np.random.RandomState(0)
    shapes = [(300, 40), (40,), (64, 64), (7,), (2000,)]
    return [jnp.asarray(rng.randn(*s), jnp.float32) for s in shapes]


@pytest.mark.parametrize("threshold", [64 * 1024 * 1024, 20_000])
@pytest.mark.parametrize("overlap", ["off", "on"])
def test_exchange_gauges_equal_the_bucket_plan_on_four_devices(
        hvd, overlap, threshold):
    from horovod_tpu.jax import fusion

    timeline.reset()
    leaves = _grad_leaves()

    def plan_step(*xs):
        return tuple(fusion.fused_reduce(
            list(xs), fusion_threshold=threshold, overlap=overlap,
            name="grads"))

    run = hvd.spmd_fn(plan_step, mesh=_mesh(4), in_specs=P(), out_specs=P())
    run(*leaves)
    plan = fusion.plan_buckets(leaves, threshold)
    got = _gauges_of("plan_step")
    assert got["hvd.exchange.buckets"] == len(plan)
    assert got["hvd.exchange.bytes"] == sum(b.nbytes for b in plan)
    assert got["hvd.exchange.bytes"] == sum(x.nbytes for x in leaves)
    assert got["hvd.exchange.tensors"] == len(leaves)
    # every member reduced in its own shape: one collective a tensor, and
    # no byte copied into a flat buffer first
    assert got["hvd.exchange.calls"] == got["hvd.exchange.tensors"]
    assert got["hvd.exchange.packed_bytes"] == 0
    # and what the handle says of its own compile: no option on the CPU
    assert got.pop("hvd.spmd.compile_options") == 0
    assert set(got) == {"hvd.exchange." + what for what in (
        "calls", "bytes", "tensors", "buckets", "packed_bytes")}


def test_exchange_gauges_count_the_ladders_legs(hvd):
    from horovod_tpu.jax import fusion

    timeline.reset()
    leaves = _grad_leaves()
    st = _state.global_state()
    saved = st.config.hierarchical_inner_size
    st.config.hierarchical_inner_size = 2

    def ladder_step(*xs):
        return tuple(fusion.fused_reduce(list(xs), hierarchical="on",
                                         name="grads"))

    try:
        hvd.spmd_fn(ladder_step, mesh=_mesh(4), in_specs=P(),
                    out_specs=P())(*leaves)
    finally:
        st.config.hierarchical_inner_size = saved
    got = _gauges_of("ladder_step")
    assert got["hvd.exchange.buckets"] == 1
    assert got["hvd.exchange.calls"] == 3
    assert got["hvd.exchange.bytes"] == sum(x.nbytes for x in leaves)
    # the ladder packs every bucket into its flat, padded buffer
    assert got["hvd.exchange.packed_bytes"] == got["hvd.exchange.bytes"]


def test_exchange_gauges_are_zero_on_one_device_and_a_retrace_overwrites(hvd):
    from horovod_tpu.jax import fusion

    timeline.reset()
    leaves = _grad_leaves()

    def twice_step(*xs):
        once = fusion.fused_reduce(list(xs), name="a")
        return tuple(fusion.fused_reduce(once, name="b"))

    hvd.spmd_fn(twice_step, mesh=_mesh(1), in_specs=P(),
                out_specs=P())(*leaves)
    alone = _gauges_of("twice_step")
    assert alone and not any(alone.values())

    run = hvd.spmd_fn(twice_step, mesh=_mesh(4), in_specs=P(), out_specs=P())
    run(*leaves)
    nbytes = sum(x.nbytes for x in leaves)
    read = lambda: _gauges_of("twice_step", 1)["hvd.exchange.bytes"]
    assert read() == 2 * nbytes             # two exchanges in one trace add
    run(*[jnp.concatenate([x, x]) for x in leaves])     # a re-trace
    assert read() == 4 * nbytes             # overwrites: not 2 + 4


def _lowered_text(lane):
    return lane.run_step._compiled.lower(lane.state, lane.batch).as_text(
        debug_info=True)


LANES = {
    "lm": ["--model", "transformer_lm", "--lm-layers", "1", "--lm-dim", "32",
           "--lm-heads", "2", "--vocab", "64", "--batch-size", "1",
           "--seq-len", "16", "--remat"],
    "image": ["--model", "resnet18", "--image-size", "32",
              "--batch-size", "1"],
}


@pytest.mark.parametrize("lane_name", sorted(LANES))
def test_the_five_scopes_are_in_the_lowered_step_of_both_lanes(
        hvd, monkeypatch, lane_name):
    monkeypatch.syspath_prepend(REPO)
    import bench

    timeline.reset()
    args = bench.build_parser().parse_args(LANES[lane_name])
    lane = bench.build_lane(args, lambda *a, **k: None)
    text = _lowered_text(lane)
    for scope in SCOPES:
        assert scope in text, scope
    assert f"transpose(jvp({timeline.FORWARD}))" in text    # the backward
    assert f"{timeline.UPDATE}/{timeline.EXCHANGE}" in text
    if lane_name == "lm":
        assert "checkpoint" in text                         # --remat
    names = [s["name"] for s in _spans()]
    build = next(s for s in _spans() if s["name"] == "hvd.lane.build")
    assert build["args"]["model"] == args.model
    programs = sum(1 for s in _spans("hvd.compile.backend")
                   if build["start_ns"] <= s["start_ns"] <= build["end_ns"])
    assert build["args"]["programs"] == programs > 0
    for child in ("hvd.lane.model_init", "hvd.lane.train_state",
                  "hvd.lane.place"):
        assert child in names
        assert all(s["parent"] == build["id"] for s in _spans(child))
    under_build = {s["id"] for s in _spans() if s["parent"] == build["id"]}
    assert any(s["name"] == "hvd.compile.backend"
               and s["parent"] in under_build for s in _spans())


@pytest.mark.parametrize("pinned", [None, "dense", "flash"])
def test_the_lm_step_says_which_attention_it_traced(hvd, monkeypatch,
                                                    pinned):
    """A GPT-2-shaped step (heads of 64, block recomputation on): the gauges
    ``hvd.attn.*_calls`` of the step's program count its three attention
    calls by implementation (unset, the policy's: dense on this platform),
    ``.block_q`` / ``.block_k`` are the kernels' blocks, ``.paired_calls``
    the kernels' calls whose programs serve two heads (all three: two heads
    of 64 from the block's fused projection), and the attention runs under
    the scope ``hvd_attn_full``."""
    monkeypatch.syspath_prepend(REPO)
    import bench

    timeline.reset()
    args = bench.build_parser().parse_args(
        ["--model", "transformer_lm", "--lm-layers", "3", "--lm-dim", "128",
         "--lm-heads", "2", "--vocab", "64", "--batch-size", "1",
         "--seq-len", "64", "--remat"]
        + (["--attention", pinned] if pinned else []))
    lane = bench.build_lane(args, lambda *a, **k: None)
    state, loss = lane.run_step(lane.state, lane.batch)   # donates
    assert np.isfinite(float(loss))
    assert f"/{timeline.ATTN_FULL}/" in lane.run_step._compiled.lower(
        state, lane.batch).as_text(debug_info=True)
    step = next(s["args"]["program"] for s in _spans("hvd.spmd.dispatch")
                if s["args"]["handle"] == "step_fn")
    gauges = timeline.snapshot()["gauges"]
    got = {name.rsplit(".", 1)[1]: by_program[step]
           for name, by_program in gauges.items()
           if name.startswith("hvd.attn.") and step in by_program}
    if pinned == "flash":
        assert got == {"flash_calls": 3, "dense_calls": 0, "block_q": 64,
                       "block_k": 64, "fused_bwd_calls": 3,
                       "paired_calls": 3, "diagonal_slab_calls": 0,
                       "slab_rows": 0}
    else:
        assert got == {"flash_calls": 0, "dense_calls": 3,
                       "fused_bwd_calls": 0, "paired_calls": 0,
                       "diagonal_slab_calls": 0}
    assert lane.stamp["attention"] == (pinned or "dense")


def test_the_looped_step_carries_its_scopes_and_gauges(hvd, monkeypatch):
    """A looped LM's step: ``hvd_loop_step`` once an application of the
    stack, ``hvd_exit_gate`` in the forward pass and in the loss,
    ``hvd_exit_loss`` inside ``hvd_loss``, and the gauges
    ``hvd.loop.applications`` (counted in the loop, one a block application
    traced, which is what a step executes because the loop is unrolled: the
    same as ``hvd.attn.dense_calls``) and ``hvd.exit.live_logits_bytes``
    keyed by the step's program."""
    monkeypatch.syspath_prepend(REPO)
    import bench

    timeline.reset()
    args = bench.build_parser().parse_args(
        ["--model", "looped_lm", "--lm-layers", "2", "--lm-loops", "4",
         "--lm-dim", "32", "--lm-heads", "2", "--lm-ffn", "48", "--vocab",
         "64", "--batch-size", "1", "--seq-len", "16", "--remat"])
    lane = bench.build_lane(args, lambda *a, **k: None)
    state, loss = lane.run_step(lane.state, lane.batch)   # donates
    assert np.isfinite(float(loss))
    text = lane.run_step._compiled.lower(state, lane.batch).as_text(
        debug_info=True)
    for scope in SCOPES + (timeline.LOOP_STEP, timeline.EXIT_GATE,
                           timeline.EXIT_LOSS, timeline.ATTN_FULL):
        assert scope in text, scope
    for outer, inner in ((timeline.FORWARD, timeline.LOOP_STEP),
                         (timeline.FORWARD, timeline.EXIT_GATE),
                         (timeline.LOSS, timeline.EXIT_LOSS),
                         (timeline.LOSS, timeline.EXIT_GATE)):
        assert re.search(rf'{outer}\)[^"]*/{inner}/', text), (outer, inner)
    assert {timeline.LOOP_STEP, timeline.EXIT_GATE, timeline.EXIT_LOSS} \
        <= set(timeline.LAYER_SCOPES)
    step = next(s["args"]["program"] for s in _spans("hvd.spmd.dispatch")
                if s["args"]["handle"] == "step_fn")
    gauges = timeline.snapshot()["gauges"]
    got = {name: by_program[step] for name, by_program in gauges.items()
           if name.startswith(("hvd.loop.", "hvd.exit.", "hvd.attn."))
           and step in by_program}
    assert got == {"hvd.loop.applications": 8,
                   "hvd.exit.live_logits_bytes": 4 * 4 * 15 * 64,
                   "hvd.attn.kv_heads": 2, "hvd.attn.dense_calls": 8,
                   "hvd.attn.flash_calls": 0, "hvd.attn.fused_bwd_calls": 0,
                   "hvd.attn.paired_calls": 0,
                   "hvd.attn.diagonal_slab_calls": 0}


def test_the_latent_step_carries_its_scopes_and_its_gauge(hvd, monkeypatch):
    """A sparse LM of latent layers: ``hvd_attn_latent`` around the attention
    call, ``hvd_latent_compress`` around the ``W_kva`` product and the row's
    norm, ``hvd_latent_expand`` around the ``W_kvb`` product and the rope
    key's rotation, all inside ``hvd_forward``; ``step_profile`` attributes
    the three; and ``hvd.attn.latent_expanded_bytes`` of the step's program
    reads what the two layers' forward passes write for the kernels: a chip's
    16 tokens x (2 heads x (8 + 8) + one rope key of 4) x 2 bytes, twice,
    counted once though both blocks are traced again for recomputation."""
    monkeypatch.syspath_prepend(REPO)
    import bench
    from horovod_tpu.utils import step_profile

    timeline.reset()
    args = bench.build_parser().parse_args(
        ["--model", "moe_lm", "--lm-layers", "2", "--lm-dim", "32",
         "--lm-heads", "2", "--lm-head-dim", "8", "--lm-rope-dim", "4",
         "--lm-value-dim", "8", "--lm-latent-dim", "16", "--lm-layer-types",
         "latent,latent", "--no-lm-output-norms", "--no-lm-embed-scale",
         "--lm-ffn", "48", "--lm-dense-layers", "1", "--moe-experts", "4",
         "--moe-experts-held", "2", "--moe-top-k", "2", "--moe-width", "16",
         "--vocab", "64", "--batch-size", "1", "--seq-len", "16", "--remat"])
    lane = bench.build_lane(args, lambda *a, **k: None)
    state, loss = lane.run_step(lane.state, lane.batch)   # donates
    assert np.isfinite(float(loss))
    text = lane.run_step._compiled.lower(state, lane.batch).as_text(
        debug_info=True)
    latent = (timeline.ATTN_LATENT, timeline.LATENT_COMPRESS,
              timeline.LATENT_EXPAND)
    for scope in SCOPES + latent:
        assert scope in text, scope
    for inner in latent:
        assert re.search(rf'{timeline.FORWARD}\)[^"]*/{inner}/', text), inner
        assert step_profile.layer_of(
            f"jit(step_fn)/jvp(hvd_forward)/DecoderBlock_1/attn/{inner}/"
            f"dot_general") == inner
    assert set(latent) <= set(timeline.LAYER_SCOPES)
    assert timeline.ATTN_WINDOW not in text and timeline.ATTN_FULL not in text
    step = next(s["args"]["program"] for s in _spans("hvd.spmd.dispatch")
                if s["args"]["handle"] == "step_fn")
    gauges = timeline.snapshot()["gauges"]
    got = {name: by_program[step] for name, by_program in gauges.items()
           if name.startswith("hvd.attn.") and step in by_program}
    assert got == {"hvd.attn.latent_expanded_bytes":
                   2 * 16 * (2 * (8 + 8) + 4) * 2,
                   "hvd.attn.dense_calls": 2, "hvd.attn.flash_calls": 0,
                   "hvd.attn.fused_bwd_calls": 0, "hvd.attn.paired_calls": 0,
                   "hvd.attn.diagonal_slab_calls": 0}


def test_windowed_train_step_has_the_same_scopes(hvd):
    import optax

    from horovod_tpu import models

    model = models.MNISTNet()
    state, opt = models.create_train_state(
        jax.random.PRNGKey(0), model, optax.sgd(0.1),
        jnp.zeros((1, 28, 28, 1), jnp.float32))
    step = models.make_windowed_train_step(model, opt, 2)
    batch = {"image": jnp.zeros((2, 8, 28, 28, 1), jnp.float32),
             "label": jnp.zeros((2, 8), jnp.int32)}
    run = hvd.spmd_fn(step, in_specs=(P(), P(None, "hvd")),
                      out_specs=(P(), P()))
    text = run._compiled.lower(state, batch).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope


def test_hvd_grad_puts_its_exchange_under_the_scope(hvd):
    def loss(w, x):
        return jnp.sum((x @ w) ** 2)

    def grad_step(w, x):
        value, grads = hvd.value_and_grad(loss)(w, x)
        return value, grads, hvd.grad(loss)(w, x)

    run = hvd.spmd_fn(grad_step, in_specs=(P(), P("hvd")),
                      out_specs=(P(), P(), P()))
    text = run._compiled.lower(
        jnp.ones((4, 2)), jnp.ones((8, 4))).as_text(debug_info=True)
    assert text.count(timeline.EXCHANGE) >= 2


def _chrome_events(path):
    return json.loads(path.read_text().rstrip().rstrip(",\n") + "]")


def test_the_chrome_writer_exports_dispatch_under_its_old_names(hvd,
                                                                tmp_path):
    """``HOROVOD_TIMELINE`` output for ``hvd.spmd.dispatch``: the events the
    inline branch of ``spmd.py`` wrote, key for key."""
    st = _state.global_state()
    trace = tmp_path / "trace.json"
    saved = st.timeline
    st.timeline = timeline.Timeline(str(trace))

    def exported_step(x):
        return hvd.allreduce(x, name="t")

    try:
        run = hvd.spmd_fn(exported_step, in_specs=P("hvd"),
                          out_specs=P("hvd"))
        for _ in range(3):
            run(jnp.ones((8, 4), jnp.float32))
        with timeline.span("hvd.lane.place", rows=8):
            pass
    finally:
        st.timeline.close()
        st.timeline = saved
    events = _chrome_events(trace)
    tid = next(e["tid"] for e in events if e["name"] == "thread_name"
               and e["args"]["name"] == "exported_step")
    mine = [e for e in events if e["tid"] == tid and e["ph"] in "BE"]
    assert [(e["name"], e["ph"]) for e in mine] == [
        ("XLA_COMPILE", "B"), ("XLA_COMPILE", "E"),
        ("XLA_EXECUTE", "B"), ("XLA_EXECUTE", "E"),
        ("XLA_EXECUTE", "B"), ("XLA_EXECUTE", "E")]
    assert [list(e) for e in mine[:2]] == [
        ["name", "ph", "pid", "tid", "ts", "args"],
        ["name", "ph", "pid", "tid", "ts"]]
    assert [e["args"] for e in mine if e["ph"] == "B"] == [
        {"span": "trace+compile"}, {"span": "host_dispatch"},
        {"span": "host_dispatch"}]
    other = [e for e in events if e["name"] == "hvd.lane.place"]
    assert [e["ph"] for e in other] == ["B", "E"]
    assert other[0]["args"] == {"rows": 8}


def test_the_names_nothing_emitted_are_gone():
    for name in ("QUEUE", "INIT_FUSION_BUFFER", "XLA_TRACE"):
        assert not hasattr(timeline, name)
    for method in ("mark_cycle_start", "negotiate_rank_ready"):
        assert not hasattr(timeline.Timeline, method)
    spmd = open(os.path.join(REPO, "horovod_tpu", "parallel",
                             "spmd.py")).read()
    assert "tl.start(track" not in spmd


def test_profile_step_builds_no_model_of_its_own():
    text = open(os.path.join(REPO, "tools", "profile_step.py")).read()
    assert "bench.build_lane(" in text
    for word in ("TransformerLM(", "models.build(", "create_train_state(",
                 "benchmarks"):
        assert word not in text.split('"""', 2)[2], word
