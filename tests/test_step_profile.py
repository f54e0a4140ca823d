"""``horovod_tpu/utils/step_profile.py``: the reader of ``.xplane.pb`` files
and the reduction of a device profile by the program's own names, on made-up
planes and on a small trace recorded on a four-chip v5e (``tests/data/``)."""

import json
import os
import struct

import pytest

from horovod_tpu.utils import step_profile as sp
from horovod_tpu.utils import timeline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STEP = "jit(step_fn)/jit(main)/jit(shmap_body)/"


BACK = STEP + "transpose(jvp(hvd_forward))/TransformerLM/jvp(hvd_forward)/"


@pytest.mark.parametrize("op_name, phase", [
    (STEP + "jvp(hvd_forward)/TransformerLM/Embed_0/gather:", "forward"),
    (STEP + "jvp(hvd_forward)/TransformerLM/checkpoint/TransformerBlock_0/"
     "Dense_0/dot_general:", "forward"),
    (STEP + "hvd_forward/ResNet/conv_general_dilated:", "forward"),
    (BACK + "TransformerLM/LayerNorm_0/mul:", "backward"),
    (BACK + "TransformerLM/checkpoint/TransformerBlock_3/Dense_1/"
     "dot_general:", "backward"),
    (BACK + "TransformerLM/checkpoint/rematted_computation/"
     "TransformerBlock_3/Dense_1/dot_general:", "recomputed"),
    (STEP + "jvp(hvd_loss)/log_softmax/reduce_max:", "loss"),
    (STEP + "transpose(jvp(hvd_loss))/mul:", "loss"),
    (STEP + "hvd_update/hvd_exchange/hvd_allreduce_grads_float32_b3/"
     "reduce_scatter:", "exchange"),
    (STEP + "hvd_update/scale_by_adam/mul:", "update"),
    (STEP + "hvd_metrics/optimization_barrier:", "metrics"),
    ("jit(step_fn)/jit(main)/copy:", "other"),
    ("", "other"),
])
def test_an_operation_belongs_to_the_innermost_scope_in_its_name(op_name,
                                                                 phase):
    assert sp.phase_of(op_name) == phase


@pytest.mark.parametrize("text, operation", [
    ("%psum_invariant.14 = bf16[1024,1024]{1,0:T(8,128)(2,1)} all-reduce("
     "bf16[1024,1024]{1,0} %fusion), channel_id=1", "all-reduce"),
    ("%reduce_scatter.7 = bf16[256,1024]{1,0} reduce-scatter(bf16[1024,1024] "
     "%x), channel_id=2", "reduce-scatter"),
    ("%all-gather-start.1 = (f32[8], f32[32]) all-gather-start(f32[8] %p)",
     "all-gather-start"),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", "fusion"),
    ("barrier-cores", ""),
])
def test_the_operation_is_read_from_the_instructions_text(text, operation):
    assert sp.operation(text) == operation


def _event(start, end, text="%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
           op_name="", **stats):
    return sp.Event(100.0 * start, 100.0 * end, text, op_name, stats)


ALL_REDUCE = "%psum.1 = f32[8]{0} all-reduce(f32[8]{0} %p), channel_id=1"
GATHER_START = "%ag-start = (f32[8], f32[32]) all-gather-start(f32[8] %p)"


def _planes():
    """One chip, two steps' worth of made-up operations (units of 100 ns):

    0-100 forward, 100-300 backward (a loop of two bodies, 100-190 and
    200-290, the second recomputed), 300-340 an all-reduce alone on the core,
    340-400 update with an all-gather running beside it from 330 to 380
    (started at 330-332 in the all-reduce's shadow: nested nowhere), a gap
    400-500, then 500-560 other. Host: a dispatch span 380-520 with a
    localize span 450-510 inside it."""
    fwd = STEP + "jvp(hvd_forward)/dense/dot_general:"
    bwd = BACK + "checkpoint/dense/dot_general:"
    remat = BACK + "checkpoint/rematted_computation/dense/dot_general:"
    upd = STEP + "hvd_update/scale_by_adam/mul:"
    exch = STEP + "hvd_update/hvd_exchange/hvd_allreduce_grads_float32_b0/psum:"
    ops = [
        _event(0, 100, op_name=fwd),
        _event(100, 300, "%while.1 = (f32[8]) while((f32[8]) %t)",
               op_name=bwd),
        _event(100, 190, op_name=bwd),
        _event(200, 290, op_name=remat),
        _event(300, 340, ALL_REDUCE, op_name=exch),
        _event(340, 400, op_name=upd),
        _event(500, 560),
    ]
    beside = [_event(330, 380, GATHER_START, op_name=exch)]
    host = [
        _event(380, 520, timeline.DISPATCH, handle="step_fn",
               program="step_fn#0", call=7),
        _event(450, 510, "hvd.spmd.localize"),
        _event(10, 20, timeline.DISPATCH, handle="step_fn",
               program="step_fn#0", call=6),
        _event(5, 8, timeline.DISPATCH, handle="step_fn",
               program="step_fn#1", call=0),
        _event(0, 1000, "bench.run_step"),
    ]
    return [
        sp.Plane("/device:TPU:0", [sp.Line(sp.OPS_LINE, ops),
                                   sp.Line(sp.ASYNC_LINE, beside)]),
        sp.Plane("/host:CPU", [sp.Line("python3", host)]),
        sp.Plane("/device:TPU:1", [sp.Line(sp.OPS_LINE, [])]),
    ]


def test_the_reduction_of_a_made_up_profile():
    r = sp.reduce(_planes())
    ns = lambda s: round(s * 1e7, 3)          # the units of _planes
    assert r["chips"] == 1              # a plane with no operation is none
    assert r["steps"] == 2              # step_fn's dispatch spans
    assert ns(r["busy_s"]) == 460 and ns(r["window_s"]) == 560
    assert {k: ns(v) for k, v in r["phases_s"].items()} == {
        "forward": 100, "backward": 110, "recomputed": 90, "loss": 0,
        "exchange": 40, "update": 60, "metrics": 0, "other": 60}
    assert sum(r["phases_s"].values()) == pytest.approx(r["busy_s"])
    # the all-reduce 300-340 and the all-gather 330-380: 80 ns in all, of
    # which the update ran beside 340-380
    assert ns(r["collective_s"]) == 80
    assert ns(r["collective_exposed_s"]) == 40
    assert {k: ns(v) for k, v in r["collectives_s"].items()} == {
        "all-reduce": 40, "all-gather": 50}
    # beside them: the update's fusion, 340-380
    assert {k: ns(v) for k, v in r["collective_beside_s"].items()} == {
        "fusion": 40}
    # the gap's middle (450) lies in localize, inside the dispatch
    assert r["gaps"] == 1
    assert {k: ns(v) for k, v in r["idle_gaps_s"].items()} == {
        "hvd.spmd.localize": 100}
    assert r["host_spans"] == {timeline.DISPATCH: 3, "hvd.spmd.localize": 1}
    assert r["per_step_ms"]["collective_exposed"] == pytest.approx(20e-4)
    assert "exposed" in sp.table(r)


EXCH = STEP + "hvd_update/hvd_exchange/hvd_allreduce_grads_float32_b0/psum:"
UPD = STEP + "hvd_update/scale_by_adam/mul:"
AR_START = ("%all-reduce-start.9 = (f32[8]{0}, f32[8]{0}) all-reduce-start("
            "f32[8]{0} %p), channel_id=1")
AR_DONE = ("%all-reduce-done.9 = f32[8]{0} all-reduce-done((f32[8]{0}, "
           "f32[8]{0}) %all-reduce-start.9)")
ACF_START = ("%async-collective-start.1 = (f32[8]{0}, u32[]{:S(2)}) fusion("
             "f32[8]{0} %fusion.3), kind=kCustom, calls=%fused_computation.9")
ACF_DONE = ("%async-collective-done.1 = f32[8]{0} fusion(f32[8]{0} %gte.1, "
            "u32[]{:S(2)} %gte.2), kind=kCustom, calls=%fused_computation.10")
STEP_FUSION = ("%fusion.77 = (f32[8]{0}, u32[]{:S(2)}) fusion(f32[8]{0} %a, "
               "u32[]{:S(2)} %gte.2), kind=kLoop, calls=%fused_computation.11")


def _async(ops, beside=()):
    """One chip: 0-100 backward, then ``ops`` (from 100 on), an update
    300-400; ``beside`` on ``Async XLA Ops``."""
    return [sp.Plane("/device:TPU:0", [
        sp.Line(sp.OPS_LINE, [_event(0, 100, op_name=BACK + "dense/mul:")]
                + list(ops) + [_event(300, 400, op_name=UPD)]),
        sp.Line(sp.ASYNC_LINE, list(beside))])]


@pytest.mark.parametrize("ops, beside, collective, exposed, kinds", [
    # the synchronous form: 100-300 alone on the core
    ([_event(100, 300, ALL_REDUCE, op_name=EXCH)], [], 200, 200,
     {"all-reduce": 200}),
    # start 100-102 and done 260-300 on XLA Ops with two products of the
    # backward pass 102-260 between them: 200 from start to done, of which
    # the start and the done's wait are exposed
    ([_event(100, 102, AR_START, op_name=EXCH),
      _event(102, 180, op_name=BACK + "dense/dot_general:"),
      _event(180, 260, op_name=BACK + "dense/dot_general:"),
      _event(260, 300, AR_DONE, op_name=EXCH)], [], 200, 42,
     {"all-reduce": 200}),
    # the same with the span on Async XLA Ops: counted once
    ([_event(100, 102, AR_START, op_name=EXCH),
      _event(102, 260, op_name=BACK + "dense/dot_general:"),
      _event(260, 300, AR_DONE, op_name=EXCH)],
     [_event(100, 300, AR_START, op_name=EXCH)], 200, 42,
     {"all-reduce": 200}),
    # an asynchronous collective fusion: its start, two loop fusions that
    # carry the all-reduce's steps beside Adam's passes (102-290: their own
    # work), a gap 290-295 and the done 295-300
    ([_event(100, 102, ACF_START, op_name=EXCH),
      _event(102, 200, STEP_FUSION, op_name=UPD),
      _event(200, 290, STEP_FUSION, op_name=UPD),
      _event(295, 300, ACF_DONE, op_name=EXCH)], [], 200, 12,
     {"all-reduce": 200}),
    # a done whose start the profile does not hold stands alone
    ([_event(260, 300, AR_DONE, op_name=EXCH)], [], 40, 40,
     {"all-reduce": 40}),
], ids=["synchronous", "start_done_on_xla_ops", "span_on_async_xla_ops",
        "async_collective_fusion", "done_alone"])
def test_asynchronous_all_reduces_read_start_to_done(ops, beside, collective,
                                                     exposed, kinds):
    r = sp.reduce(_async(ops, beside), steps=1)
    ns = lambda s: round(s * 1e7, 3)
    assert ns(r["collective_s"]) == collective
    assert ns(r["collective_exposed_s"]) == exposed
    assert {k: ns(v) for k, v in r["collectives_s"].items()} == kinds
    # no operation is counted twice: the phases add up to the busy time
    assert sum(r["phases_s"].values()) == pytest.approx(r["busy_s"])
    assert "all-reduce" in sp.table(r)
    # what ran beside is what was not exposed
    assert ns(sum(r["collective_beside_s"].values())) == collective - exposed
    assert ("beside the collectives" in sp.table(r)) == (exposed < collective)


def test_an_async_collective_fusion_is_named_by_its_primitive():
    gather = STEP + "hvd_forward/all_gather:"
    r = sp.reduce(_async([
        _event(100, 102, ACF_START),     # as the v5e writes it: no name
        _event(102, 250, STEP_FUSION, op_name=UPD),
        _event(250, 300, ACF_DONE, op_name=gather)]), steps=1)
    assert list(r["collectives_s"]) == ["all-gather"]
    unnamed = sp.reduce(_async([
        _event(100, 102, ACF_START), _event(250, 300, ACF_DONE)]), steps=1)
    assert list(unnamed["collectives_s"]) == ["async-collective"]
    assert round(unnamed["collective_in_other_s"] * 1e7, 3) == 52


def test_a_gap_outside_every_span_and_a_profile_with_no_chip():
    planes = _planes()
    planes[1] = sp.Plane("/host:CPU", [sp.Line("python3", [])])
    r = sp.reduce(planes, steps=5)
    assert r["steps"] == 5 and list(r["idle_gaps_s"]) == [sp.BETWEEN]
    assert sp.reduce(planes[1:]) is None


# ------------------------------------------------------ the file's format

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value % (1 << 64))
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def test_an_xspace_is_read_from_its_wire_format(tmp_path):
    stat_names = {1: "tf_op", 2: "call", 3: "ratio", 4: "handle",
                  5: "jit(f)/hvd_loss/exp:", 6: "offset"}
    plane = _field(2, "/device:TPU:0")
    for ident, name in stat_names.items():
        plane += _field(5, _entry(ident, _field(1, ident) + _field(2, name)))
    metadata = (_field(1, 9) + _field(2, "%exp.1 = f32[8]{0} exponential()")
                + _field(5, _field(1, 1) + _field(7, 5)))   # tf_op by ref
    plane += _field(4, _entry(9, metadata))
    plane += _field(4, _entry(10, _field(1, 10) + _field(2, "plain")
                              + _field(5, _field(1, 1) + _field(5, "a/b:"))))
    event = (_field(1, 9) + _field(2, 2_500_000) + _field(3, 1_250_000)
             + _field(4, _field(1, 2) + _field(3, 300))
             + _field(4, _field(1, 3) + _field(2, 0.5))
             + _field(4, _field(1, 4) + _field(5, "step_fn"))
             + _field(4, _field(1, 6) + _field(4, -3)))
    line = (_field(2, sp.OPS_LINE) + _field(3, 1_000)
            + _field(4, event) + _field(4, _field(1, 10) + _field(3, 1000)))
    plane += _field(3, line)
    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, _field(2, "/host:CPU")))
    first, second = sp.read_xspace(str(path))
    assert (first.name, second.name) == ("/device:TPU:0", "/host:CPU")
    (only,) = first.lines
    a, b = only.events
    assert only.name == sp.OPS_LINE
    assert (a.start_ns, a.end_ns) == (3_500.0, 4_750.0)    # ps over ns
    assert a.name.startswith("%exp.1") and a.op_name == "jit(f)/hvd_loss/exp:"
    assert a.stats == {"call": 300, "ratio": 0.5, "handle": "step_fn",
                       "offset": -3}
    assert (b.name, b.op_name, b.start_ns, b.end_ns) == (
        "plain", "a/b:", 1_000.0, 1_001.0)
    assert sp.reduce_file(str(tmp_path))["phases_s"]["loss"] == \
        pytest.approx(1.25e-6)


# ------------------------------------------- a trace recorded on the chip

RECORDED = os.path.join(DATA, "v5e_2x2_lm_three_steps.xplane.pb.gz")
# ``tools/profile_step.py --model transformer_lm --lm-layers 2 --lm-dim 128
# --lm-heads 4 --vocab 1024 --batch-size 4 --seq-len 128 --remat --steps 3``
# on the four chips of a v5e host (PR 26), buckets of 1 MiB scattered from
# 256 KiB. Hand-read: every chip's ``XLA Ops`` events listed with
# TensorFlow's generated protobuf classes (nothing of this package), 1,488 a
# chip and none inside another, their durations added up by the words in
# ``tf_op`` and by the operation in the instruction's text; seconds, means
# over the four chips.
HAND_READ = {
    "busy_s": 0.0006143581675, "window_s": 0.006211324375,
    "collective_s": 0.0003747069335,
    "collective_in_other_s": 0.0002847887305,
    "phases_s": {"forward": 5.14393745e-05, "backward": 8.337504e-05,
                 "recomputed": 1.00586e-07, "loss": 3.1195605e-05,
                 "exchange": 9.1317734e-05, "update": 3.05156445e-05,
                 "metrics": 0.0, "other": 0.0003264141835},
}


def test_the_reduction_reproduces_the_hand_read_numbers_of_a_recorded_trace():
    planes = sp.read_xspace(RECORDED)
    r = sp.reduce(planes)
    assert r["chips"] == 4 and r["steps"] == 3
    for key in ("busy_s", "window_s", "collective_s",
                "collective_in_other_s"):
        assert r[key] == pytest.approx(HAND_READ[key], rel=1e-9), key
    for phase, seconds in HAND_READ["phases_s"].items():
        assert r["phases_s"][phase] == pytest.approx(seconds, rel=1e-9,
                                                     abs=1e-15), phase
    # no collective ran beside other work here: all of it is exposed, and
    # XLA's own all-reduces (the scattered buckets' reduce-scatters come out
    # as all-reduces with no op_name) are the part counted under other
    assert r["collective_exposed_s"] == pytest.approx(r["collective_s"])
    assert set(r["collectives_s"]) == {"all-reduce", "all-gather"}
    assert r["collectives_s"]["all-reduce"] == pytest.approx(
        HAND_READ["collective_in_other_s"], rel=1e-9)
    # the host plane holds the program's spans with their arguments, on the
    # device's clock: both idle gaps lie under a dispatch
    spans = sp.host_spans(planes)
    assert [(s[2], s[3]) for s in spans] == [
        (timeline.DISPATCH, {"handle": "step_fn", "call": c})
        for c in (3, 4, 5)]
    assert r["gaps"] == 2
    assert list(r["idle_gaps_s"]) == [timeline.DISPATCH]
    assert r["idle_gaps_s"][timeline.DISPATCH] == pytest.approx(
        0.0055903125, rel=1e-6)


def test_the_recorded_trace_carries_the_scopes_in_its_op_names():
    names = {e.op_name for plane in sp.read_xspace(RECORDED)
             if plane.name == "/device:TPU:0"
             for line in plane.lines if line.name == sp.OPS_LINE
             for e in line.events}
    for scope in (timeline.FORWARD, timeline.LOSS, timeline.EXCHANGE,
                  timeline.UPDATE):
        assert any(scope in n for n in names), scope
    assert any(n.startswith("jit(step_fn)/shard_map/transpose(jvp("
                            "hvd_forward))/") for n in names)
    assert any("hvd_update/hvd_exchange/hvd_allreduce_grads_float32_b1/"
               "all_gather" in n for n in names)


@pytest.mark.parametrize("op_name, layer, phase", [
    ("jit(step_fn)/jvp(hvd_forward)/DecoderBlock_1/attn/hvd_attn_window/"
     "jit(flash_attention)/hvd_flash_fwd/pallas_call",
     "hvd_attn_window", "forward"),
    ("jit(step_fn)/transpose(jvp(hvd_forward))/DecoderBlock_4/attn/"
     "hvd_attn_full/hvd_flash_dkv/pallas_call", "hvd_attn_full", "backward"),
    ("jit(step_fn)/transpose(jvp(hvd_forward))/checkpoint/"
     "rematted_computation/DecoderBlock_2/moe/hvd_moe_experts/ragged_dot",
     "hvd_moe_experts", "recomputed"),
    ("jit(step_fn)/jvp(hvd_forward)/DecoderBlock_2/moe/hvd_moe_shared/"
     "shared/gate/dot_general", "hvd_moe_shared", "forward"),
    # a latent layer: the kernels, the compression, the expansion
    ("jit(step_fn)/transpose(jvp(hvd_forward))/DecoderBlock_5/attn/"
     "hvd_attn_latent/jit(flash_attention)/hvd_flash_dq/pallas_call",
     "hvd_attn_latent", "backward"),
    ("jit(step_fn)/jvp(hvd_forward)/DecoderBlock_0/attn/hvd_latent_compress/"
     "kv_a/dot_general", "hvd_latent_compress", "forward"),
    ("jit(step_fn)/transpose(jvp(hvd_forward))/checkpoint/"
     "rematted_computation/DecoderBlock_2/attn/hvd_latent_expand/kv_b/"
     "dot_general", "hvd_latent_expand", "recomputed"),
    ("jit(step_fn)/hvd_update/mul", None, "update"),
    ("jit(step_fn)/jvp(hvd_forward)/embed/take", None, "forward"),
    # a looped model: an application of the stack less its attention, the
    # exit gate in the forward pass and in the loss, the chunked exit loss
    ("jit(step_fn)/jvp(hvd_forward)/hvd_loop_step/DecoderBlock_3/mlp/up/"
     "dot_general", "hvd_loop_step", "forward"),
    ("jit(step_fn)/transpose(jvp(hvd_forward))/hvd_loop_step/checkpoint/"
     "rematted_computation/DecoderBlock_3/attn/hvd_attn_full/hvd_flash_fwd/"
     "pallas_call", "hvd_attn_full", "recomputed"),
    ("jit(step_fn)/jvp(hvd_forward)/hvd_exit_gate/exit_gate/dot_general",
     "hvd_exit_gate", "forward"),
    ("jit(step_fn)/jvp(hvd_loss)/hvd_exit_gate/cumsum", "hvd_exit_gate",
     "loss"),
    ("jit(step_fn)/transpose(jvp(hvd_loss))/hvd_exit_loss/while/body/"
     "dot_general", "hvd_exit_loss", "loss"),
])
def test_layer_scopes_split_the_forward_and_backward_passes(op_name, layer,
                                                            phase):
    assert sp.layer_of(op_name) == layer
    assert sp.phase_of(op_name) == phase
