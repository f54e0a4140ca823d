"""``trace_reduce.py``: its interval arithmetic on hand-made events, and the
whole reduction on the two small traces recorded on the chip: one chip, and
the four chips of a 2x2 host exchanging what a data-parallel step does."""

import os

import pytest

from benchmarks import trace_reduce

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_union_counts_overlap_once():
    total, merged = trace_reduce.union_ns([(0, 10), (5, 20), (30, 40),
                                           (32, 35)])
    assert total == 30 and merged == [[0, 20], [30, 40]]


def test_self_time_gives_a_loop_its_time_less_its_body():
    events = [(0, 100, "while"), (10, 40, "fusion.1"), (50, 90, "fusion.2"),
              (60, 70, "inner"), (200, 210, "fusion.1")]
    got = trace_reduce.self_times(events)
    assert got == {"while": 30, "fusion.1": 40, "fusion.2": 30, "inner": 10}
    assert sum(got.values()) == trace_reduce.union_ns(
        (s, e) for s, e, _ in events)[0]


def test_a_gap_goes_to_the_innermost_span_at_its_middle():
    spans = sorted([(0, 100, "in run_step"), (40, 60, "waiting for a loss"),
                    (200, 300, "in run_step")])
    got = trace_reduce._attribute([(45, 55), (10, 20), (120, 180)], spans)
    assert got == {"waiting for a loss": 10, "in run_step": 10,
                   trace_reduce.BETWEEN: 60}


@pytest.mark.parametrize("name, is_collective", [
    ("%psum_invariant.14 = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} all-reduce("
     "bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} %fusion), channel_id=1", True),
    ("%reduce_scatter.7 = bf16[256,1024]{1,0:T(8,128)(2,1)S(1)} "
     "reduce-scatter(bf16[1024,1024]{1,0} %x), channel_id=1", True),
    ("%ag = (bf16[8]{0}, bf16[32]{0}) all-gather-start(bf16[8]{0} %p)", True),
    # an operand that is a collective does not make its user one
    ("%convert_reduce_fusion = (f32[]{:T(128)}, bf16[1024,1024]{1,0}) fusion("
     "bf16[1024,1024]{1,0} %all-gather.4), kind=kLoop", False),
    ("%all-reduce.3", True), ("barrier-cores", False),
])
def test_a_collective_is_known_by_its_operation_not_its_name(name,
                                                             is_collective):
    assert bool(trace_reduce.COLLECTIVE.match(
        trace_reduce.operation(name))) is is_collective


def test_a_trace_with_no_chip_in_it_gives_nothing():
    class Empty:
        planes = []
    assert trace_reduce.reduce(Empty(), 1) is None


@pytest.fixture(scope="module")
def recorded():
    import jax.profiler

    path = os.path.join(TESTDATA, "v5e_six_steps.xplane.pb")
    return jax.profiler.ProfileData.from_file(path)


def test_the_recorded_trace_reduces_to_what_was_read_by_hand(recorded):
    import json

    want = json.load(open(os.path.join(TESTDATA, "v5e_six_steps.json")))
    got = trace_reduce.reduce(recorded, 1)
    assert got is not None
    for key in ("busy_s", "window_s", "collective_s", "gaps"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["device_ops"][0][0] == want["top_op"]
    # the pause planted between two calls is the longest gap, and the host
    # was between calls in it
    assert got["longest_gap_s"] >= 0.015
    assert got["idle_gaps"][0][0] == trace_reduce.BETWEEN


def test_the_four_chip_trace_gives_the_collectives_read_by_hand():
    import json

    import jax.profiler

    want = json.load(open(os.path.join(TESTDATA, "v5e_2x2_six_steps.json")))
    got = trace_reduce.reduce(jax.profiler.ProfileData.from_file(
        os.path.join(TESTDATA, "v5e_2x2_six_steps.xplane.pb")), 4)
    for key in ("chips", "busy_s", "window_s", "collective_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["device_ops"][0][0] == want["top_op"]
    assert 0 < got["collective_s"] < got["busy_s"]
