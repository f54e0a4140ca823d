"""What the host did in a late step (``horovod_tpu/utils/timeline.py``, "the
host's clock"): the step clock on an ``hvd.spmd_fn`` handle's dispatch
records, the collections of the heap on the profile's clock, the stall record
and its ``cause``, the warning, and the benchmark's readers of all of it.

The pace, the lateness rule, ``pause``, ``reset`` and the warning's limit are
driven on a made-up clock (a real dispatch span whose start the test sets), so
that a loaded machine cannot make a step late; the causes that are read off
the thread itself (``waiting``, ``python``, ``gc``, ``compile``) are driven in
real time through a real handle, and asserted on the step the test made
late. Every test has a time limit of its own (``limit``)."""

import gc
import logging
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.utils import timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

STEP_NS = 50_000_000        # the made-up clock's step: 50 ms
NEW_READERS = ("hvd_step_period_ms_max.tok", "hvd_dispatch_ms_max.tok",
               "host_stall_pct.tok", "host_gc_ms_per_step.tok",
               "setup_init_s", "setup_before_init_s")


@pytest.fixture(autouse=True)
def limit():
    """A minute a test: the alarm raises in the test's own thread."""
    def over(signum, frame):
        raise TimeoutError("the test passed its time limit of 60 s")

    before = signal.signal(signal.SIGALRM, over)
    signal.alarm(60)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


@pytest.fixture
def fresh(hvd):
    timeline.reset()
    yield
    timeline.reset()


def _records(name, call=None):
    return [s for s in timeline.snapshot()["spans"] if s["name"] == name
            and (call is None or s["args"]["call"] == call)]


class MadeUpLoop:
    """A handle's clock ticked by real dispatch spans whose starts the test
    sets: ``step(after_ns)`` is one dispatch that long after the last."""

    def __init__(self, name="made_up"):
        self.clock = timeline.StepClock(name, name + "#0")
        self.now = time.time_ns()
        self.calls = 0

    def step(self, after_ns=STEP_NS):
        self.now += after_ns
        with timeline.span(timeline.DISPATCH, handle=self.clock.handle,
                           program=self.clock.program, call=self.calls) as sp:
            sp._start = self.now
            self.clock.tick(sp, self.calls == 0)
        self.calls += 1
        return self.calls - 1

    def steps(self, n, after_ns=STEP_NS):
        for _ in range(n):
            self.step(after_ns)


def test_an_even_pace_arms_the_handle_and_records_no_stall(fresh):
    loop = MadeUpLoop()
    loop.steps(1 + timeline.ARM_PERIODS - 1)
    assert loop.clock.median is None            # one period short
    loop.steps(40)
    assert loop.clock.median == pytest.approx(STEP_NS / 1e6)
    # late by less than max(20 ms, 10%) of 50 ms, and early: neither a stall
    loop.step(STEP_NS + 19_000_000)
    loop.step(STEP_NS // 10)
    assert _records(timeline.STALL) == []
    assert timeline.snapshot()["counters"]["hvd.host.stalls"] == 0


def test_the_dispatch_record_carries_the_step_clock_from_call_1(fresh):
    loop = MadeUpLoop()
    loop.steps(3)
    first, second, third = _records(timeline.DISPATCH)
    assert "period_ms" not in first["args"]     # call 0: nothing before it
    for record in (second, third):
        args = record["args"]
        assert args["period_ms"] == pytest.approx(STEP_NS / 1e6)
        assert args["cpu_ms"] >= 0 and args["cpu_ms"] < 1e3
        assert {"vol", "invol", "majflt"} <= set(args)
        assert all(args[k] >= 0 for k in ("vol", "invol", "majflt"))
    if os.path.exists("/proc/thread-self/schedstat"):
        assert third["args"]["runq_ms"] >= 0


def test_a_late_step_on_the_made_up_clock_is_one_stall_record(fresh):
    loop = MadeUpLoop()
    loop.steps(12)
    call = loop.step(STEP_NS + 100_000_000)
    (stall,) = _records(timeline.STALL)
    (closing,) = _records(timeline.DISPATCH, call)
    last = _records(timeline.DISPATCH, call - 1)[0]
    assert (stall["start_ns"], stall["end_ns"]) == (
        last["start_ns"], closing["start_ns"])
    assert stall["parent"] == closing["id"]
    args = stall["args"]
    assert (args["handle"], args["program"], args["call"]) == (
        "made_up", "made_up#0", call)
    assert args["late_ms"] == pytest.approx(100.0)
    assert args["median_ms"] == pytest.approx(50.0)
    assert args["period_ms"] == pytest.approx(150.0)
    # no real time passed: the thread neither ran nor waited for a core,
    # nothing was collected or compiled, the last span had closed
    assert args["cause"] == "waiting"
    assert args["inside_dispatch"] is False
    assert args["gc_ms"] == 0 and args["compile_s"] == 0
    counters = timeline.snapshot()["counters"]
    assert counters["hvd.host.stalls"] == 1
    assert counters["hvd.host.stall_s"] == pytest.approx(0.1)
    # the late period stays out of the pace, and the next step is on time
    loop.steps(3)
    assert loop.clock.median == pytest.approx(50.0)
    assert len(_records(timeline.STALL)) == 1


def test_a_long_pause_is_recorded_as_pause_and_disarms(fresh):
    loop = MadeUpLoop()
    loop.steps(12)
    loop.step(30 * STEP_NS)
    (stall,) = _records(timeline.STALL)
    assert stall["args"]["cause"] == "pause"
    assert loop.clock.median is None and loop.clock.deadline_ns is None
    counters = timeline.snapshot()["counters"]
    assert counters["hvd.host.stalls"] == 0 and counters["hvd.host.stall_s"] == 0
    # until the pace is learnt again nothing is late
    loop.steps(timeline.ARM_PERIODS - 1)
    loop.step(STEP_NS + 200_000_000)
    assert len(_records(timeline.STALL)) == 1


def test_an_irregular_handle_is_never_armed(fresh):
    loop = MadeUpLoop()
    for i in range(60):
        loop.step(STEP_NS * (1 + i % 3))        # 50, 100, 150 ms in turn
        assert loop.clock.median is None
    loop.step(40 * STEP_NS)
    assert _records(timeline.STALL) == []


def test_a_new_pace_is_learnt_after_as_many_periods_off_the_old(fresh):
    loop = MadeUpLoop()
    loop.steps(12)
    loop.steps(timeline.ARM_PERIODS, 2 * STEP_NS)   # every one late: recorded
    assert len(_records(timeline.STALL)) == timeline.ARM_PERIODS
    assert loop.clock.median is None
    loop.steps(timeline.ARM_PERIODS, 2 * STEP_NS)
    assert loop.clock.median == pytest.approx(100.0)
    loop.steps(5, 2 * STEP_NS)
    assert len(_records(timeline.STALL)) == timeline.ARM_PERIODS


def test_the_warning_is_logged_once_a_stall_and_stops_at_20(fresh, caplog):
    loop = MadeUpLoop()
    loop.steps(12)
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        for n in range(1, timeline.WARNINGS + 6):
            loop.step(STEP_NS + 100_000_000)
            loop.steps(2)
            lines = [r for r in caplog.records
                     if r.getMessage().startswith("hvd.host.stall")]
            assert len(lines) == min(n, timeline.WARNINGS)
    assert len(_records(timeline.STALL)) == timeline.WARNINGS + 5
    text = lines[0].getMessage()
    assert "100.0 ms late" in text and "cause waiting" in text
    assert "made_up#0" in text
    assert "the last" in lines[-1].getMessage()
    assert "the last" not in lines[-2].getMessage()
    # a long pause is no stall of a step: recorded, not logged
    caplog.clear()
    timeline.reset()
    loop.steps(12)
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        loop.step(30 * STEP_NS)
    assert not caplog.records


def test_reset_forgets_the_detectors_state(fresh):
    loop = MadeUpLoop()
    loop.steps(12)
    loop.step(STEP_NS + 100_000_000)
    assert loop.clock.median is not None
    timeline.reset()
    assert loop.clock.median is None and loop.clock.deadline_ns is None
    snap = timeline.snapshot()
    assert snap["counters"]["hvd.host.stalls"] == 0
    assert [s for s in snap["spans"] if s["name"] == timeline.STALL] == []
    # the first dispatch after it has nothing to take a period from
    call = loop.step(STEP_NS + 100_000_000)
    assert "period_ms" not in _records(timeline.DISPATCH, call)[0]["args"]
    assert _records(timeline.STALL) == []


# ------------------------------------------------ in real time, a real handle

PERIOD_S = 0.1


class RealLoop:
    """A real handle dispatched ``PERIOD_S`` apart. ``arm`` drives it until
    its clock has learnt the pace, however long a loaded machine takes."""

    def __init__(self, hvd, name):
        def step(x):
            return hvd.allreduce(x, name=name)

        step.__name__ = name
        self.run = hvd.spmd_fn(step, in_specs=P("hvd"), out_specs=P("hvd"))
        self.x = jnp.ones((8, 4), jnp.float32)
        (self.clock,) = [c for c in timeline._clocks if c.handle == name]
        self.calls = 0

    def step(self, x=None):
        jax.block_until_ready(self.run(self.x if x is None else x))
        self.calls += 1
        time.sleep(PERIOD_S)
        return self.calls - 1

    def steps(self, n):
        for _ in range(n):
            self.step()

    def arm(self):
        while self.clock.median is None and self.calls < 300:
            self.step()
        assert self.clock.median is not None, "the handle never armed"

    def late_by(self, late, more=4):
        """Arm, run ``late()`` before the next dispatch, go on for ``more``;
        returns the call that came late."""
        self.arm()
        late()
        at = self.step()
        self.steps(more)
        return at


def _the_stall(call):
    stalls = _records(timeline.STALL, call)
    assert len(stalls) == 1, timeline.snapshot()["spans"][-5:]
    return stalls[0]["args"]


def _sleeps():
    time.sleep(0.3)         # the line a sample of the thread names


def test_a_sleep_between_two_dispatches_is_a_stall_that_waited(fresh, hvd):
    stall = _the_stall(RealLoop(hvd, "sleeps").late_by(_sleeps))
    assert stall["cause"] == "waiting"
    assert stall["late_ms"] == pytest.approx(300, rel=0.2)
    assert stall["samples"] >= 3
    line = _sleeps.__code__.co_firstlineno + 1
    assert stall["where"] == f"{__file__}:{line} _sleeps"
    assert stall["stack"][0] == stall["where"]
    assert 2 <= len(stall["stack"]) <= timeline.STACK_FRAMES
    assert any("test_a_sleep_between" in frame for frame in stall["stack"])
    assert stall["inside_dispatch"] is False
    assert stall["cpu_ms"] < 100


def test_a_busy_loop_between_two_dispatches_is_python(fresh, hvd):
    def spins():
        until = time.perf_counter() + 0.3
        while time.perf_counter() < until:
            pass

    stall = _the_stall(RealLoop(hvd, "spins").late_by(spins))
    # on a machine with more runnable threads than cores the loop itself
    # waits for a core, and where it did so for half of the late time the
    # record rightly says that instead
    starved = 2 * stall.get("runq_ms", 0.0) >= stall["late_ms"]
    assert stall["cause"] == ("off_cpu" if starved else "python")
    assert starved or stall["cpu_ms"] > 150
    assert "spins" in stall["where"]


def test_a_collection_of_a_large_heap_is_gc_and_has_its_record(fresh, hvd):
    gc.disable()
    try:
        heap = []
        for _ in range(500_000):        # a million lists, in cycles of two
            a = []
            a.append([a])
            heap.append(a)
    finally:
        gc.enable()
    gc.collect()                        # the heap is old before the loop runs
    timeline.reset()

    def collects():
        heap.clear()
        gc.collect()

    stall = _the_stall(RealLoop(hvd, "collects").late_by(collects))
    assert stall["cause"] == "gc"
    assert stall["gc_ms"] >= stall["late_ms"] / 2
    full = [s for s in _records(timeline.GC)
            if s["args"]["generation"] == 2
            and s["args"]["collected"] >= 999_000]
    assert len(full) == 1
    assert 1e-6 * (full[0]["end_ns"] - full[0]["start_ns"]) \
        == pytest.approx(stall["gc_ms"], rel=0.5)
    counters = timeline.snapshot()["counters"]
    assert counters["hvd.host.gc_collections"] >= 1
    assert counters["hvd.host.gc_s"] >= stall["gc_ms"] / 1e3


def test_a_retrace_makes_the_next_dispatch_late_by_compile(fresh, hvd):
    loop = RealLoop(hvd, "retraces")
    loop.arm()
    anew = loop.step(jnp.ones((8, 6), jnp.float32))   # traces and compiles
    loop.steps(4)
    assert _records(timeline.DISPATCH, anew)[0]["args"]["programs"] >= 1
    stall = _the_stall(anew + 1)
    assert stall["cause"] == "compile"
    assert stall["compile_s"] > 0


# ---------------------------------------------------- the benchmark's readers

def _readers():
    from benchmarks import run

    return {name: run.load_reader(name) for name in NEW_READERS}


def test_the_readers_read_a_driven_window_and_nothing_without_the_detector(
        fresh, hvd, monkeypatch):
    from benchmarks.metrics import program_spans

    loop, steps = RealLoop(hvd, "windowed"), 21
    loop.arm()
    first = loop.calls              # set-up's calls, then the window's
    loop.steps(10)
    late_call = loop.late_by(lambda: time.sleep(0.3), more=steps - 11)
    record = {"cell": {"compare_steps": first},
              "window": {"steps": steps, "seconds": 2.0}}
    snap = timeline.snapshot()
    init = {"id": 0, "parent": 0, "name": "hvd.init", "args": {},
            "start_ns": 5_000_000_000, "end_ns": 6_500_000_000}
    snap["spans"].append(init)
    snap["gauges"]["hvd.init.process_age_s"] = {"": 12.5}
    monkeypatch.setattr(program_spans, "snapshot", lambda: snap)
    read = {name: reader(record) for name, reader in _readers().items()}
    late_ms = _the_stall(late_call)["late_ms"]
    window_stalls = [s["args"]["late_ms"] for s in _records(timeline.STALL)
                     if first < s["args"]["call"] < first + steps]
    assert read["host_stall_pct.tok"] == pytest.approx(
        100 * sum(window_stalls) / 1e3 / 2.0)
    assert read["host_stall_pct.tok"] >= 100 * late_ms / 1e3 / 2.0 > 5
    periods = [s["args"]["period_ms"] for s in _records(timeline.DISPATCH)
               if first < s["args"]["call"] < first + steps]
    assert read["hvd_step_period_ms_max.tok"] == max(periods) >= 300
    assert read["hvd_dispatch_ms_max.tok"] > 0
    assert read["host_gc_ms_per_step.tok"] >= 0
    assert read["setup_init_s"] == 1.5
    assert read["setup_before_init_s"] == 12.5
    # a window that the ring no longer holds whole: nothing
    longer = dict(record, window={"steps": steps + 5, "seconds": 2.0})
    assert _readers()["host_stall_pct.tok"](longer) is None
    # the parent's snapshot: dispatch spans without the clock, no counter, no
    # gauge (and here no ``hvd.init`` in the ring): every reader is silent
    bare = dict(snap, gauges={}, counters={
        k: v for k, v in snap["counters"].items()
        if not k.startswith("hvd.host.")})
    bare["spans"] = [
        dict(s, args={k: v for k, v in s["args"].items()
                      if k in ("handle", "program", "call")})
        for s in snap["spans"] if s["name"] == timeline.DISPATCH]
    monkeypatch.setattr(program_spans, "snapshot", lambda: bare)
    assert {name: reader(record) for name, reader in _readers().items()} \
        == dict.fromkeys(NEW_READERS)
    monkeypatch.setattr(program_spans, "snapshot", lambda: None)
    assert {name: reader(record) for name, reader in _readers().items()} \
        == dict.fromkeys(NEW_READERS)


def test_a_window_without_a_stall_reads_zero(fresh, monkeypatch):
    from benchmarks.metrics import program_spans

    loop = MadeUpLoop()
    loop.steps(3 + 20)
    record = {"cell": {"compare_steps": 3},
              "window": {"steps": 20, "seconds": 1.0}}
    read = {name: reader(record) for name, reader in _readers().items()}
    assert read["host_stall_pct.tok"] == 0.0
    assert read["hvd_step_period_ms_max.tok"] == pytest.approx(50.0)
    assert read["host_gc_ms_per_step.tok"] >= 0.0
    # a pause inside the window is recorded and not counted
    loop.steps(3 + 8)
    loop.step(30 * STEP_NS)
    loop.steps(11)
    record = {"cell": {"compare_steps": 3 + 20},
              "window": {"steps": 20, "seconds": 1.0}}
    assert len(_records(timeline.STALL)) == 1
    assert _readers()["host_stall_pct.tok"](record) == 0.0


def test_the_manifest_lists_the_new_metrics_and_is_well_formed():
    import json

    from benchmarks import check_manifest

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert check_manifest.check(manifest, REPO) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cells = [c["name"] for c in manifest["workloads"]]
    for stem in ("hvd_step_period_ms_max", "hvd_dispatch_ms_max",
                 "host_stall_pct", "host_gc_ms_per_step"):
        img, tok = by_name[stem + ".img"], by_name[stem + ".tok"]
        assert img["moves"] == "img_per_s_per_chip"
        assert tok["moves"] == "tok_per_s_per_chip"
        assert img["workloads"] == ["resnet50_bs128_1chip"]
        assert tok["workloads"] == [c for c in cells if c not in img["workloads"]]
        assert {img["layer"], tok["layer"]} == {"spmd_harness"}
        assert {img["better"], tok["better"]} == {"lower"}
    for name in ("setup_init_s", "setup_before_init_s"):
        assert by_name[name]["moves"] == "setup_s"
        assert by_name[name]["workloads"] == cells
    # the ten new entries stand where they were added, at what was then the
    # end of the list (one entry of PR 37 has followed them)
    names = [m["name"] for m in manifest["per_layer"]]
    start = names.index("hvd_step_period_ms_max.img")
    assert names[start:start + 10] == [
        "hvd_step_period_ms_max.img", "hvd_step_period_ms_max.tok",
        "hvd_dispatch_ms_max.img", "hvd_dispatch_ms_max.tok",
        "host_stall_pct.img", "host_stall_pct.tok",
        "host_gc_ms_per_step.img", "host_gc_ms_per_step.tok",
        "setup_init_s", "setup_before_init_s"]


def test_hvd_init_says_how_old_the_process_was(hvd):
    from horovod_tpu.common import basics

    age = basics._process_age_s()
    if not os.path.exists("/proc/self/stat"):
        assert age is None
        return
    assert 0 < age < 24 * 3600
    time.sleep(0.05)
    assert basics._process_age_s() >= age + 0.03
