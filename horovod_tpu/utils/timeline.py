"""Spans, counters and the Chrome-tracing timeline: the program's one
tracing module.

**Spans and counters** (always on, no switch):

* ``span(name, **args)`` is a context manager. It opens a
  ``jax.profiler.TraceAnnotation``, so whenever a ``jax.profiler`` session
  is running the span lies on the profile's host plane, on the clock the
  device's operations are on (with no session the annotation is a flag
  test); it appends one record ``(id, parent, name, start_ns, end_ns, args)``
  to a ring of the newest ``RING`` records (parent: the span open on this
  thread; the oldest record is dropped and the drop counted); and, where
  ``HOROVOD_TIMELINE`` is set, it forwards to the Chrome writer below. A
  span under which anything compiled says so in its record: ``programs``
  and ``compile_s`` join its arguments.
* ``count(name, n)`` adds to, and ``gauge(name, value, key)`` sets, a
  process-wide number.
* ``install_compile_listener()`` (``hvd.init`` calls it) registers one
  ``jax.monitoring`` listener that records every jaxpr trace, lowering and
  backend compile-or-cache-load as a child record of the span open on the
  thread that caused it (``hvd.compile.trace`` / ``.lower`` / ``.backend``,
  the last with ``cache_hit`` where the persistent cache served it) and
  counts ``hvd.compile.programs``, ``hvd.compile.seconds`` and
  ``hvd.compile.cache_hits``. Of a jit traced inside another only the
  outer trace is kept, so the seconds add up. These records have a ring of
  their own (``RING`` too, its drops counted apart): an eager phase that
  compiles hundreds of small programs pushes out older compile records,
  never a span.
* **What the host did in a late step** (always on too; installed with the
  compile listener). Every ``hvd.spmd_fn`` handle has a :class:`StepClock`:
  its dispatch records carry the host's step period and what the thread
  spent over it (``period_ms``, ``cpu_ms``, ``runq_ms``, ``vol``,
  ``invol``, ``majflt``). One ``gc.callbacks`` entry puts every collection of the
  Python heap on the profile's clock (``hvd.host.gc``). One daemon thread
  sleeps until the armed handle's next dispatch is overdue and then samples
  where the dispatching thread stands; the dispatch that ends the wait
  writes one record ``hvd.host.stall`` that names a ``cause``, and one
  WARNING line. The rule and its constants are below, under "the host's
  clock"; docs/timeline.md, "A late step", reads one.
* ``snapshot()`` returns all of it as plain data, ``dump(path)`` writes
  that as JSON (when asked, never on the hot path), ``reset()`` forgets.

Names in use are listed in docs/timeline.md. The reduction of a device
profile by these names is :mod:`horovod_tpu.utils.step_profile`.

**The Chrome writer** is the TPU-native rebuild of the reference Horovod
Timeline (horovod/common/timeline.{h,cc}; semantics documented in the
reference's docs/timeline.md:17-62), and an exporter of the spans above:

* activated by ``HOROVOD_TIMELINE=/path/trace.json``; rank-0 writes
  (reference operations.cc:1824-1829);
* per-tensor state machine NEGOTIATING -> TOP_LEVEL -> ACTIVITY
  (reference timeline.h:75-121);
* records never block the hot path: they are pushed onto a queue drained by
  a background writer thread (reference timeline.h:45-73 used a boost
  lock-free SPSC queue + writer thread; Python's ``SimpleQueue`` is the
  equivalent lock-free-enough primitive here — a C++ writer lives in
  csrc/timeline.cc for the native core);
* activity taxonomy kept from reference operations.h:29-50 with XLA-flavored
  additions. Its clock is its own (``time.monotonic_ns`` from its start):
  to lay host spans beside device time, use a profiler session.

The Chrome trace format is the "JSON Array Format": one event object per
line, comma-separated, '[' prologue — loadable in chrome://tracing and
Perfetto even when truncated mid-run (same property the reference relied on).
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import logging
import os
import queue
import resource
import statistics
import sys
import threading
import time
import weakref
from typing import Optional

# Activity names (reference horovod/common/operations.h:29-50).
MEMCPY_IN_FUSION_BUFFER = "MEMCPY_IN_FUSION_BUFFER"
MEMCPY_OUT_FUSION_BUFFER = "MEMCPY_OUT_FUSION_BUFFER"
ALLREDUCE = "ALLREDUCE"
ALLGATHER = "ALLGATHER"
BROADCAST = "BROADCAST"
ALLTOALL = "ALLTOALL"
# Bucket reductions (horovod_tpu/jax/fusion.py): the hierarchical ladder
# splits a bucket's allreduce into REDUCESCATTER, the DCN exchange and
# ALLGATHER, each its own activity under the bucket's ALLREDUCE span,
# which under overlap opens at collective ISSUE and closes at UNPACK so
# the trace shows every in-flight bucket.
REDUCESCATTER = "REDUCESCATTER"
# XLA-path additions: what the span ``hvd.spmd.dispatch`` is exported as.
XLA_COMPILE = "XLA_COMPILE"
XLA_EXECUTE = "XLA_EXECUTE"
# Multi-step window activities (horovod_tpu/jax/window.py): WINDOW spans
# the ONE host dispatch of a K-step scanned window; WINDOW_SYNC spans the
# boundary block_until_ready + d2h pull, so a trace attributes host time
# to dispatch vs sync even when K steps share one program.
WINDOW = "WINDOW"
WINDOW_SYNC = "WINDOW_SYNC"

_NEGOTIATING = "NEGOTIATING"
_TOP_LEVEL = "TOP_LEVEL"

# ---------------------------------------------------------------- spans

DISPATCH = "hvd.spmd.dispatch"
# Device scopes (``jax.named_scope``: compile-time only): the phases of a
# training step, as they stand in every operation's ``op_name`` in a device
# profile. The backward pass needs none: JAX writes it as
# ``transpose(jvp(hvd_forward))``, and recomputation as ``checkpoint`` /
# ``rematted_computation``.
FORWARD = "hvd_forward"     # model.apply inside the loss function
LOSS = "hvd_loss"           # logits to scalar
EXCHANGE = "hvd_exchange"   # fused_reduce of the gradients
UPDATE = "hvd_update"       # the inner optimizer's update, apply_updates
METRICS = "hvd_metrics"     # accuracy, the loss all-reduce, the read-out
# Layers inside ``hvd_forward`` (and so inside its backward): attention by
# the layer's type, the parts of a sparse expert layer, and a looped model's
# applications of its stack and exit gate; ``hvd_exit_loss`` lies inside
# ``hvd_loss``.
ATTN_WINDOW = "hvd_attn_window"     # attention of a sliding-window layer
ATTN_FULL = "hvd_attn_full"         # attention of a full (causal) layer
ATTN_LATENT = "hvd_attn_latent"     # attention of a latent layer
LATENT_COMPRESS = "hvd_latent_compress"     # x W_kva and the row's norm
LATENT_EXPAND = "hvd_latent_expand"         # c W_kvb, the rope key rotated
MOE_ROUTE = "hvd_moe_route"         # scores, top k, weights, counts
MOE_DISPATCH = "hvd_moe_dispatch"   # sort by expert, gather the rows
MOE_EXPERTS = "hvd_moe_experts"     # the grouped matrix products
MOE_COMBINE = "hvd_moe_combine"     # rows back to tokens, weighted sum
MOE_SHARED = "hvd_moe_shared"       # the shared expert every token passes
LOOP_STEP = "hvd_loop_step"     # one application of a looped model's stack
EXIT_GATE = "hvd_exit_gate"     # the exit gate, and the exit distribution
EXIT_LOSS = "hvd_exit_loss"     # every exit's per-token loss, in chunks
SSM_MIXER = "hvd_ssm_mixer"     # a Mamba-2 mixer, projections to projection
SSD = "hvd_ssd"                 # its chunked scan (ops/ssd.py's kernels)
LAYER_SCOPES = (ATTN_WINDOW, ATTN_FULL, ATTN_LATENT, LATENT_COMPRESS,
                LATENT_EXPAND, MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS,
                MOE_COMBINE, MOE_SHARED, LOOP_STEP, EXIT_GATE, EXIT_LOSS,
                SSM_MIXER, SSD)
RING = 8192     # records kept: a 10 s window of 46 ms steps is 217 of them

_lock = threading.Lock()
_ring: "collections.deque" = collections.deque(maxlen=RING)
_compiles: "collections.deque" = collections.deque(maxlen=RING)
_gcs: "collections.deque" = collections.deque(maxlen=RING)
_ids = itertools.count(1)
# what each ring let go
_dropped = {"dropped": 0, "dropped_compiles": 0, "dropped_gcs": 0}
_counters: dict = {}
_gauges: dict = {}
_local = threading.local()      # .open: the spans open on this thread
_listening = False
_TraceAnnotation = None         # jax.profiler's, imported at the first span

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "hvd.compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "hvd.compile.lower",
    "/jax/core/compile/backend_compile_duration": "hvd.compile.backend",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _open_spans() -> list:
    try:
        return _local.open
    except AttributeError:
        _local.open = []
        return _local.open


def _append(ring, record: tuple, drops: str) -> None:
    with _lock:
        if len(ring) == RING:
            _dropped[drops] += 1
        ring.append(record)


def _chrome_writer():
    from horovod_tpu.common.state import global_state

    tl = global_state().timeline
    return tl if tl is not None and tl.enabled else None


class span:
    """``with span("hvd.lane.build", model="resnet50"): ...`` (module
    docstring). ``id`` and ``args`` can be read while it is open, and
    ``end_ns`` is ``None`` until it has closed."""

    __slots__ = ("name", "args", "id", "parent", "_start", "end_ns",
                 "_annotation", "_exported", "_programs", "_compile_s")

    def __init__(self, name: str, **args):
        self.name, self.args = name, args
        self._programs, self._compile_s = 0, 0.0
        self.end_ns = None

    def __enter__(self):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            import jax.profiler

            _TraceAnnotation = jax.profiler.TraceAnnotation
        _flush_traces()             # what was traced before is not ours
        stack = _open_spans()
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else 0
        stack.append(self)
        tl = _chrome_writer()
        self._exported = (tl, *tl.span_start(self)) if tl else None
        self._annotation = _TraceAnnotation(self.name, **self.args)
        self._annotation.__enter__()
        self._start = time.time_ns()
        return self

    def __exit__(self, *exc):
        end = self.end_ns = time.time_ns()
        self._annotation.__exit__(*exc)
        if self._exported is not None:
            tl, track, op = self._exported
            tl.end(track, op)       # a no-op once the writer is closed
        _flush_traces()
        stack = _open_spans()
        if stack and stack[-1] is self:
            stack.pop()
        args = self.args
        if self._programs or self._compile_s:
            args = dict(args, programs=self._programs,
                        compile_s=self._compile_s)
        _append(_ring, (self.id, self.parent, self.name, self._start, end,
                        args), "dropped")
        return False


def enclosing(name: str) -> Optional[span]:
    """The innermost span called ``name`` that is open on this thread."""
    for open_span in reversed(_open_spans()):
        if open_span.name == name:
            return open_span
    return None


def tracing_program():
    """``(program, id)`` of the ``hvd.spmd.dispatch`` span whose call is
    tracing the code that asks: the key of a gauge that describes a compiled
    program, and what tells a re-trace from more of the same trace.
    ``("", None)`` outside any dispatch."""
    tracing = enclosing(DISPATCH)
    if tracing is None:
        return "", None
    return tracing.args.get("program", ""), tracing.id


def program_tally(store: dict, fresh):
    """``(program, tally)`` of the program being traced: what its trace has
    gathered in ``store`` so far for the gauges that describe it, begun anew
    with ``fresh()`` by a re-trace (another dispatch span) and outside any
    dispatch."""
    program, owner = tracing_program()
    held = store.get(program)
    if not (held and owner is not None and held[0] == owner):
        held = store[program] = (owner, fresh())
    return program, held[1]


def count(name: str, n=1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def gauge(name: str, value, key: str = "") -> None:
    """Set the number ``name``; ``key`` tells one program's from another's
    (the gradient exchange's plan is a gauge a compiled program)."""
    with _lock:
        _gauges.setdefault(name, {})[key] = value


def _compiled(record: tuple, programs: int = 0) -> None:
    """Keep one compile record and credit it to every span open on this
    thread."""
    seconds = (record[4] - record[3]) / 1e9
    count("hvd.compile.seconds", seconds)
    for open_span in _open_spans():
        open_span._programs += programs
        open_span._compile_s += seconds
    _append(_compiles, record, "dropped_compiles")


def _flush_traces() -> None:
    """Hand over this thread's finished traces. They wait because JAX
    reports a jit traced inside another before the outer one, which then
    takes its place: only the outermost is kept and counted."""
    waiting = getattr(_local, "traces", None)
    if waiting:
        for record in waiting:
            _compiled(record)
        waiting.clear()


def _on_duration(event: str, duration: float, **_):
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    end = time.time_ns()
    start = end - int(duration * 1e9)
    stack = _open_spans()
    parent = stack[-1].id if stack else 0
    if name == "hvd.compile.trace":
        waiting = getattr(_local, "traces", None)
        if waiting is None:
            waiting = _local.traces = []
        while waiting and waiting[-1][3] >= start:
            waiting.pop()                   # traced inside this one
        waiting.append((next(_ids), parent, name, start, end, {}))
        return
    _flush_traces()
    args = {}
    if name == "hvd.compile.backend":
        count("hvd.compile.programs")
        if getattr(_local, "cache_hit", False):
            _local.cache_hit = False
            args["cache_hit"] = True
    _compiled((next(_ids), parent, name, start, end, args),
              programs=name == "hvd.compile.backend")


def _on_event(event: str, **_):
    if event == _CACHE_HIT_EVENT:
        count("hvd.compile.cache_hits")
        _local.cache_hit = True     # the backend record that follows


def install_compile_listener() -> None:
    """Once a process; ``jax.monitoring`` has no way to take one back. The
    host's clock (below) is installed with it: the collections' callback,
    and the stall counters at 0, which is how a reader tells "no stall" from
    "no detector"."""
    global _listening, _TraceAnnotation
    with _lock:
        if _listening:
            return
        _listening = True
        _counters.update(dict.fromkeys(_HOST_COUNTERS, 0))
    import jax.monitoring
    import jax.profiler

    _TraceAnnotation = jax.profiler.TraceAnnotation
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    gc.callbacks.append(_on_gc)


def snapshot() -> dict:
    """``{"spans": [{"id", "parent", "name", "start_ns", "end_ns", "args"}],
    "dropped", "dropped_compiles", "dropped_gcs", "counters": {name: n},
    "gauges": {name: {key: value}}}``: closed spans, compile records and
    the collections' records by their end; ``parent`` 0 is none; the three
    ``dropped`` count what the span ring, the compile records' ring and the
    collections' ring let go."""
    with _lock:
        # the collections' ring is written with no lock held (``_on_gc``):
        # copied whole before anything walks it
        records = sorted(itertools.chain(_ring, _compiles, list(_gcs)),
                         key=lambda r: r[4])
        counters = dict(_counters)
        if _listening:
            counters["hvd.host.gc_collections"] = _gc_seen[0]
            counters["hvd.host.gc_s"] = _gc_seen[1]
        return {
            "spans": [dict(zip(("id", "parent", "name", "start_ns",
                                "end_ns", "args"), r)) for r in records],
            **_dropped,
            "counters": counters,
            "gauges": {k: dict(v) for k, v in _gauges.items()},
        }


def dump(path: str) -> None:
    with open(path, "w") as f:
        json.dump(snapshot(), f, default=str)


def reset() -> None:
    global _warned
    with _lock:
        _ring.clear()
        _compiles.clear()
        _gcs.clear()
        _counters.clear()
        if _listening:
            _counters.update(dict.fromkeys(_HOST_COUNTERS, 0))
        _gauges.clear()
        _dropped.update(dict.fromkeys(_dropped, 0))
        _gc_seen[:] = [0, 0.0]
        _warned = 0
        for clock in list(_clocks):
            clock.forget()
    # a trace this thread finished with nothing lowered after it still waits
    # (``_flush_traces``): forgotten too, or it surfaces after the reset
    getattr(_local, "traces", []).clear()


# ------------------------------------------------------- the host's clock
#
# What the host did in a late step. The rule (docs/timeline.md has it for the
# operator): a handle is ARMED once ARM_PERIODS of its step periods in a row
# lie within ARM_WITHIN of their median; a period further off (the late step
# itself, the short ones in which a loop with steps in flight catches up)
# stays out of that history, and ARM_PERIODS of those in a row are a new
# pace, learnt from nothing. A step of an armed handle is LATE when its
# dispatch starts more than max(LATE_MS, LATE_SHARE x median) after the
# median's time; past PAUSE_TIMES medians the loop had left (``pause``).

STALL = "hvd.host.stall"
GC = "hvd.host.gc"
ARM_PERIODS = 8
ARM_WITHIN = 0.25
LATE_MS = 20.0
LATE_SHARE = 0.10
PAUSE_TIMES = 20
SAMPLE_EVERY_S = 0.05   # the watcher's samples of a late step: how often,
SAMPLES = 40            # how many at most
SAMPLE_FOR_S = 5.0      # and for how long
STACK_FRAMES = 12       # frames kept of a sample, from the innermost
GC_RECORD_NS = 200_000  # a collection of generation 0 has a record from here
WARNINGS = 20           # stalls logged a process; every one is recorded

_HOST_COUNTERS = ("hvd.host.stalls", "hvd.host.stall_s")
_log = logging.getLogger("horovod_tpu")
_warned = 0
_clocks: "weakref.WeakSet" = weakref.WeakSet()  # every handle's, for reset
# Collections so far and their seconds, and the one that runs now. ``_on_gc``
# alone writes them, with no lock: a collection can start wherever this
# module holds ``_lock``, and the interpreter runs one at a time.
_gc_seen = [0, 0.0]
_gc_open: list = []
# The watcher: the armed clock that ticked last, the thread, what wakes it
# where it waits for no deadline, and whether it does.
_armed: list = [None]
_watcher: Optional[threading.Thread] = None
_wake = threading.Event()
_watcher_waits = False


def _on_gc(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` entry: every collection counted
    (``hvd.host.gc_collections``, ``hvd.host.gc_s``) and annotated for a
    profiler session; one of generation 1 or 2, or of ``GC_RECORD_NS`` or
    longer, recorded (``generation``, ``collected``)."""
    if phase == "start":
        annotation = _TraceAnnotation(GC, generation=info["generation"])
        annotation.__enter__()
        _gc_open[:] = (time.time_ns(), annotation)
        return
    if not _gc_open:
        return                      # installed while this collection ran
    end = time.time_ns()
    start, annotation = _gc_open
    del _gc_open[:]
    annotation.__exit__(None, None, None)
    _gc_seen[0] += 1
    _gc_seen[1] += (end - start) / 1e9
    if info["generation"] or end - start >= GC_RECORD_NS:
        if len(_gcs) == RING:
            _dropped["dropped_gcs"] += 1
        _gcs.append((next(_ids), 0, GC, start, end,
                     {"generation": info["generation"],
                      "collected": info["collected"]}))


class _RunQueue:
    """This thread's ``/proc/thread-self/schedstat``, held open: its second
    number is the time the thread has stood ready to run with no core to
    run on. ``None`` from ``wait_ns`` where ``/proc`` has no such file."""

    def __init__(self):
        try:
            self.fd = os.open("/proc/thread-self/schedstat", os.O_RDONLY)
        except OSError:
            self.fd = None

    def wait_ns(self) -> Optional[int]:
        if self.fd is None:
            return None
        return int(os.pread(self.fd, 64, 0).split()[1])

    def close(self) -> None:
        if self.fd is not None:
            os.close(self.fd)
            self.fd = None

    __del__ = close     # a thread's goes with the thread's locals


class StepClock:
    """The host's clock on one ``hvd.spmd_fn`` handle. ``tick`` runs inside
    every dispatch span, at its entry: it reads the thread's CPU time,
    ``getrusage(RUSAGE_THREAD)`` and the thread's wait for a core (a
    microsecond and a half together), writes what passed since the same
    thread's last dispatch of the handle into the span's record
    (``period_ms``, ``cpu_ms``, ``runq_ms``, ``vol`` / ``invol``: context
    switches the thread asked for and did not, ``majflt``), keeps the
    handle's pace, and where this dispatch came late writes the record
    ``hvd.host.stall``. Everything else here is read by the watcher."""

    def __init__(self, handle: str, program: str):
        self.handle, self.program = handle, program
        self.forget()
        _clocks.add(self)

    def _disarm(self) -> None:
        # the last even periods and the CPU time of each
        self.periods = collections.deque(maxlen=ARM_PERIODS)
        self.cpus = collections.deque(maxlen=ARM_PERIODS)
        self.median = None          # of ``periods``, while the handle is armed
        self.strays = 0             # periods off the pace, in a row
        self.deadline_ns = None     # when the next dispatch will be late
        self.samples: list = []     # the watcher's: (deadline, stack)

    def forget(self) -> None:
        """What a rebuilt program and ``reset()`` leave: no pace, and no
        thread's last readings."""
        self._disarm()
        self._last = threading.local()      # .seen: this thread's readings
        self.thread = None          # the thread that dispatched last

    def tick(self, sp: "span", fresh: bool) -> None:
        """``sp`` is the dispatch span just opened; ``fresh`` says that it
        compiles (call 0, or the first call of a rebuilt program): such a
        call carries no period and the pace is learnt anew after it."""
        cpu_ns = time.thread_time_ns()
        usage = resource.getrusage(resource.RUSAGE_THREAD)
        try:
            runq = _local.runq
        except AttributeError:
            runq = _local.runq = _RunQueue()
        # 0 start, 1 CPU ns, 2 vol, 3 invol, 4 majflt, 5 wait for a core,
        # 6 seconds of collections, 7 seconds of compiles, 8 the span
        seen = (sp._start, cpu_ns, usage.ru_nvcsw, usage.ru_nivcsw,
                usage.ru_majflt, runq.wait_ns(), _gc_seen[1],
                _counters.get("hvd.compile.seconds", 0.0), sp)
        if fresh:
            self.forget()
        last = getattr(self._last, "seen", None)
        self._last.seen = seen
        deadline, self.thread = self.deadline_ns, threading.get_ident()
        if last is not None:
            spent = {"period_ms": (seen[0] - last[0]) / 1e6,
                     "cpu_ms": (seen[1] - last[1]) / 1e6,
                     "vol": seen[2] - last[2], "invol": seen[3] - last[3],
                     "majflt": seen[4] - last[4]}
            if seen[5] is not None:
                spent["runq_ms"] = (seen[5] - last[5]) / 1e6
            sp.args.update(spent)
            cause = None
            if deadline is not None and seen[0] > deadline:
                cause = self._stall(sp, last, seen, spent, deadline)
            elif self.samples:
                self.samples = []   # the watcher woke as this one came in
            if cause != "pause":
                self._learn(spent["period_ms"], spent["cpu_ms"])
        median = self.median
        if median is None:
            self.deadline_ns = None
            return
        self.deadline_ns = seen[0] + int(
            (median + max(LATE_MS, LATE_SHARE * median)) * 1e6)
        _armed[0] = self
        if _watcher_waits:
            _wake.set()
        elif _watcher is None:
            _start_watcher()

    def _learn(self, period_ms: float, cpu_ms: float) -> None:
        median = self.median
        if median is not None and abs(period_ms - median) > ARM_WITHIN * median:
            self.strays += 1
            if self.strays >= ARM_PERIODS:
                self._disarm()
            return
        self.strays = 0
        self.periods.append(period_ms)
        self.cpus.append(cpu_ms)
        if len(self.periods) == ARM_PERIODS:
            ordered = sorted(self.periods)
            median = (ordered[ARM_PERIODS // 2 - 1]
                      + ordered[ARM_PERIODS // 2]) / 2
            even = ordered[-1] - median <= ARM_WITHIN * median \
                and median - ordered[0] <= ARM_WITHIN * median
            self.median = median if even else None

    def _stall(self, sp, last, seen, spent, deadline) -> str:
        """The dispatch ``sp`` of an armed handle started after ``deadline``:
        one record from the last dispatch's start to this one's. Returns
        its ``cause``, decided in the order of the tests below."""
        median, period_ms = self.median, spent["period_ms"]
        late_ms = period_ms - median
        stacks = collections.Counter(
            stack for late_from, stack in self.samples if late_from == deadline)
        self.samples = []
        stack = stacks.most_common(1)[0][0] if stacks else ()
        frames = [f"{code.co_filename}:{line} {code.co_name}"
                  for code, line in stack]
        # was the last dispatch span itself open for half of the late time
        closed = last[8].end_ns or seen[0]
        inside = 2 * (min(closed, seen[0]) - deadline) >= seen[0] - deadline
        gc_ms, compile_s = 1e3 * (seen[6] - last[6]), seen[7] - last[7]
        # the thread's CPU time over what an even step of this handle takes
        more_cpu_ms = spent["cpu_ms"] - statistics.median(self.cpus)
        if period_ms > PAUSE_TIMES * median:
            cause = "pause"         # the loop had left
        elif compile_s > 0:
            cause = "compile"       # a compile record lies in the period
        elif 2 * gc_ms >= late_ms:
            cause = "gc"
        elif inside:
            cause = "dispatch"      # the runtime's enqueue held the thread
        elif 2 * spent.get("runq_ms", 0.0) >= late_ms or spent["majflt"]:
            cause = "off_cpu"       # ready to run with no core, or paged in
        elif 2 * more_cpu_ms >= late_ms:
            cause = "python"        # host code ran on this thread
        else:
            cause = "waiting"       # it slept of its own accord: for a result
        args = dict(
            spent, handle=self.handle, program=self.program,
            call=sp.args.get("call"), late_ms=late_ms, median_ms=median,
            cause=cause, where=frames[0] if frames else "", stack=frames,
            samples=sum(stacks.values()), inside_dispatch=inside,
            gc_ms=gc_ms, compile_s=compile_s)
        _append(_ring, (next(_ids), sp.id, STALL, last[0], seen[0], args),
                "dropped")
        if cause == "pause":
            self._disarm()          # and so no stall of a step
        else:
            count("hvd.host.stalls")
            count("hvd.host.stall_s", late_ms / 1e3)
            _warn(args)
        return cause



def _warn(stall: dict) -> None:
    """The SPMD path's stall warning (the reference's is
    operations.cc:1625-1672, for tensors that wait for a rank)."""
    global _warned
    with _lock:
        _warned += 1
        nth = _warned
    if nth > WARNINGS:
        return
    _log.warning(
        "hvd.host.stall: call %s of %s started %.1f ms late (a step takes "
        "%.1f ms): cause %s, the thread stood at %s%s",
        stall["call"], stall["program"], stall["late_ms"],
        stall["median_ms"], stall["cause"],
        stall["where"] or "(no sample)",
        "" if nth < WARNINGS else f"; this is the {WARNINGS}th such line "
        "and the last, later stalls are in timeline.snapshot()")


def _stack_of(thread: int) -> tuple:
    frame, stack = sys._current_frames().get(thread), []
    while frame is not None and len(stack) < STACK_FRAMES:
        stack.append((frame.f_code, frame.f_lineno))
        frame = frame.f_back
    return tuple(stack)


def _watch() -> None:
    """The watcher thread. It sleeps until the armed handle's deadline, so
    it wakes about once a step and finds a later deadline; where the
    deadline has passed it samples the dispatching thread's Python stack
    every ``SAMPLE_EVERY_S`` until the handle's next dispatch (at most
    ``SAMPLES`` times and ``SAMPLE_FOR_S``), then waits to be woken."""
    global _watcher_waits
    while True:
        clock = _armed[0]
        deadline = clock.deadline_ns if clock is not None else None
        if deadline is not None:
            ahead = deadline - time.time_ns()
            if ahead > 0:
                time.sleep(ahead / 1e9)
                continue
            until = time.monotonic() + SAMPLE_FOR_S
            for _ in range(SAMPLES):
                if clock.deadline_ns != deadline or time.monotonic() > until:
                    break       # the next dispatch has come, or the caps
                clock.samples.append((deadline, _stack_of(clock.thread)))
                time.sleep(SAMPLE_EVERY_S)
            if clock.deadline_ns != deadline:
                continue
        _watcher_waits = True       # nothing armed, or the loop stands still
        _wake.wait()
        _watcher_waits = False
        _wake.clear()


def _start_watcher() -> None:
    global _watcher
    with _lock:
        if _watcher is None:
            _watcher = threading.Thread(target=_watch, name="hvd-step-watcher",
                                        daemon=True)
            _watcher.start()


# --------------------------------------------------------- Chrome writer


class Timeline:
    """Thread-safe, non-blocking chrome-trace writer.

    API mirrors the reference (timeline.h:83-93): ``negotiate_start/
    negotiate_end``, ``start/activity_start/activity_end/end``; cycle
    markers (``HOROVOD_TIMELINE_MARK_CYCLES``) are the native core's
    (csrc/timeline.cc), which has control cycles to mark.
    """

    def __init__(
        self,
        path: Optional[str],
        enabled_rank: bool = True,
    ) -> None:
        self._enabled = bool(path) and enabled_rank
        self._path = path
        self._queue: "queue.SimpleQueue[Optional[dict]]" = queue.SimpleQueue()
        self._tensor_tracks: dict = {}
        self._next_tid = 1
        self._lock = threading.Lock()
        self._writer: Optional[threading.Thread] = None
        self._t0 = time.monotonic_ns()
        if self._enabled:
            self._writer = threading.Thread(
                target=self._drain, name="hvd-timeline-writer", daemon=True
            )
            self._writer.start()

    @property
    def enabled(self) -> bool:
        return self._enabled

    # -- infrastructure ----------------------------------------------------

    # Cap on named tracks so auto-named ops in long training loops cannot
    # grow the map unboundedly; overflow names share hashed tracks.
    _MAX_TRACKS = 4096

    def _now_us(self) -> float:
        return (time.monotonic_ns() - self._t0) / 1e3

    def _tid(self, tensor_name: str) -> int:
        with self._lock:
            tid = self._tensor_tracks.get(tensor_name)
            if tid is None:
                if self._next_tid > self._MAX_TRACKS:
                    return (hash(tensor_name) % self._MAX_TRACKS) + 1
                tid = self._next_tid
                self._next_tid += 1
                self._tensor_tracks[tensor_name] = tid
                self._queue.put(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": 0,
                        "tid": tid,
                        "args": {"name": tensor_name},
                    }
                )
            return tid

    def _emit(self, ev: dict) -> None:
        self._queue.put(ev)

    def _drain(self) -> None:
        assert self._path is not None
        with open(self._path, "w") as f:
            f.write("[\n")
            while True:
                ev = self._queue.get()
                if ev is None:
                    break
                f.write(json.dumps(ev))
                f.write(",\n")
                # Writer thread owns the file; flush per event batch is
                # acceptable off the hot path.
                if self._queue.empty():
                    f.flush()

    # -- reference API -----------------------------------------------------

    def negotiate_start(self, tensor_name: str, op: str) -> None:
        if not self._enabled:
            return
        self._emit(
            {
                "name": _NEGOTIATING,
                "ph": "B",
                "pid": 0,
                "tid": self._tid(tensor_name),
                "ts": self._now_us(),
                "args": {"op": op},
            }
        )

    def negotiate_end(self, tensor_name: str) -> None:
        if not self._enabled:
            return
        self._emit(
            {
                "name": _NEGOTIATING,
                "ph": "E",
                "pid": 0,
                "tid": self._tid(tensor_name),
                "ts": self._now_us(),
            }
        )

    def start(self, tensor_name: str, op: str,
              args: Optional[dict] = None) -> None:
        if not self._enabled:
            return
        ev = {
            "name": op,
            "ph": "B",
            "pid": 0,
            "tid": self._tid(tensor_name),
            "ts": self._now_us(),
        }
        if args:
            ev["args"] = args
        self._emit(ev)

    def activity_start(self, tensor_name: str, activity: str) -> None:
        if not self._enabled:
            return
        self._emit(
            {
                "name": activity,
                "ph": "B",
                "pid": 0,
                "tid": self._tid(tensor_name),
                "ts": self._now_us(),
            }
        )

    def activity_end(self, tensor_name: str) -> None:
        if not self._enabled:
            return
        self._emit(
            {
                "name": "",
                "ph": "E",
                "pid": 0,
                "tid": self._tid(tensor_name),
                "ts": self._now_us(),
            }
        )

    def end(self, tensor_name: str, op: Optional[str] = None) -> None:
        if not self._enabled:
            return
        self._emit(
            {
                "name": op or "",
                "ph": "E",
                "pid": 0,
                "tid": self._tid(tensor_name),
                "ts": self._now_us(),
            }
        )

    def span_start(self, sp: "span"):
        """Export an opening :class:`span`; returns what ``end`` takes to
        close it. ``hvd.spmd.dispatch`` keeps the names it has always had
        here: ``XLA_COMPILE`` on the handle's track for a call that blocks
        through trace and compile (a handle's first, and the first after
        the autotuner rebuilt it), ``XLA_EXECUTE`` for the asynchronous
        host dispatch of every other. Any other span is an activity of
        its own name on a track of that name."""
        if not self._enabled:
            return None
        if sp.name == DISPATCH:
            compiles = sp.args.get("call") == 0 or sp.args.get("rebuilt")
            track = sp.args.get("handle", sp.name)
            op = XLA_COMPILE if compiles else XLA_EXECUTE
            args = {"span": "trace+compile" if compiles else "host_dispatch"}
        else:
            track, op, args = sp.name, sp.name, dict(sp.args)
        self.start(track, op, args=args)
        return track, op

    def mark_window(self, index: int, steps: int) -> None:
        """Instant global marker at a multi-step window boundary
        (horovod_tpu/jax/window.py): the window-loop analogue of the
        reference's cycle marker, carrying the window index and the number
        of steps its single dispatch covers."""
        if not self._enabled:
            return
        self._emit(
            {
                "name": "WINDOW_START",
                "ph": "i",
                "s": "g",
                "pid": 0,
                "tid": 0,
                "ts": self._now_us(),
                "args": {"window": index, "steps": steps},
            }
        )

    def close(self) -> None:
        if self._enabled and self._writer is not None:
            self._queue.put(None)
            self._writer.join(timeout=5.0)
            self._writer = None
            self._enabled = False


class _Activity:
    """Context manager sugar: ``with timeline.activity(name, ALLREDUCE): ...``"""

    def __init__(self, timeline: Timeline, tensor_name: str, activity: str):
        self._t = timeline
        self._name = tensor_name
        self._activity = activity

    def __enter__(self):
        self._t.activity_start(self._name, self._activity)
        return self

    def __exit__(self, *exc):
        self._t.activity_end(self._name)
        return False


def activity(timeline: Timeline, tensor_name: str, act: str) -> _Activity:
    return _Activity(timeline, tensor_name, act)
