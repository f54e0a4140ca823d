"""From the profiler's ``.xplane.pb`` to the numbers the benchmark reports.

Read with ``jax.profiler.ProfileData`` and nothing else. A chip is a plane
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event for every operation
that ran, with start and duration on the clock the host's lines share. From
those:

* busy: the union of the operations' intervals (an operation inside a loop
  lies inside the loop's own event, and the union counts the time once);
* window: first operation's start to last operation's end on that chip;
* the operations that took most time, by family (the instruction's name less
  its number; a plain ``fusion`` keeps its result's shape, or it would say
  nothing), nested ones not counted twice (an event that contains others gives
  its time to them);
* collectives: the time of events whose operation (the word before the
  operands in ``%name = shape operation(...)``; the name itself is whatever
  JAX called the primitive, ``psum_invariant`` for an all-reduce) is
  all-reduce, reduce-scatter, all-gather, all-to-all or collective-permute, on
  ``XLA Ops`` and, for those that run beside other work, on ``Async XLA Ops``
  (start to done);
* idle gaps: the complement of busy inside the window, each gap put down to
  what the benchmark's own loop was doing at its middle, by the
  ``TraceAnnotation`` spans ``bench.*`` that ``run.py`` records on the host.

Busy, window and collectives are means over the chips; operations and gaps are
chip 0's. Where no chip's plane is found (a CPU run) there is nothing to read
and ``reduce`` returns ``None``: the metrics that read the trace are then left
out of the line.
"""

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
NUMBERING = re.compile(r"(\.\d+|\.remat\d*|\.clone)+$")
RESULT_SHAPE = re.compile(r" = \(?(\w+\[[\d,]*\])")
OPERATION = re.compile(r" ([a-z][a-z0-9-]*)\(")
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start|-done)?$")
HOST_SPANS = {"bench.run_step": "in run_step",
              "bench.wait_loss": "waiting for a loss",
              "bench.drain": "draining the last steps"}
BETWEEN = "between calls"
MIN_GAP_NS = 1_000          # shorter than a microsecond is the clock's grain


def union_ns(intervals):
    """``(total, merged)`` of ``(start, end)`` pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def self_times(events):
    """``{name: ns}`` with every event's time less that of the events it
    contains. ``events``: ``(start, end, name)``."""
    out = collections.Counter()
    stack = []                              # [end, name, self_ns]
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            done = stack.pop()
            out[done[1]] += done[2]
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    for done in stack:
        out[done[1]] += done[2]
    return out


def family(name: str) -> str:
    """``%multiply_reduce_fusion.12 = (bf16[256]...`` -> ``multiply_reduce_fusion``;
    ``%fusion.235 = (f32[1024,50257]...`` -> ``fusion f32[1024,50257]``."""
    stem = NUMBERING.sub("", name.split(" = ", 1)[0].lstrip("%"))[:80]
    shape = RESULT_SHAPE.search(name)
    return f"{stem} {shape.group(1)}" if stem == "fusion" and shape else stem


def operation(name: str) -> str:
    """``%psum_invariant.14 = bf16[1024,1024]{...} all-reduce(bf16[...] %fusion),
    channel_id=1`` -> ``all-reduce``; an event that is not an instruction's
    text gives its family."""
    head, eq, text = name.partition(" = ")
    found = OPERATION.search(text) if eq else None
    return found.group(1) if found else family(name)


def _line_events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
    return []


def _host_spans(planes):
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  HOST_SPANS[e.name]))
    return sorted(spans)


def _attribute(gaps, spans):
    """Total idle seconds by what the host was doing at each gap's middle.
    The innermost (latest-started) span that covers the middle wins."""
    out = collections.Counter()
    for start, end in gaps:
        mid = (start + end) // 2
        doing = BETWEEN
        for s, e, what in spans:
            if s > mid:
                break
            if e >= mid:
                doing = what
        out[doing] += end - start
    return out


def reduce(profile, chips: int):
    """``profile``: a ``jax.profiler.ProfileData``. Returns ``None`` where no
    chip's plane holds an operation."""
    planes = list(profile.planes)
    devices = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = _line_events(plane, OPS_LINE)
            if events:
                devices[int(m.group(1))] = (
                    events, _line_events(plane, ASYNC_LINE))
    if not devices:
        return None
    ids = sorted(devices)[:chips]
    busy, window, collective = [], [], []
    for i in ids:
        events, beside = devices[i]
        total, _ = union_ns((s, e) for s, e, _ in events)
        busy.append(total)
        window.append(max(e for _, e, _ in events)
                      - min(s for s, _, _ in events))
        coll, _ = union_ns((s, e) for s, e, n in events + beside
                           if COLLECTIVE.match(operation(n)))
        collective.append(coll)

    first = devices[ids[0]][0]
    by_name = collections.Counter()
    for name, ns in self_times(first).items():
        by_name[family(name)] += ns
    _, merged = union_ns((s, e) for s, e, _ in first)
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] - a[1] >= MIN_GAP_NS]
    idle = _attribute(gaps, _host_spans(planes))

    def mean_s(xs):
        return sum(xs) / len(xs) / 1e9

    return {"chips": len(ids),
            "busy_s": mean_s(busy), "window_s": mean_s(window),
            "collective_s": mean_s(collective),
            "device_ops": [[n, ns / 1e9] for n, ns in by_name.most_common(10)],
            "idle_gaps": [[n, ns / 1e9] for n, ns in idle.most_common(10)],
            "longest_gap_s": max((e - s for s, e in gaps), default=0) / 1e9,
            "gaps": len(gaps)}


def reduce_dir(trace_dir: str, chips: int):
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    import jax.profiler

    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        return None
    return reduce(jax.profiler.ProfileData.from_file(found[-1]), chips)
