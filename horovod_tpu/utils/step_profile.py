"""A device profile reduced by the program's own names.

``reduce_file(path)`` reads the ``.xplane.pb`` that any ``jax.profiler``
session writes and returns, for the training steps it holds:

* **phases**: device time by the scopes of :mod:`horovod_tpu.utils.timeline`
  (``hvd_forward``, ``hvd_loss``, ``hvd_exchange``, ``hvd_update``,
  ``hvd_metrics``), read from each operation's ``op_name`` (the ``tf_op``
  stat of the event's metadata: ``jit(step_fn)/.../hvd_forward/...``).
  ``backward`` is what JAX wrote as ``transpose(jvp(hvd_forward))``,
  ``recomputed`` the part of it under ``checkpoint/rematted_computation``
  (block recomputation: the forward pass run again), ``other`` what carries
  none of the names (copies, the runtime's own operations). The innermost
  name wins: the exchange lies inside ``hvd_update`` and counts as exchange.
  A fusion carries one of its operations' names, so a pass that XLA fused
  into a neighbour counts with the neighbour. Times are self times: an
  operation that contains others (a loop, a call) gives its time to them.
* **layers**: the same self times by the layer scopes inside ``hvd_forward``
  (``timeline.LAYER_SCOPES``: attention by the layer's type, a latent
  layer's compression and expansion, the parts of a sparse expert layer, a
  looped model's applications of its stack and its
  exit gate; its chunked exit loss lies inside ``hvd_loss``), forward,
  recomputed and backward together; the innermost name wins. Empty for a model
  that names no layer. The grouped products of an expert layer (``%ragged-
  dot-*`` instructions, XLA:TPU's own lowering, which keeps no ``op_name``)
  count as ``hvd_moe_experts`` here and, having no phase, as ``other`` above.
* **collectives**: time of all-reduce, reduce-scatter, all-gather,
  all-to-all and collective-permute events on the lines ``XLA Ops`` and
  ``Async XLA Ops`` (start to done), the part of it that is **exposed**
  (during which no other operation ran on that chip), and the part that
  the phases count under ``other``: a collective that XLA made itself, by
  combining several or by decomposing a reduce-scatter, carries no
  ``op_name``. An asynchronous collective whose ``-start`` and ``-done``
  both stand on ``XLA Ops`` with no span on ``Async XLA Ops`` counts from
  the start's beginning to the done's end. So does XLA:TPU's asynchronous
  collective fusion (``%async-collective-start`` / ``%async-collective-done``,
  fusions whose kind is read from the primitive the done's ``op_name`` ends in):
  the fusions between the two carry the collective's steps with work of
  their own and count as that work, so a step they stretch reads as hidden;
  what reads as exposed there is the start, the done's wait and any gap.
  ``collective_beside_s`` names what ran while a collective was open: the
  eight operation families with the most time there.
* **gaps**: the chip's idle time inside the window, each gap put down to the
  innermost ``hvd.*`` span (``hvd.spmd.dispatch`` and the others of
  docs/timeline.md) open on the host at its middle, or ``between spans``.

Phases, collectives and busy time are means over the chips in the profile;
gaps are the first chip's. ``jax.profiler.ProfileData`` hands out an event's
own stats but not its metadata's, where ``tf_op`` lives, so the file is read
here from the protobuf wire format (XSpace of tsl/profiler/protobuf/
xplane.proto; the fields used are listed above ``Event``).
"""

from __future__ import annotations

import collections
import glob
import gzip
import os
import re
import struct
from typing import Iterator, List, NamedTuple, Optional, Tuple

from horovod_tpu.utils import timeline

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
OPERATION = re.compile(r" ([a-z][a-z0-9-]*)\(")
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start|-done)?$")
# XLA:TPU's asynchronous collective fusion: the two fusions that open and
# close it, and the JAX primitive (the end of the done's ``op_name``) that says
# which collective it is
ASYNC_FUSION = re.compile(r"^%async-collective-(start|done)((?:\.\d+)?)$")
_PRIMITIVES = {"psum": "all-reduce", "psum_invariant": "all-reduce",
               "pmax": "all-reduce", "pmin": "all-reduce",
               "all_gather": "all-gather", "all_gather_invariant": "all-gather",
               "psum_scatter": "reduce-scatter",
               "reduce_scatter": "reduce-scatter",
               "all_to_all": "all-to-all", "ppermute": "collective-permute"}
_PAIRED = re.compile(r"-done\([^%]*%([\w.\-]+)")
PHASES = ("forward", "backward", "recomputed", "loss", "exchange", "update",
          "metrics", "other")
_SCOPES = {timeline.FORWARD: "forward", timeline.LOSS: "loss",
           timeline.EXCHANGE: "exchange", timeline.UPDATE: "update",
           timeline.METRICS: "metrics"}
_SCOPE = re.compile("|".join(map(re.escape, _SCOPES)))
_LAYER = re.compile("|".join(map(re.escape, timeline.LAYER_SCOPES)))
_GROUPED_PRODUCT = "%ragged-dot"
_RECOMPUTED = "rematted_computation"
_BACKWARD = f"transpose(jvp({timeline.FORWARD}"
BETWEEN = "between spans"
MIN_GAP_NS = 1_000          # shorter than a microsecond is the clock's grain


# ------------------------------------------------------------ the file

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field, wire type, value)`` of one protobuf message: varints as
    ints, fixed64 and fixed32 as their bytes, length-delimited as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield field, wire, value


# Field numbers (xplane.proto). XSpace: planes 1. XPlane: name 2, lines 3,
# event_metadata 4 and stat_metadata 5 (maps: key 1, value 2). XLine: name 2,
# timestamp_ns 3, events 4. XEvent: metadata_id 1, offset_ps 2, duration_ps 3,
# stats 4. XStat: metadata_id 1, double 2, uint64 3, int64 4, str 5, bytes 6,
# ref 7. XEventMetadata: id 1, name 2, stats 5. XStatMetadata: id 1, name 2.


class Event(NamedTuple):
    start_ns: float
    end_ns: float
    name: str           # the instruction's text on a device line
    op_name: str        # its ``tf_op``: the JAX name stack; "" where none
    stats: dict         # the event's own stats (a host span's arguments)


class Line(NamedTuple):
    name: str
    events: List[Event]


class Plane(NamedTuple):
    name: str
    lines: List[Line]


def _stat(buf, stat_names):
    name, value = "", None
    for f, w, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, "")
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v - (1 << 64) if f == 4 and v >= 1 << 63 else v
        elif f in (5, 6):
            value = v.decode("utf-8", "replace")
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf) -> Plane:
    name, lines, metadata, stat_names = "", [], {}, {}
    parts = list(_fields(buf))
    for f, _, v in parts:
        if f == 2:
            name = v.decode()
        elif f == 5:
            entry = dict((g, x) for g, _, x in _fields(v))
            meta = dict((g, x) for g, _, x in _fields(entry.get(2, b"")))
            stat_names[meta.get(1, entry.get(1, 0))] = \
                meta.get(2, b"").decode()
    for f, _, v in parts:
        if f == 4:
            entry = dict((g, x) for g, _, x in _fields(v))
            text, op_name, ident = "", "", entry.get(1, 0)
            for g, _, x in _fields(entry.get(2, b"")):
                if g == 1:
                    ident = x
                elif g == 2:
                    text = x.decode("utf-8", "replace")
                elif g == 5:
                    key, value = _stat(x, stat_names)
                    if key == "tf_op":
                        op_name = value or ""
            metadata[ident] = (text, op_name)
    for f, _, v in parts:
        if f != 3:
            continue
        line_name, t0, raw = "", 0, []
        for g, _, x in _fields(v):
            if g == 2:
                line_name = x.decode()
            elif g == 3:
                t0 = x
            elif g == 4:
                raw.append(x)
        events = []
        for x in raw:
            ident = offset = duration = 0
            stats = {}
            for g, _, y in _fields(x):
                if g == 1:
                    ident = y
                elif g == 2:
                    offset = y
                elif g == 3:
                    duration = y
                elif g == 4:
                    key, value = _stat(y, stat_names)
                    stats[key] = value
            text, op_name = metadata.get(ident, ("", ""))
            start = t0 + offset / 1e3
            events.append(Event(start, start + duration / 1e3, text, op_name,
                                stats))
        lines.append(Line(line_name, events))
    return Plane(name, lines)


def read_xspace(path: str) -> List[Plane]:
    """The planes of an ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        buf = f.read()
    return [_plane(v) for f, _, v in _fields(buf) if f == 1]


# ------------------------------------------------------- the reduction

def phase_of(op_name: str) -> str:
    """The phase an operation belongs to, by the last of the scope names in
    its ``op_name``. Under ``hvd_forward`` JAX tells three things apart:
    ``jvp(hvd_forward)/...`` is the forward pass,
    ``transpose(jvp(hvd_forward))/.../jvp(hvd_forward)/...`` the backward
    pass, and of that what lies in ``checkpoint/rematted_computation`` the
    forward pass computed again. The loss's own backward
    (``transpose(jvp(hvd_loss))``) counts as loss."""
    found = None
    for found in _SCOPE.finditer(op_name):
        pass
    if found is None:
        return "other"
    phase = _SCOPES[found.group(0)]
    if phase != "forward":
        return phase
    if _RECOMPUTED in op_name:
        return "recomputed"
    return "backward" if _BACKWARD in op_name else "forward"


def layer_of(op_name: str) -> Optional[str]:
    """The last of the layer scopes in an operation's ``op_name``, or
    ``None``."""
    found = None
    for found in _LAYER.finditer(op_name):
        pass
    return found.group(0) if found else None


def operation(text: str) -> str:
    """``%psum.14 = bf16[8]{0} all-reduce(bf16[8] %x), channel_id=1`` ->
    ``all-reduce``; JAX names an instruction after its primitive, XLA says
    what it is before the operands."""
    _, eq, rest = text.partition(" = ")
    found = OPERATION.search(rest) if eq else None
    return found.group(1) if found else ""


def _instruction(text: str) -> str:
    return text.partition(" = ")[0]


def _family(text: str) -> str:
    """``%multiply_add_fusion.407 = ...`` -> ``multiply_add_fusion``."""
    return re.sub(r"[.\d]+$", "", _instruction(text).lstrip("%"))


def _async_pairs(ops: List[Event], beside: List[Event]):
    """``(start event, done event, which collective, held)`` of every
    asynchronous collective whose two halves stand on ``XLA Ops``:
    ``<collective>-start`` with the ``-done`` that names it, and
    ``%async-collective-start[.n]`` with the next
    ``%async-collective-done[.n]``; ``held`` where an event of ``Async XLA
    Ops`` already holds the span from start to done."""
    open_by_name, open_fusions, pairs = {}, {}, []
    for e in sorted(ops, key=lambda e: e.start_ns):
        name = _instruction(e.name)
        fused = ASYNC_FUSION.match(name)
        if fused:
            half, suffix = fused.groups()
            if half == "start":
                open_fusions[suffix] = e
            elif suffix in open_fusions:
                start = open_fusions.pop(suffix)
                # on the v5e the done carries the psum's name, the start none
                last = (e.op_name or start.op_name).rstrip(":").rsplit(
                    "/", 1)[-1]
                pairs.append((start, e, _PRIMITIVES.get(
                    last, "async-collective")))
            continue
        found = COLLECTIVE.match(operation(e.name))
        if not found or not found.group(2):
            continue
        if found.group(2) == "-start":
            open_by_name[name.lstrip("%")] = e
        else:
            named = _PAIRED.search(e.name)
            start = open_by_name.pop(named.group(1), None) if named else None
            if start is not None:
                pairs.append((start, e, found.group(1)))
    spans = [(b.start_ns, b.end_ns) for b in beside
             if COLLECTIVE.match(operation(b.name))]
    return [(s, d, kind, any(a <= s.start_ns and d.end_ns <= b + 1
                             for a, b in spans))
            for s, d, kind in pairs]


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


def _self_times(events: List[Event]):
    """``(event, self_ns, is_leaf)``: an event's time less that of those it
    contains, and whether it contains none."""
    out, stack = [], []                     # stack: [event, self_ns, leaf]
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end_ns, stack[-1][0].end_ns) - e.start_ns
            stack[-1][2] = False
        stack.append([e, e.end_ns - e.start_ns, True])
    out.extend(tuple(x) for x in stack)
    return out


def _overlaps(intervals, merged) -> Iterator[float]:
    """For each of ``(start, end)`` pairs in the order of their starts, the
    time it shares with a merged interval list."""
    j = 0
    for s, e in intervals:
        while j < len(merged) and merged[j][1] <= s:
            j += 1
        total, k = 0.0, j
        while k < len(merged) and merged[k][0] < e:
            total += min(e, merged[k][1]) - max(s, merged[k][0])
            k += 1
        yield total


def _overlap_ns(merged_a, merged_b) -> float:
    """Time covered by both of two merged interval lists."""
    return sum(_overlaps(merged_a, merged_b))


def host_spans(planes: List[Plane]):
    """``(start_ns, end_ns, name, stats)`` of every ``hvd.*`` annotation on
    the host's lines, by start."""
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hvd."):
                    spans.append((e.start_ns, e.end_ns, e.name, e.stats))
    return sorted(spans, key=lambda s: s[:2])


def _line(plane: Plane, name: str) -> List[Event]:
    for line in plane.lines:
        if line.name == name:
            return line.events
    return []


def reduce(planes: List[Plane], steps: Optional[int] = None) -> Optional[dict]:
    """The reduction the module docstring describes, or ``None`` where no
    chip's plane holds an operation (a CPU profile). ``steps``: the training
    steps the profile holds; default the ``hvd.spmd.dispatch`` spans of the
    handle dispatched most often in it. Seconds throughout; with ``steps``
    known, ``per_step_ms`` repeats phases and collectives a step."""
    chips = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and _line(plane, OPS_LINE):
            chips[int(m.group(1))] = plane
    if not chips:
        return None
    spans = host_spans(planes)
    if steps is None:
        handles = collections.Counter(
            s[3].get("program", s[3].get("handle"))    # older: name alone
            for s in spans if s[2] == timeline.DISPATCH)
        steps = handles.most_common(1)[0][1] if handles else None

    phases, layers = collections.Counter(), collections.Counter()
    busy = window = collective = exposed = unnamed = 0.0
    by_operation, beside_ns = collections.Counter(), collections.Counter()
    for plane in chips.values():
        ops, beside = _line(plane, OPS_LINE), _line(plane, ASYNC_LINE)
        moving = {}                         # id(event) -> which collective
        pairs = _async_pairs(ops, beside)
        halves = {id(e) for s, d, _, _ in pairs for e in (s, d)}
        for e in ops + beside:
            found = COLLECTIVE.match(operation(e.name))
            if found:
                moving[id(e)] = found.group(1)
                if id(e) not in halves:     # a pair counts start to done
                    by_operation[found.group(1)] += e.end_ns - e.start_ns
        for start, done, kind, held in pairs:
            moving[id(start)] = moving[id(done)] = kind
            if not held:
                by_operation[kind] += done.end_ns - start.start_ns
        nested = _self_times(ops)
        for e, self_ns, _ in nested:
            phase = phase_of(e.op_name)
            phases[phase] += self_ns
            layer = layer_of(e.op_name)
            if layer is None and e.name.startswith(_GROUPED_PRODUCT):
                # XLA:TPU's own lowering of ``lax.ragged_dot`` keeps no
                # ``op_name``: its phase reads other, its layer is known
                layer = timeline.MOE_EXPERTS
            if layer:
                layers[layer] += self_ns
            if phase == "other" and id(e) in moving:
                unnamed += self_ns
        total, _ = union_ns((e.start_ns, e.end_ns) for e in ops)
        busy += total
        window += max(e.end_ns for e in ops) - min(e.start_ns for e in ops)
        coll, coll_merged = union_ns(
            [(e.start_ns, e.end_ns) for e in ops + beside if id(e) in moving]
            + [(s.start_ns, d.end_ns) for s, d, _, _ in pairs])
        # what else ran: the operations that contain no other (a loop's
        # own event spans its body, collectives too) and move nothing
        _, other = union_ns(
            (e.start_ns, e.end_ns) for e, _, is_leaf in nested
            if is_leaf and id(e) not in moving)
        collective += coll
        exposed += coll - _overlap_ns(coll_merged, other)
        leaves = sorted((e for e, _, is_leaf in nested
                         if is_leaf and id(e) not in moving),
                        key=lambda e: e.start_ns)
        for e, shared in zip(leaves, _overlaps(
                ((e.start_ns, e.end_ns) for e in leaves), coll_merged)):
            if shared:
                beside_ns[_family(e.name)] += shared

    first = chips[min(chips)]
    _, merged = union_ns((e.start_ns, e.end_ns)
                         for e in _line(first, OPS_LINE))
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] - a[1] >= MIN_GAP_NS]
    idle = collections.Counter()
    for start, end in gaps:
        mid, doing = (start + end) / 2, BETWEEN
        for s, e, name, _ in spans:
            if s > mid:
                break
            if e >= mid:
                doing = name            # a later start lies further inside
        idle[doing] += end - start

    n = len(chips)
    out = {
        "chips": n, "steps": steps,
        "busy_s": busy / n / 1e9, "window_s": window / n / 1e9,
        "phases_s": {p: phases[p] / n / 1e9 for p in PHASES},
        "layers_s": {k: layers[k] / n / 1e9 for k in timeline.LAYER_SCOPES
                     if k in layers},
        "collective_s": collective / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "collective_in_other_s": unnamed / n / 1e9,
        "collectives_s": {k: v / n / 1e9 for k, v in by_operation.items()},
        "collective_beside_s": {k: v / n / 1e9
                                for k, v in beside_ns.most_common(8)},
        "idle_gaps_s": {k: v / 1e9 for k, v in idle.most_common()},
        "gaps": len(gaps),
        "host_spans": dict(collections.Counter(s[2] for s in spans)),
    }
    if steps:
        out["per_step_ms"] = dict(
            {p: 1e3 * v / steps for p, v in out["phases_s"].items()},
            busy=1e3 * out["busy_s"] / steps,
            collective=1e3 * out["collective_s"] / steps,
            collective_exposed=1e3 * out["collective_exposed_s"] / steps,
            collective_in_other=1e3 * out["collective_in_other_s"] / steps)
        out["per_step_ms"].update(
            {k: 1e3 * v / steps for k, v in out["layers_s"].items()})
    return out


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def reduce_file(path: str, steps: Optional[int] = None) -> Optional[dict]:
    """``path``: an ``.xplane.pb``, or a directory a profiler session wrote
    into (the newest profile under it is taken)."""
    if os.path.isdir(path):
        path = newest_xplane(path)
        if path is None:
            return None
    return reduce(read_xspace(path), steps)


def table(result: dict) -> str:
    """The reduction as the lines ``tools/profile_step.py`` prints."""
    steps = result["steps"] or 1
    lines = [f"chips {result['chips']}, steps {result['steps']}, device busy "
             f"{1e3 * result['busy_s'] / steps:.3f} ms a step, idle "
             f"{100 * (1 - result['busy_s'] / result['window_s']):.3f}% of "
             f"the window"]
    busy = result["busy_s"] or 1.0
    for phase in PHASES:
        s = result["phases_s"][phase]
        lines.append(f"  {phase:<11}{1e3 * s / steps:10.3f} ms a step"
                     f"{100 * s / busy:7.2f}% of busy")
    for layer, s in result.get("layers_s", {}).items():
        lines.append(f"  {layer:<17}{1e3 * s / steps:10.3f} ms a step"
                     f"{100 * s / busy:7.2f}% of busy (forward, recomputed "
                     f"and backward)")
    lines.append(f"  collectives{1e3 * result['collective_s'] / steps:10.3f} "
                 f"ms a step, exposed "
                 f"{1e3 * result['collective_exposed_s'] / steps:.3f} ms, "
                 f"counted under other "
                 f"{1e3 * result['collective_in_other_s'] / steps:.3f} ms "
                 + str({k: round(1e3 * v / steps, 3)
                        for k, v in result["collectives_s"].items()}))
    if result.get("collective_beside_s"):
        lines.append("  beside the collectives, ms a step: "
                     + str({k: round(1e3 * v / steps, 3) for k, v
                            in result["collective_beside_s"].items()}))
    lines.append(f"  idle gaps ({result['gaps']}) by host span, ms in all: "
                 + str({k: round(1e3 * v, 3)
                        for k, v in result["idle_gaps_s"].items()}))
    return "\n".join(lines)
