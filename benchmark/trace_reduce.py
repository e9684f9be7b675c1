"""From a ``jax.profiler`` trace to device busy/idle time, the operations
that took most of it, and the idle gaps by what the host was doing.

Two halves. :func:`read_xplane` turns an ``.xplane.pb`` into plain lists
(device operations per chip, host spans), and needs a trace to exist.
:func:`reduce` works on those lists alone, so the arithmetic is tested on
a small synthetic list (``benchmark/tests``) and is the same for every PR.

Definitions, all in seconds of the trace's own clock:

* window: the extent of the host span named ``WINDOW_SPAN`` (the measured
  loop); device operations are clipped to it.
* busy: the length of the union of the device-operation intervals of one
  chip, averaged over the chips that ran anything. idle = window - busy.
* self time of an operation: its duration minus what operations nested
  inside it on the same line cover (a ``while`` holds its body's ops).
* an idle gap belongs to the innermost ``bench.*`` host span open at the
  gap's midpoint; gaps shorter than ``SEAM_S`` are summed as seams.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
SEAM_S = 10e-6
SEAMS = "_seams_under_10_us_"
NO_SPAN = "_no_host_span_"
TOP_N = 10

_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16|c64|c128)\[([0-9,]*)\]")


class Op(NamedTuple):
    name: str        # the trace's own name of the operation
    start: float
    dur: float
    text: str        # whatever else the trace says of it (shapes, category)


class Span(NamedTuple):
    name: str
    start: float
    dur: float


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _clip(ops: Sequence[Op], lo: float, hi: float) -> List[Op]:
    out = []
    for o in ops:
        s, e = max(o.start, lo), min(o.start + o.dur, hi)
        if e > s:
            out.append(Op(o.name, s, e - s, o.text))
    return out


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, float]]:
    """Each operation with its self time: duration minus the part covered
    by operations that start inside it (same line, so properly nested).
    An operation that holds others is a container (a ``while``): its self
    time is loop overhead, not the work of its body."""
    order = sorted(ops, key=lambda o: (o.start, -o.dur))
    out: List[List] = []
    stack: List[int] = []
    for o in order:
        # a parent holds the whole of its child; an operation that merely
        # overlaps the one before it is its sibling
        while stack and (out[stack[-1]][0].start + out[stack[-1]][0].dur
                         < o.start + o.dur - 1e-12
                         or out[stack[-1]][0].start + out[stack[-1]][0].dur
                         <= o.start):
            stack.pop()
        if stack:
            out[stack[-1]][1] -= o.dur
        out.append([o, o.dur])
        stack.append(len(out) - 1)
    return [(o, max(t, 0.0)) for o, t in out]


def leaves(ops: Sequence[Op]) -> List[Op]:
    """The operations that hold no other operation."""
    return [o for o, t in self_times(ops) if t >= o.dur * (1 - 1e-9)]


def result_shape(op: Op) -> str:
    """``f32[3555224,128]``-style shape of the operation's result, read
    from the first shape in the trace's text for it; '' if none."""
    m = _SHAPE.search(op.text)
    return f"{m.group(1)}[{m.group(2)}]" if m else ""


def label(op: Op) -> str:
    shape = result_shape(op)
    return f"{op.name} {shape}" if shape else op.name


def mentions_shape(op: Op, shapes: Sequence[Tuple[int, ...]]) -> bool:
    """Whether any operand or result of the operation has one of
    ``shapes`` (exact dimensions), going by the trace's text for it."""
    return any("[" + ",".join(str(d) for d in s) + "]" in op.text
               for s in shapes)


def _innermost(spans: Sequence[Span], t: float) -> Optional[Span]:
    best = None
    for s in spans:
        if s.start <= t <= s.start + s.dur and (best is None
                                                or s.dur < best.dur):
            best = s
    return best


def reduce(device_ops: Dict[str, Sequence[Op]], host_spans: Sequence[Span],
           table_shapes: Sequence[Tuple[int, ...]] = ()) -> Dict:
    """Busy, idle, top operations and idle gaps of one traced window.

    ``device_ops`` maps a chip's name to its operations. Returns
    ``window_s``, ``busy_s`` (mean over chips that ran an operation),
    ``idle_share`` (0..1), ``table_s`` (length of the union of the
    operations that hold no other and mention a shape in ``table_shapes``,
    mean over the same chips; never above ``busy_s``),
    ``device_ops`` and ``idle_gaps`` (lists of ``[name, seconds]``, most
    first, at most ``TOP_N``), ``n_ops`` and ``chips``."""
    windows = [s for s in host_spans if s.name == WINDOW_SPAN]
    every = [o for ops in device_ops.values() for o in ops]
    if windows:
        lo = min(s.start for s in windows)
        hi = max(s.start + s.dur for s in windows)
    elif every:
        lo = min(o.start for o in every)
        hi = max(o.start + o.dur for o in every)
    else:
        return {"window_s": 0.0, "busy_s": 0.0, "idle_share": 1.0,
                "table_s": 0.0, "device_ops": [], "idle_gaps": [],
                "n_ops": 0, "chips": 0}
    spans = [s for s in host_spans
             if s.name.startswith(SPAN_PREFIX) and s.name != WINDOW_SPAN]
    busy, table, n_ops = [], [], 0
    by_op: Dict[str, float] = {}
    by_gap: Dict[str, float] = {}
    for ops in device_ops.values():
        ops = _clip(ops, lo, hi)
        if not ops:
            continue
        n_ops += len(ops)
        merged = union([(o.start, o.start + o.dur) for o in ops])
        busy.append(sum(e - s for s, e in merged))
        for o, t in self_times(ops):
            by_op[label(o)] = by_op.get(label(o), 0.0) + t
        # a union, like busy: operations of one chip can overlap (the trace
        # of the fused epoch summed to 100.6% of busy as plain self times)
        table.append(sum(e - s for s, e in union(
            [(o.start, o.start + o.dur) for o in leaves(ops)
             if table_shapes and mentions_shape(o, table_shapes)])))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            if g1 - g0 < SEAM_S:
                name = SEAMS
            else:
                s = _innermost(spans, 0.5 * (g0 + g1))
                name = s.name if s is not None else NO_SPAN
            by_gap[name] = by_gap.get(name, 0.0) + (g1 - g0)
    chips = len(busy)
    window_s = hi - lo
    busy_s = sum(busy) / chips if chips else 0.0

    def top(d: Dict[str, float]) -> List[List]:
        # per chip, like busy_s
        return [[k, v / max(chips, 1)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]]

    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else 1.0,
            "table_s": sum(table) / chips if chips else 0.0,
            "device_ops": top(by_op), "idle_gaps": top(by_gap),
            "n_ops": n_ops, "chips": chips}


# ---------------------------------------------------------------------- #
# .xplane.pb -> lists
# ---------------------------------------------------------------------- #
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _op(event) -> Op:
    """The TPU trace names an operation by its whole HLO instruction,
    ``%fusion.3 = f32[5310447,128]{1,0:T(8,128)} fusion(f32[...] %x, ...)``:
    the part before `` = `` is the name, the rest (result shape first,
    then operands) is the text, with any string stats appended."""
    name, _, rest = event.name.partition(" = ")
    stats = " ".join(str(v) for _, v in event.stats if isinstance(v, str))
    return Op(name.lstrip("%"), event.start_ns * 1e-9,
              event.duration_ns * 1e-9, (rest + " " + stats).strip())


def read_xplane(path: str) -> Tuple[Dict[str, List[Op]], List[Span]]:
    """Device operations per chip (the ``XLA Ops`` line of each
    ``/device:TPU:n`` plane) and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Op]] = {}
    host_spans: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                device_ops.setdefault(plane.name, []).extend(
                    _op(e) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    Span(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return device_ops, host_spans


def describe(path: str, per_line: int = 4) -> str:
    """What a trace holds, for a reader who has to write against it:
    planes, lines, event counts and a few events with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name} events={len(events)}")
            for e in events[:per_line]:
                out.append(f"    {e.name} start_ns={e.start_ns} "
                           f"dur_ns={e.duration_ns} stats={list(e.stats)}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(find_xplane(sys.argv[1])))
