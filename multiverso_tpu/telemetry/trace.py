"""The one span primitive: program spans and counts, on the profiler's clock.

A **span** is one interval of host work (or of asynchronous device work
closed by the watcher, below) with enough identity to be read back
later: its ``name``, start ``ts`` and ``dur``, its own ``id``, the
``parent`` open on the same thread when it began (so a reader can take
**self time**, :func:`self_ms`), an optional ``cause`` (a span on
*another* thread that made this one happen: a producer's ``we.prepare``
causes the consumer's ``we.block.dispatch``), a ``request`` shared by
every span of one call or one block (the PS plane's per-request trace
ID is this field), the thread, the counts given at entry or set on the
handle before exit, and ``prof``: whether a ``jax.profiler`` trace was
being captured.

A **step** is a span that says it is one (the count ``step=1``: one
iteration of a training loop), and a **phase** is a span inside it; the
count ``phase=`` files a span's time under a name the apps share
(``prepare``, ``compute``, ``ps_wait``, ``io_wait``, ``push``).
:func:`step_report` reads a step's wall time, its phases, what other
threads did meanwhile and what compiled inside it off the records, and
:func:`step_summary` is the ``profile`` block of a MSG_STATS payload.

Two classes of site, one gate each:

* **coarse** sites (:func:`span`; :func:`record` for one that has
  already ended) fire at most a few dozen times a second: once per
  training call, per block, per table build, per compile. They are
  recorded ALWAYS, into
  the bounded ring below, as the flight recorder and the Dashboard
  monitors already are, and feed the Dashboard ``Monitor`` of the same
  name, so a site is one ``with`` statement.
* **fine** sites (plain :func:`add_span`, per PS request) stay behind
  the ``trace_ids`` flag. The hot-path check is :func:`enabled`, one
  attribute read; callers pre-check it to skip even the clock reads.
  A per-request **trace ID** minted at the client rides the frame meta
  (``ps/wire.TRACE_META_KEY``, and each MSG_BATCH inner frame's own
  meta), so spans recorded independently on the client (enqueue, window
  flush, ack) and on the owning shard (serve, wave apply) stitch into
  one causal chain by ID. Natively-served ops (zero-Python C++ fast
  path) are not traced by design: the punt path (MSG_BATCH, compressed
  wires, MSG_STATS) and the pure-Python plane are.

No per-minibatch, per-row or in-``jit`` site belongs in either class.

One clock with the device trace: while a ``jax.profiler`` trace is
active (``TraceAnnotation.is_enabled()``), a span also opens a
``jax.profiler.TraceAnnotation(name, request=...)``, so the same span
lies in the xplane's host plane beside the device operations, and the
in-memory record says ``prof: true``. Stamps are ``time.time_ns()``; the
profiler counts from the start of its own session, so the first span of
a capture also writes one ``mv.trace.anchor`` annotation that carries
its ``time_ns`` (:meth:`Tracer._anchor`), which ties the two clocks.

Records are Chrome ``trace_event`` complete events (``"ph": "X"``,
``ts``/``dur`` in microseconds of ``time.time_ns()``: an absolute
clock, so events from every rank of a single-host run land on one
Perfetto timeline; ``pid`` = PS rank, ``tid`` = OS thread; counts under
``args``). Files are JSONL (one event per line, append-friendly across
crashes); ``tools/dump_metrics.py to-perfetto`` wraps them into the
``{"traceEvents": [...]}`` envelope viewers expect
(``python tools/dump_metrics.py to-perfetto in.jsonl out.json``).
"""

from __future__ import annotations

import bisect
import itertools
import json
import os
import queue
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

from multiverso_tpu.utils import config
from multiverso_tpu.utils.dashboard import Dashboard
from multiverso_tpu.utils.intervals import (clip, intersect_disjoint,
                                            union_intervals, union_length)

config.define_bool(
    "trace_ids", False,
    "mint per-request trace IDs on async-PS client ops, carry them in "
    "frame meta, and record the FINE trace_event spans (per PS request) "
    "on both endpoints, plus the device-completion watcher "
    "(telemetry/trace.py:DeviceWatcher): one device span, from dispatch "
    "to ready, for every program train_fused, train_ps_blocks and the "
    "language-model Trainer launch. Off by default: fine tracing must "
    "cost nothing when unused; coarse program spans (per call, block, "
    "table build, compile) are always recorded. Spans dump to "
    "metrics_dir as trace-rank<r>.jsonl")

# bounded span buffer: an always-on tracer must cap memory, not OOM a
# training run; 200k events is hours of coarse spans or of windowed PS
# traffic
_MAX_EVENTS = 200_000
ANCHOR = "mv.trace.anchor"
COMPILE = "xla.compile"


def _no_steps() -> Dict[str, Any]:
    """Sums over no step yet: what :func:`_add_step` adds into."""
    return {"steps": 0, "wall_ms": 0.0, "attributed_ms": 0.0,
            "stall_ms": 0.0, "overlap_ms": 0.0, "phases": {},
            "steady": set(), "compiles": 0}


def profiling() -> bool:
    """Whether a ``jax.profiler`` trace is being captured right now."""
    return TraceAnnotation.is_enabled()


class Span:
    """An open coarse span; what ``with span(...) as s`` yields.
    ``s.set(rows=3)`` adds counts before exit; ``s.id`` is what another
    thread passes as ``cause=``."""

    __slots__ = ("name", "id", "parent", "cause", "request", "counts",
                 "prof", "_tracer", "_t0", "_ann", "_mark")

    def __init__(self, tracer: "Tracer", name: str, request, cause,
                 counts: Dict[str, Any]):
        self._tracer, self.name = tracer, name
        self.request, self.cause = request, cause
        self.counts = counts
        self.id = next(tracer._span_ids)
        self.parent: Optional[int] = None
        self.prof = False
        self._ann = self._mark = None

    def set(self, **counts) -> None:
        self.counts.update(counts)

    def __enter__(self) -> "Span":
        stack = self._tracer._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self.prof = profiling()
        if self.prof != self._tracer._anchored:
            self._tracer._anchor(self.prof)
        if self.prof:
            self._ann = (TraceAnnotation(self.name)
                         if self.request is None else
                         TraceAnnotation(self.name, request=self.request))
            self._ann.__enter__()
        self._t0 = time.time_ns()
        if "step" in self.counts:
            self._mark = self._tracer._step_opens(self._t0 / 1e3)
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.time_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._stack().pop()
        self._tracer._record(
            self.name, self._t0, t1, self.id, self.parent, self.cause,
            self.request, "prog", self.counts, self.prof)
        Dashboard.get(self.name).observe_ms((t1 - self._t0) * 1e-6)
        if self._mark is not None:
            self._tracer._step_closes(self.id, self._mark)


class Tracer:
    """Process-global span recorder (one per process, like Dashboard)."""

    def __init__(self) -> None:
        self.enabled = False     # plain attribute: the fine sites' gate
        self.rank = 0
        self._rank_pinned = False
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=_MAX_EVENTS)
        self._next_id = 0
        self._span_ids = itertools.count(1)
        self._tls = threading.local()
        self._anchored = False   # an anchor was written for this capture
        # the steps so far, added up as each closes (_step_closes): how
        # many records the ring has ever taken, the sums, and each
        # thread's first step (its interval; open-ended while it runs)
        self._taken = 0
        self._steps = _no_steps()
        self._first: Dict[int, Tuple[float, float]] = {}

    # ------------------------------------------------------------------ #
    def configure(self, rank: Optional[int] = None) -> None:
        """Adopt the ``trace_ids`` flag (called from PSService init and
        Zoo.start — the points where flags are settled); idempotent.
        The FIRST caller's rank sticks: a process holding several
        PSContexts (bench workers, test fixtures) must not have the
        last-constructed rank clobber the pid/ID-space of spans already
        attributed to the first — in-process multi-rank spans then all
        carry the first rank, a known (and documented) collapse."""
        if rank is not None and not self._rank_pinned:
            self.rank = int(rank)
            self._rank_pinned = True
        self.enabled = bool(config.get_flag("trace_ids"))

    def new_id(self) -> int:
        """Mint a trace ID unique across processes: the pinned rank in
        the high bits, a process-local counter below (fits JSON's
        exact-int range). Several in-process ranks share one tracer and
        therefore one ID space — still unique, attributed to the first
        rank (see :meth:`configure`)."""
        with self._lock:
            self._next_id += 1
            n = self._next_id
        return ((self.rank & 0xFFFF) << 32) | (n & 0xFFFFFFFF)

    # ------------------------------------------------------------------ #
    def _stack(self) -> List[int]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _anchor(self, prof: bool) -> None:
        """The two clocks' tie, once per capture: the profiler stamps its
        events from the start of its own session, the ring in
        ``time.time_ns()``. When a span first finds a capture running,
        write one ``mv.trace.anchor`` annotation whose ``time_ns``
        argument is the ring's clock at the annotation's start, and the
        same span into the ring: a reader of either converts with it."""
        self._anchored = prof
        if prof:
            with TraceAnnotation(ANCHOR):    # the first one is slow
                pass
            with TraceAnnotation(ANCHOR) as ann:
                t0 = time.time_ns()
                ann.set_metadata(time_ns=t0)
            self.record(ANCHOR, t0, time.time_ns(), time_ns=t0)

    def _record(self, name: str, t0_ns: int, t1_ns: int, span_id: int,
                parent: Optional[int], cause: Optional[int], request,
                cat: str, args: Dict[str, Any], prof: bool) -> None:
        ev = {
            "name": name, "cat": cat, "ph": "X",
            "ts": t0_ns / 1e3, "dur": max(t1_ns - t0_ns, 0) / 1e3,
            "pid": self.rank, "tid": threading.get_ident() & 0x7FFFFFFF,
            "id": span_id, "parent": parent, "cause": cause,
            "request": request, "prof": prof, "args": args,
        }
        # append under the lock: dump()'s snapshot-then-clear would
        # otherwise drop a span landing between its two steps
        with self._lock:
            self._events.append(ev)
            self._taken += 1
            self._steps["compiles"] += name == COMPILE

    def add_span(self, name: str, t0: float, t1: float,
                 trace: Optional[int] = None, cat: str = "ps",
                 args: Optional[Dict] = None) -> None:
        """A FINE site: record a span that has already ended; ``t0`` /
        ``t1`` are ``time.time()`` seconds. No-op with ``trace_ids`` off
        (callers pre-check :func:`enabled` to skip even the clock
        reads). ``trace`` is the request the span belongs to."""
        if not self.enabled:
            return
        a = dict(args) if args else {}
        if trace is not None:
            a["trace"] = trace
        self.record(name, int(t0 * 1e9), int(t1 * 1e9), request=trace,
                    cat=cat, **a)

    def record(self, name: str, t0_ns: int, t1_ns: int, *, request=None,
               cause: Optional[int] = None, cat: str = "prog",
               prof: Optional[bool] = None, **counts) -> None:
        """A COARSE span that has already ended (``time.time_ns()``
        stamps): what a listener or the watcher learns after the fact.
        Its parent is the span open on the calling thread; ``prof`` is
        whether a capture runs now, unless the caller saw for itself
        when the work began."""
        stack = self._stack()
        self._record(name, t0_ns, t1_ns, next(self._span_ids),
                     stack[-1] if stack else None, cause, request, cat,
                     counts, profiling() if prof is None else prof)

    def span(self, name: str, *, request=None, cause: Optional[int] = None,
             **counts) -> Span:
        """A COARSE span around a ``with`` block: always recorded, feeds
        the Dashboard monitor ``name``, and annotates the profiler's
        trace while one is captured. Not for per-request or
        per-minibatch sites."""
        return Span(self, name, request, cause, counts)

    # ------------------------------------------------------------------ #
    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def _step_opens(self, t0: float) -> int:
        """A step span opens at ``t0`` (microseconds): its thread's first
        is noted while it still runs, so a compile inside it is warm-up
        to every step that closes meanwhile. Returns the ring's count,
        from which the step's own fold reads."""
        with self._lock:
            self._first.setdefault(threading.get_ident() & 0x7FFFFFFF,
                                   (t0, float("inf")))
            return self._taken

    def _step_closes(self, step_id: int, mark: int) -> None:
        """A step span has closed and left its record: add its report
        (:func:`step_report`'s, over what the ring took since it opened)
        into the sums. The ring's lock is held for the copy of that tail
        alone. A step from under which a dump or the ring's bound took
        spans is left out of the sums; the file holds it whole."""
        with self._lock:
            n = self._taken - mark
            tail = (list(itertools.islice(reversed(self._events), n))
                    if 0 < n <= len(self._events) else ())
            first = dict(self._first)
        step = next((e for e in tail if e["id"] == step_id), None)
        report = _Steps(tail, first).report(step) if step else None
        with self._lock:
            tid = threading.get_ident() & 0x7FFFFFFF
            t0, t1 = self._first.get(tid, (0.0, 0.0))
            if t1 == float("inf"):
                self._first[tid] = (t0, time.time_ns() / 1e3)
            if report is not None:
                _add_step(self._steps, report)

    def step_summary(self) -> Optional[Dict[str, Any]]:
        """The ``profile`` block of a MSG_STATS payload: every step this
        process has closed since :meth:`reset`, each added up as it
        closed (cumulative, whatever a dump drained or the ring dropped
        since); ``None`` while no step span was recorded. A step is
        credited with what the ring held when it closed: a span that
        ends later (another thread's still open, a send-to-reply span
        recorded afterwards) is the file's report's to credit."""
        with self._lock:
            return profile_block(self._steps)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._next_id = 0
            self._taken, self._steps, self._first = 0, _no_steps(), {}
        self._rank_pinned = False

    def dump(self, path: str, append: bool = True) -> int:
        """Write buffered spans as JSONL; returns the event count. The
        buffer drains (a second dump appends only NEW spans), so the
        periodic exporter can stream without duplicating. The file write
        stays under the lock: two concurrent dumps to the same path
        (exporter tick racing a context-close flush) must not interleave
        their lines mid-record."""
        with self._lock:
            events, n = list(self._events), len(self._events)
            self._events.clear()
            if not events:
                return 0
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(path, "a" if append else "w") as f:
                for e in events:
                    f.write(json.dumps(e) + "\n")
        return n


TRACER = Tracer()


class DeviceWatcher:
    """Closes spans for asynchronous device work when the device is done.

    A dispatch returns before the device has run it, so a host span
    around the dispatch reads a millisecond for a block that holds the
    chip for hundreds. ``watch(name, array, ...)`` hands ``array`` (any
    output of the dispatched program) to ONE thread that waits on each
    in submission order (``block_until_ready`` releases the GIL) and
    records a coarse span (``cat`` ``"device"``) from the dispatch's
    start to the ready time, with the count ``dispatched``: the
    ``time.time_ns()`` at which ``watch`` was called, when the dispatch
    had returned and the runtime had the program. From ``dispatched`` to
    the span's end the program is in flight (:func:`device_timeline`).
    ``prof`` is taken at ``watch`` too: a program dispatched inside a
    captured window belongs to it wherever its end falls.

    Only while it can be read: with no profiler trace being captured and
    ``trace_ids`` off, ``watch`` does nothing, no thread exists and
    nothing waits. ``close()`` (or leaving the ``with`` block) waits for
    what was submitted and ends the thread."""

    def __init__(self) -> None:
        self._queue: Optional[queue.SimpleQueue] = None
        self._thread: Optional[threading.Thread] = None

    def watch(self, name: str, array: Any, t0_ns: int, *, request=None,
              cause: Optional[int] = None) -> None:
        prof = profiling()
        if not (TRACER.enabled or prof):
            return
        dispatched = time.time_ns()
        if self._thread is None:
            self._queue = queue.SimpleQueue()
            self._thread = threading.Thread(
                target=self._run, name="mv-trace-watcher", daemon=True)
            self._thread.start()
        self._queue.put((name, array, t0_ns, dispatched, request, cause,
                         prof))

    def _run(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            name, array, t0_ns, dispatched, request, cause, prof = item
            try:
                jax.block_until_ready(array)
            except Exception:   # noqa: BLE001 — a failed program is the
                continue        # caller's to raise; record no span for it
            TRACER.record(name, t0_ns, time.time_ns(), request=request,
                          cause=cause, cat="device", prof=prof,
                          dispatched=dispatched)

    def close(self) -> None:
        if self._thread is not None:
            self._queue.put(None)
            self._thread.join()
            self._thread = self._queue = None

    def __enter__(self) -> "DeviceWatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def self_ms(events: List[Dict]) -> Dict[int, float]:
    """Self time of every span in ``events``, by span ``id``, in ms: its
    duration minus the union of its children's intervals (children are
    the spans whose ``parent`` it is; each is clipped to the span)."""
    children: Dict[int, List[Tuple[float, float]]] = {}
    for e in events:
        if e.get("parent") is not None:
            children.setdefault(e["parent"], []).append(
                (e["ts"], e["ts"] + e["dur"]))
    out: Dict[int, float] = {}
    for e in events:
        lo, hi = e["ts"], e["ts"] + e["dur"]
        covered = union_length([(max(a, lo), min(b, hi))
                                for a, b in children.get(e["id"], ())])
        out[e["id"]] = (e["dur"] - covered) * 1e-3
    return out


NO_SPAN = "_no_span_open_"


def device_timeline(events: List[Dict], since: Optional[float] = None
                    ) -> Optional[Dict[str, Any]]:
    """The device's timeline as the host knew it, from span records alone.

    A ``cat == "device"`` span (:class:`DeviceWatcher`) is **in flight**
    from its ``dispatched`` count (its start, where a record has none)
    to its end: the device has work as far as the host can know. The
    device is **starved** wherever no program is in flight between
    ``since`` (by default the first ``dispatched``) and the last end. A
    starved interval's **owner** is the innermost ``cat == "prog"`` span
    open at its midpoint on the thread that made the next dispatch (the
    thread of that device span's ``cause``), or :data:`NO_SPAN`: the
    caller's own code between two calls. A device span's **run** is its
    end less the later of its ``dispatched`` and the previous end: the
    time the device had for it alone, queueing left out.

    Returns ``lo`` / ``hi`` (microseconds, the events' clock),
    ``in_flight`` (the union, ``[(a, b)]``), ``starved`` (``[(a, b,
    owner)]``), ``starved_s``, ``by_owner`` (seconds by owner) and
    ``runs`` (one ``{"name", "request", "id", "run_ms"}`` a device span,
    in order of their ends); ``None`` where there is no device span."""
    devs = sorted((e for e in events if e.get("cat") == "device"),
                  key=lambda e: e["ts"] + e["dur"])
    if not devs:
        return None
    flights = [(e["args"].get("dispatched", e["ts"] * 1e3) * 1e-3,
                e["ts"] + e["dur"]) for e in devs]
    runs, prev = [], float("-inf")
    for e, (a, b) in zip(devs, flights):
        runs.append({"name": e["name"], "request": e.get("request"),
                     "id": e["id"], "run_ms": (b - max(a, prev)) * 1e-3})
        prev = b
    lo = min(a for a, _ in flights) if since is None else since
    hi = flights[-1][1]
    in_flight = [(max(a, lo), b) for a, b in
                 union_intervals(flights) if b > lo]
    by_id = {e["id"]: e for e in events}
    by_tid: Dict[Any, List[Dict]] = {}      # host spans by thread, by start
    for e in sorted((e for e in events if e.get("cat") == "prog"),
                    key=lambda e: e["ts"]):
        by_tid.setdefault(e["tid"], []).append(e)
    starts = {tid: [e["ts"] for e in es] for tid, es in by_tid.items()}
    queued = {}                 # a flight's start -> its device span
    for e, (a, _) in zip(devs, flights):
        queued.setdefault(a, e)
    edges = [lo] + [x for iv in in_flight for x in iv] + [hi]
    starved, by_owner = [], {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        # the thread that queued the program whose dispatch ended the
        # wait; on it, the last span to start before the midpoint or the
        # nearest of its parents that is still open there
        tid = by_id.get(queued[b].get("cause"), {}).get("tid")
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts.get(tid, ()), mid) - 1
        e = by_tid[tid][i] if i >= 0 else None
        while e is not None and e["ts"] + e["dur"] < mid:
            e = by_id.get(e["parent"])
        owner = e["name"] if e is not None else NO_SPAN
        starved.append((a, b, owner))
        by_owner[owner] = by_owner.get(owner, 0.0) + (b - a) * 1e-6
    return {"lo": lo, "hi": hi, "in_flight": in_flight, "starved": starved,
            "starved_s": sum(by_owner.values()), "by_owner": by_owner,
            "runs": runs}


def _is_step(e: Dict) -> bool:
    return "step" in (e.get("args") or {})


def _touching(events: List[Dict]):
    """``events`` indexed for the question "which of you touch [lo, hi]":
    sorted by start, with the latest end so far, so the answer walks back
    from the last that starts in time and stops where none reaches."""
    es = sorted(events, key=lambda e: e["ts"])
    starts = [e["ts"] for e in es]
    reach = list(itertools.accumulate((e["ts"] + e["dur"] for e in es), max))

    def touching(lo: float, hi: float):
        i = bisect.bisect_right(starts, hi) - 1
        while i >= 0 and reach[i] >= lo:
            if es[i]["ts"] + es[i]["dur"] >= lo:
                yield es[i]
            i -= 1
    return touching


class _Steps:
    """What every step's report needs of one list of span records, made
    once: the children of every span, the work that can lie beside a
    step, the compiles by their ends and the threads' first steps."""

    def __init__(self, events: List[Dict],
                 first: Optional[Dict[Any, Tuple[float, float]]] = None):
        self.steps = sorted(filter(_is_step, events), key=lambda e: e["ts"])
        self.kids: Dict[int, List[Dict]] = {}
        for e in events:
            if e.get("parent") is not None:
                self.kids.setdefault(e["parent"], []).append(e)
        # every thread's first step, by thread: the caller's where it has
        # seen more of the run than these records hold
        if first is None:
            first = {}
            for s in self.steps:
                first.setdefault(s["tid"], (s["ts"], s["ts"] + s["dur"]))
        self.first = list(first.values())
        # beside a step: the top-level spans of the threads that run none
        self.beside_at = _touching([e for e in events if e["tid"] not in first
                                    and e.get("parent") is None])
        self.compiles = sorted((e for e in events if e["name"] == COMPILE),
                               key=lambda e: e["ts"] + e["dur"])
        self.compile_ends = [e["ts"] + e["dur"] for e in self.compiles]

    def report(self, s: Dict) -> Dict[str, Any]:
        lo, hi = s["ts"], s["ts"] + s["dur"]
        wall = max(s["dur"], 1e-3)
        # the step's tree: its descendants, each with the phase it is
        # filed under and whether it carries that name itself
        tree, todo = [], [(c, None) for c in self.kids.get(s["id"], ())]
        while todo:
            e, above = todo.pop()
            own = e["args"].get("phase")
            tree.append((e, own or above or e["name"], bool(own or not above)))
            todo += [(c, own or above) for c in self.kids.get(e["id"], ())]
        self_time = self_ms([e for e, _, _ in tree])
        phases: Dict[str, Dict[str, Any]] = {}
        for e, name, marks in tree:
            d = phases.setdefault(name, {"ms": 0.0, "count": 0})
            d["ms"] += self_time[e["id"]]
            d["count"] += marks
        under = union_intervals(
            [iv for iv in (clip(c["ts"], c["ts"] + c["dur"], lo, hi)
                           for c in self.kids.get(s["id"], ())) if iv])
        beside: Dict[str, Dict[str, Any]] = {}
        covered = list(under)
        for e in self.beside_at(lo, hi):
            iv = clip(e["ts"], e["ts"] + e["dur"], lo, hi)
            if iv is None:
                continue
            covered.append(iv)
            d = beside.setdefault(e["name"], {"ms": 0.0, "overlap_ms": 0.0,
                                              "count": 0, "open": 0})
            d["ms"] += (iv[1] - iv[0]) * 1e-3
            d["overlap_ms"] += intersect_disjoint(iv, under) * 1e-3
            d["count"] += 1
            d["open"] += e["ts"] + e["dur"] > hi
        attributed = union_length(covered)
        ends = self.compile_ends
        i, j = bisect.bisect_left(ends, lo), bisect.bisect_right(ends, hi)
        compiles = [
            {"id": c["id"], "fun": c["args"].get("fun", ""),
             "seconds": c["args"].get("seconds", c["dur"] * 1e-6),
             "steady": not any(a <= t <= b for a, b in self.first)}
            for c, t in zip(self.compiles[i:j], ends[i:j])]
        return {
            "name": s["name"], "request": s.get("request"),
            "rank": s.get("pid", 0), "tid": s["tid"], "ts": lo,
            "wall_ms": wall * 1e-3,
            "attributed_ms": attributed * 1e-3,
            "attributed_fraction": min(attributed / wall, 1.0),
            "overlap_ms": sum(d["overlap_ms"] for d in beside.values()),
            "stall_ms": max(wall - attributed, 0.0) * 1e-3,
            "stall_fraction": max(wall - attributed, 0.0) / wall,
            "phases": dict(sorted(phases.items())),
            "async": dict(sorted(beside.items())),
            "compiles": compiles,
        }


def step_report(events: List[Dict]) -> List[Dict[str, Any]]:
    """One report a step span (a span with the count ``step``), from span
    records alone, oldest first (``step``: its place in that order).

    ``wall_ms`` is the step's duration. ``phases`` is the self time
    (:func:`self_ms`) of the spans under it on its thread, each filed
    under the nearest ``phase`` count on it or above it inside the step,
    or under its own name: ``{name: {"ms", "count"}}``, ``count`` the
    spans that carry the name themselves. ``async`` is what ran beside
    it: the work of the threads that run no step of their own (a
    producer's ``we.prepare``, a recv thread's send-to-reply span, the
    watcher's device span), each thread's taken by its top-level spans
    (no parent: what is nested lies inside its root's interval), those
    that intersect the step, clipped to it: ``{name: {"ms",
    "overlap_ms", "count", "open"}}``, ``overlap_ms`` the part under
    the step's own spans (work the wait for it did not cost) and
    ``open`` those that outlast the step. A thread that runs steps is a
    trainer and none of its spans is beside anybody's step: the span
    its steps run inside (``we.blocks`` round its ``we.step``'s) covers
    them whole by construction. ``attributed_ms`` is the union of the
    step's own spans and what ran beside it, ``stall_ms`` the wall time
    nothing claims, each with its fraction of the wall.
    ``compiles`` lists the ``xla.compile`` records that ended inside the
    step, any thread: ``{"id", "fun", "seconds", "steady"}``. A compile
    is **steady** iff it ended inside some step and inside no thread's
    FIRST step: a rule per compile, so one warm-up compile shared by two
    trainers' first steps is no recompile of either."""
    index = _Steps(events)
    return [dict(index.report(s), step=i) for i, s in enumerate(index.steps)]


def _add_step(totals: Dict[str, Any], report: Dict[str, Any]) -> None:
    """One step's report added into ``totals`` (:func:`_no_steps`)."""
    totals["steps"] += 1
    for k in ("wall_ms", "attributed_ms", "stall_ms", "overlap_ms"):
        totals[k] += report[k]
    for name, d in report["phases"].items():
        totals["phases"][name] = totals["phases"].get(name, 0.0) + d["ms"]
    totals["steady"].update(c["id"] for c in report["compiles"]
                            if c["steady"])


def step_totals(events: List[Dict]) -> Dict[str, Any]:
    """:func:`step_report` over ``events`` added up: ``steps``,
    ``wall_ms``, ``attributed_ms``, ``stall_ms``, ``overlap_ms``,
    ``phases`` (ms by name), ``steady`` (the ids of the steady compiles:
    each once, however many steps it ended inside) and ``compiles``
    (every ``xla.compile`` record, inside a step or not)."""
    totals = _no_steps()
    for r in step_report(events):
        _add_step(totals, r)
    totals["compiles"] = sum(e["name"] == COMPILE for e in events)
    return totals


def profile_block(totals: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The six keys MSG_STATS carries as ``profile`` (the aggregator, the
    ``stall_fraction`` objective and the straggler verdict of
    ``telemetry/slo.py``, ``tools/mvtop.py``); ``None`` without a step."""
    if not totals["steps"]:
        return None
    wall = totals["wall_ms"] or 1e-6
    return {"steps": totals["steps"],
            "stall_fraction": round(totals["stall_ms"] / wall, 4),
            "attributed_fraction": round(totals["attributed_ms"] / wall, 4),
            "steady_recompiles": len(totals["steady"]),
            "compiles": totals["compiles"],
            "phases": {n: round(v, 3)
                       for n, v in sorted(totals["phases"].items())}}


def enabled() -> bool:
    """THE hot-path gate of the fine sites (attribute read, no locks)."""
    return TRACER.enabled


def configure(rank: Optional[int] = None) -> None:
    TRACER.configure(rank)


def new_id() -> int:
    return TRACER.new_id()


def add_span(name: str, t0: float, t1: float, trace: Optional[int] = None,
             cat: str = "ps", args: Optional[Dict] = None) -> None:
    TRACER.add_span(name, t0, t1, trace=trace, cat=cat, args=args)


def record(name: str, t0_ns: int, t1_ns: int, *, request=None,
           cause: Optional[int] = None, cat: str = "prog",
           **counts) -> None:
    TRACER.record(name, t0_ns, t1_ns, request=request, cause=cause,
                  cat=cat, **counts)


def span(name: str, *, request=None, cause: Optional[int] = None,
         **counts) -> Span:
    return TRACER.span(name, request=request, cause=cause, **counts)


def events() -> List[Dict]:
    return TRACER.events()


def step_summary() -> Optional[Dict[str, Any]]:
    return TRACER.step_summary()


def trace_path(directory: str, rank: Optional[int] = None) -> str:
    """Canonical per-rank trace file path under a metrics dir."""
    r = TRACER.rank if rank is None else rank
    return os.path.join(directory, f"trace-rank{r}.jsonl")


def dump_to(directory: str) -> int:
    """Dump buffered spans to the canonical per-rank file (no-op and 0
    when nothing was recorded)."""
    return TRACER.dump(trace_path(directory))
