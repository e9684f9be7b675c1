"""Device-plane observability: transfers, collectives, mesh-keyed compiles.

Every observability plane built so far (PRs 3/4/6/9/10) measures the
HOST plane — wire latency, step phases, bytes in Python-owned buffers.
The scale-out work (ROADMAP item 1: N-shard topologies on a device
mesh; item 4: the PS-bypassing allreduce plane) is judged by
DEVICE-plane costs this rank could not see: host<->device transfer
bytes, which mesh configuration triggered a recompile, where the live
device bytes sit, and what each collective moved. This module is that
layer — four gauges sharing the flight-recorder's cost discipline
(cheap increments at instrumented sites, everything else pull-only):

* **Transfer chokepoint** — :func:`note_transfer` counts host<->device
  bytes PER DIRECTION (``h2d``/``d2h``). It generalizes the PR-9
  instrumented-site accounting into one funnel: the word-embedding and
  DLRM pipelines, ``sequence_shard``/``shard_params`` device_puts, and
  ``process_sum``'s round trip all report here.
* **Mesh-keyed compile events** — the package's one ``jax.monitoring``
  duration listener attributes every backend compile
  to the ACTIVE mesh shape: :func:`mesh_scope` (collective spans push
  it automatically) or the Zoo's :func:`set_default_mesh`. A recompile
  now names which mesh configuration triggered it — the signal the
  1->2->4->8 scale harness keys its compile accounting on.
* **Per-device census rollup** — :func:`device_rollup` groups the
  PR-10 ``jax.live_arrays()`` census BY DEVICE (sharded arrays are
  attributed per addressable shard), so "which chip holds the bytes"
  is a stats pull, not a forensic dump.
* **Collective spans** — :func:`collective_span` wraps every
  ``parallel/`` collective entry point: op/bytes/duration land as
  Dashboard monitors (``coll[op]`` timed + ``.calls``/``.bytes``
  counters in the zoo shutdown report), flight-recorder
  ``coll.begin``/``coll.end`` events, one coarse ``coll.<op>`` span
  (what a step's report counts as work beside it,
  ``trace.step_report``), and this module's per-op tally. Durations are
  HOST dispatch+compile wall time — jax dispatch is async, so a
  non-blocking caller's span excludes device execution (same caveat
  as every Dashboard monitor around jitted code).

The rollup rides MSG_STATS as the ``"devices"`` block
(:func:`stats_snapshot`): ``aggregator.merge_cluster`` merges it per
rank with (host, pid)-deduped cluster totals, ``tools/mvtop.py`` grows
a device panel, ``tools/dump_metrics.py`` renders it, and the exporter
emits ``mv_dev_*`` Prometheus gauges. A payload WITHOUT the block (an
older peer in a mixed-version cluster) renders as "-" everywhere — the
block is additive, never required.

**Compile hygiene** (the scale-out gate): :func:`capture_hygiene`
scopes a structured ``warnings`` + jax-logger capture around dryrun
compiles and classifies SPMD remat / sharding-fallback / donation
warnings into a machine-readable report keyed (jitted fn, mesh shape).
``tools/bench_scale.py`` asserts the report CLEAN in-run for the
shipped workload at every mesh shape; :func:`dump_hygiene` writes
``compile-hygiene-rank<r>.json`` for ``tools/mvprof.py``.

**The compiled program's map** (:func:`describe_program`,
:func:`scope_seconds`): a device trace names an operation by its HLO
instruction and carries no ``jax.named_scope``; the compiled program's
text carries both. A trainer hands its jitted program over once, after
its first call, and one coarse ``xla.program`` record says which
``mv.*`` scope and which pass (forward, made again under
``jax.checkpoint``, backward) each instruction belongs to and what the
program reserves of the device; the join of
that map with a trace's operations is :func:`scope_seconds`
(``tools/dump_metrics.py scopes``). ``xla.compile`` carries the seconds
of tracing and lowering that led to each compile.

Cost discipline: the ``devstats`` flag (default ON) gates every
recording site behind one attribute read; counters are one int add
under a lock at per-batch (not per-row) sites; the live-arrays walk
runs only on a stats pull. ``tools/bench_small_add.py`` asserts the
PR-2 0.03-0.06 ms small-add band in-run with the plane live.
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
import traceback
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.utils import config, log
from multiverso_tpu.utils.intervals import union_length

config.define_bool(
    "devstats", True,
    "device-plane observability (telemetry/devstats.py): host<->device "
    "transfer byte counters, per-mesh-shape compile attribution, "
    "collective op spans (Dashboard coll[op] monitors + flightrec "
    "coll.begin/end + one coll.<op> trace span), and the per-device "
    "live-arrays rollup in the MSG_STATS 'devices' block. On by "
    "default: one attribute read gates every site; the live-arrays "
    "walk runs only on a stats pull, never on a hot path")

# directions the transfer chokepoint accepts — anything else raises at
# the instrumented site (a typo'd direction must not open a third,
# never-rendered counter)
_DIRECTIONS = ("h2d", "d2h")

# compile events with no mesh scope active (host-plane jits, warmup
# before any mesh exists) key under this label
_NO_MESH = "unmeshed"

# the jax.monitoring events of Python's part of a compile, by the last
# component of their names, and the xla.compile count each adds up into
_LED_TO_COMPILE = {"jaxpr_trace_duration": "trace_s",
                   "jaxpr_to_mlir_module_duration": "lower_s"}


# ---------------------------------------------------------------------- #
# mesh labels
# ---------------------------------------------------------------------- #
def mesh_label(mesh: Any) -> str:
    """Canonical label for a mesh configuration: ``"{'mv': 4}"`` for a
    ``jax.sharding.Mesh``; dicts/strings pass through (bench harnesses
    and tests hand shapes around without building a Mesh)."""
    if mesh is None:
        return _NO_MESH
    if isinstance(mesh, str):
        return mesh
    if isinstance(mesh, dict):
        return str(dict(mesh))
    names = getattr(mesh, "axis_names", None)
    devs = getattr(mesh, "devices", None)
    if names is not None and devs is not None:
        return str(dict(zip(names, devs.shape)))
    return str(mesh)


# ---------------------------------------------------------------------- #
# compile-hygiene classification (pure; oracle-tested)
# ---------------------------------------------------------------------- #
# category -> lowercase substrings; first hit wins, in order — remat and
# sharding fallbacks are the SPMD warnings the scale harness gates on,
# donation is the PR-9 signal lifted to the same report
_HYGIENE_PATTERNS = (
    ("remat", ("remat", "rematerial")),
    ("sharding-fallback", ("could not infer sharding",
                           "falling back to replicat",
                           "fully replicated",
                           "sharding propagation",
                           "resharding",
                           "spmd partition")),
    ("donation", ("donated buffers were not usable",)),
    ("spmd", ("spmd",)),
)


def classify_compile_warning(message: str) -> Optional[str]:
    """SPMD-hygiene category for one warning/log message, or None for
    noise (deprecations, user warnings) that is NOT a compile-hygiene
    finding. Substring match, case-insensitive — the exact wordings
    move across jax/XLA versions, the vocabulary does not."""
    low = str(message).lower()
    for cat, needles in _HYGIENE_PATTERNS:
        for n in needles:
            if n in low:
                return cat
    return None


class _LogTap(logging.Handler):
    """Captures jax-logger records during a hygiene scope (XLA routes
    some SPMD diagnostics through logging, not warnings)."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.messages: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self.messages.append(record.getMessage())
        except Exception:   # noqa: BLE001 — a bad log record must not
            pass            # fail the compile it decorates


# ---------------------------------------------------------------------- #
# device census rollup (pull-only; injectable for tests)
# ---------------------------------------------------------------------- #
def device_rollup(arrays: Optional[List[Any]] = None
                  ) -> Optional[Dict[str, Dict[str, int]]]:
    """Live JAX buffers grouped BY DEVICE: ``{device: {"bytes",
    "arrays"}}``. Sharded arrays are attributed per addressable shard
    (each device is charged exactly the bytes it holds); ``arrays``
    injects a fixture list so the grouping is testable without a live
    backend. None when JAX is unavailable; {} when nothing is live."""
    if arrays is None:
        try:
            import jax
            arrays = jax.live_arrays()
        except Exception:   # noqa: BLE001 — census is best-effort
            return None
    per: Dict[str, List[int]] = {}
    for a in arrays:
        try:
            shards = getattr(a, "addressable_shards", None)
            if shards:
                for s in shards:
                    g = per.setdefault(str(s.device), [0, 0])
                    g[0] += int(s.data.nbytes)
                    g[1] += 1
            else:
                dev = ",".join(sorted(str(d) for d in a.devices()))
                g = per.setdefault(dev, [0, 0])
                g[0] += int(a.nbytes)
                g[1] += 1
        except Exception:   # noqa: BLE001 — a buffer donated/deleted
            continue        # mid-walk must not fail the rollup
    return {d: {"bytes": b, "arrays": n}
            for d, (b, n) in sorted(per.items())}


# ---------------------------------------------------------------------- #
# the span / scope contexts
# ---------------------------------------------------------------------- #
class _NullCtx:
    """Shared no-op context — the flag-off path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


class _MeshScope:
    __slots__ = ("_ds", "_label")

    def __init__(self, ds: "DevStats", label: str):
        self._ds = ds
        self._label = label

    def __enter__(self):
        stack = getattr(self._ds._tls, "mesh_stack", None)
        if stack is None:
            stack = self._ds._tls.mesh_stack = []
        stack.append(self._label)
        return self._label

    def __exit__(self, *exc):
        try:
            self._ds._tls.mesh_stack.pop()
        except (AttributeError, IndexError):
            pass
        return False


class _CollSpan:
    """One collective op's span: Dashboard + flightrec + the ring +
    the per-op tally, and a mesh scope so a compile triggered inside
    is keyed to the op's mesh."""

    __slots__ = ("_ds", "_op", "_nbytes", "_scope", "_t0")

    def __init__(self, ds: "DevStats", op: str, nbytes: int,
                 label: Optional[str]):
        self._ds = ds
        self._op = op
        self._nbytes = int(nbytes)
        self._scope = (_MeshScope(ds, label) if label is not None
                       else None)

    def __enter__(self):
        from multiverso_tpu.telemetry import flightrec as _flight
        if self._scope is not None:
            self._scope.__enter__()
        self._t0 = time.time_ns()
        _flight.record(_flight.EV_COLL_BEGIN, nbytes=self._nbytes,
                       note=f"coll.{self._op}")
        return self

    def __exit__(self, *exc):
        from multiverso_tpu.telemetry import flightrec as _flight
        from multiverso_tpu.utils.dashboard import Dashboard
        t1 = time.time_ns()
        if self._scope is not None:
            self._scope.__exit__()
        ms = (t1 - self._t0) * 1e-6
        with self._ds._lock:
            d = self._ds._coll.setdefault(
                self._op, {"calls": 0, "bytes": 0, "ms": 0.0})
            d["calls"] += 1
            d["bytes"] += self._nbytes
            d["ms"] = round(d["ms"] + ms, 4)
        Dashboard.get(f"coll[{self._op}]").observe_ms(ms)
        Dashboard.get(f"coll[{self._op}].calls").incr()
        Dashboard.get(f"coll[{self._op}].bytes").incr(self._nbytes)
        _flight.record(_flight.EV_COLL_END, nbytes=self._nbytes,
                       note=f"coll.{self._op}")
        # the wire-hiding question for collectives is the same as for
        # PS round-trips: a step open on any thread counts it beside it
        _trace.record(f"coll.{self._op}", self._t0, t1, nbytes=self._nbytes)
        return False


# ---------------------------------------------------------------------- #
# the process-global gauge set
# ---------------------------------------------------------------------- #
class DevStats:
    """One per process (like the FlightRecorder/Tracer);
    in-process multi-rank worlds share it — the same documented
    collapse, deduped by (host, pid) in the cluster merge."""

    def __init__(self) -> None:
        self.enabled = True       # plain attribute: THE site gate
        self.rank = 0
        self._rank_pinned = False
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._default_mesh: Optional[str] = None
        # direction -> [ops, bytes]
        self._transfers: Dict[str, List[int]] = {
            d: [0, 0] for d in _DIRECTIONS}
        # op -> {"calls", "bytes", "ms"}
        self._coll: Dict[str, Dict[str, Any]] = {}
        # mesh label -> {"compiles", "compile_s"}
        self._compiles: Dict[str, Dict[str, Any]] = {}
        self._listener_installed = False
        # hygiene report: entries + per-scope check log
        self._hygiene: List[Dict[str, Any]] = []
        self._hygiene_checked: List[Dict[str, Any]] = []

    # ------------------------------------------------------------------ #
    def configure(self, rank: Optional[int] = None) -> None:
        """Adopt the ``devstats`` flag (PSService init / Zoo.start);
        idempotent, first caller's rank sticks."""
        if rank is not None and not self._rank_pinned:
            self.rank = int(rank)
            self._rank_pinned = True
        self.enabled = bool(config.get_flag("devstats"))
        if self.enabled:
            self._install_listener()

    def _install_listener(self) -> None:
        with self._lock:
            if self._listener_installed:
                return
            self._listener_installed = True
        try:
            import jax.monitoring as _jm
            _jm.register_event_duration_secs_listener(self._on_duration)
        except Exception:   # noqa: BLE001 — device telemetry must
            pass            # degrade, not break, on exotic builds

    def _on_duration(self, name: str, dur: float, **kw) -> None:
        # each compile is keyed to the active mesh shape and leaves one
        # coarse xla.compile span (telemetry/trace.py), which is what
        # trace.step_report reads a step's recompiles from
        if not self.enabled:
            return
        if name.endswith("cache_retrieval_time_sec"):
            # fires inside the compile event, on the same thread, when
            # the executable came from the persistent cache
            self._tls.cache_load = True
            return
        led = _LED_TO_COMPILE.get(name.rpartition("/")[2])
        if led is not None:
            # Python's part of a compile: tracing (one event for every
            # nested jit, hundreds a step) and lowering, on this thread,
            # fold into the compile they lead to
            setattr(self._tls, led, getattr(self._tls, led, 0.0) + float(dur))
            return
        if not name.endswith("backend_compile_duration"):
            return
        label = self._mesh_label()
        with self._lock:
            d = self._compiles.setdefault(
                label, {"compiles": 0, "compile_s": 0.0})
            d["compiles"] += 1
            d["compile_s"] = round(d["compile_s"] + float(dur), 6)
        loaded = getattr(self._tls, "cache_load", False)
        self._tls.cache_load = False
        trace_s, lower_s = self._take_led()
        t1 = time.time_ns()
        _trace.record("xla.compile", t1 - int(float(dur) * 1e9), t1,
                      seconds=float(dur), mesh=label,
                      event="cache_load" if loaded else "compile",
                      fun=str(kw.get("fun_name", "")),
                      trace_s=trace_s, lower_s=lower_s)

    def _take_led(self, put: Tuple[float, float] = (0.0, 0.0)
                  ) -> Tuple[float, float]:
        """This thread's seconds of tracing and lowering since its last
        compile event, replaced by ``put``."""
        tls = self._tls
        had = (getattr(tls, "trace_s", 0.0), getattr(tls, "lower_s", 0.0))
        tls.trace_s, tls.lower_s = put
        return had

    def compile_events(self) -> int:
        """Compile events heard so far (cache loads among them), all
        meshes."""
        with self._lock:
            return sum(d["compiles"] for d in self._compiles.values())

    # ------------------------------------------------------------------ #
    # mesh context
    # ------------------------------------------------------------------ #
    def _mesh_label(self) -> str:
        stack = getattr(self._tls, "mesh_stack", None)
        if stack:
            return stack[-1]
        return self._default_mesh or _NO_MESH

    def mesh_scope(self, mesh: Any):
        """Key compiles fired inside this scope (on this thread) to
        ``mesh``'s shape. Collective spans push one automatically."""
        if not self.enabled:
            return _NULL
        return _MeshScope(self, mesh_label(mesh))

    def set_default_mesh(self, mesh: Any) -> None:
        """Process-default mesh label (Zoo.start's adopted mesh) for
        compiles with no explicit scope on their thread."""
        self._default_mesh = mesh_label(mesh) if mesh is not None else None

    # ------------------------------------------------------------------ #
    # recording sites
    # ------------------------------------------------------------------ #
    def note_transfer(self, nbytes: int, direction: str = "h2d") -> None:
        """THE host<->device transfer chokepoint."""
        if direction not in _DIRECTIONS:
            raise ValueError(f"direction {direction!r}: expected one of "
                             f"{_DIRECTIONS}")
        if self.enabled:
            with self._lock:
                g = self._transfers[direction]
                g[0] += 1
                g[1] += int(nbytes)

    def collective_span(self, op: str, nbytes: int, mesh: Any = None):
        """Span context for one collective call — see module
        docstring. No-op (shared context, no allocation) when the
        ``devstats`` flag is off."""
        if not self.enabled:
            return _NULL
        return _CollSpan(self, op, nbytes,
                         mesh_label(mesh) if mesh is not None else None)

    # ------------------------------------------------------------------ #
    # compile hygiene
    # ------------------------------------------------------------------ #
    def capture_hygiene(self, fn: str, mesh: Any = None):
        """Scope a dryrun compile: captured ``warnings`` + jax-logger
        messages are classified (:func:`classify_compile_warning`) and
        classified hits land in the report keyed (``fn``, mesh shape).
        Returns the context manager; the report accumulates across
        scopes until :meth:`reset`."""
        return _HygieneScope(self, fn,
                             mesh_label(mesh) if mesh is not None
                             else self._mesh_label())

    def _hygiene_commit(self, fn: str, label: str,
                        messages: List[str]) -> List[Dict[str, Any]]:
        entries = []
        for m in messages:
            cat = classify_compile_warning(m)
            if cat:
                entries.append({"fn": fn, "mesh": label,
                                "category": cat,
                                "message": str(m)[:240]})
        with self._lock:
            self._hygiene_checked.append(
                {"fn": fn, "mesh": label, "captured": len(messages),
                 "findings": len(entries)})
            self._hygiene.extend(entries)
        return entries

    def hygiene_report(self) -> Dict[str, Any]:
        """The machine-readable compile-hygiene report: every scoped
        dryrun checked, every classified finding, and the headline
        ``clean`` verdict ``bench_scale`` asserts in-run."""
        with self._lock:
            return {"clean": not self._hygiene,
                    "checked": list(self._hygiene_checked),
                    "findings": list(self._hygiene)}

    def dump_hygiene(self, directory: str,
                     rank: Optional[int] = None) -> str:
        """Write ``compile-hygiene-rank<r>.json`` (atomic replace) for
        ``tools/mvprof.py``; returns the path."""
        r = self.rank if rank is None else rank
        rep = self.hygiene_report()
        rep["rank"] = r
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"compile-hygiene-rank{r}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rep, f, indent=1)
        os.replace(tmp, path)
        return path

    # ------------------------------------------------------------------ #
    # reads
    # ------------------------------------------------------------------ #
    def stats_snapshot(self) -> Optional[Dict[str, Any]]:
        """The MSG_STATS ``"devices"`` block: per-direction transfer
        counters, per-op collective tallies, per-mesh-shape compile
        events, and the per-device live-buffer rollup. None when the
        flag is off AND when there is nothing to report (no activity,
        no live buffers) — older-peer payloads simply lack the block,
        and every renderer degrades to "-"."""
        if not self.enabled:
            return None
        with self._lock:
            transfers = {d: {"ops": g[0], "bytes": g[1]}
                         for d, g in self._transfers.items() if g[0]}
            colls = {op: dict(d) for op, d in self._coll.items()}
            compiles = {k: dict(v) for k, v in self._compiles.items()}
            findings = len(self._hygiene)
        per_device = device_rollup()
        # findings count as activity: a rank whose compiles all hit the
        # persistent cache can still carry a DIRTY hygiene report, and
        # omitting the block would keep mvtop's HYGIENE FINDINGS header
        # and mv_dev_hygiene_findings dark exactly when they matter
        if not (transfers or colls or compiles or per_device
                or findings):
            return None
        out: Dict[str, Any] = {"transfers": transfers,
                               "collectives": colls,
                               "compiles_by_mesh": compiles}
        if per_device:
            out["per_device"] = per_device
        if findings:
            out["hygiene_findings"] = findings
        return out

    def reset(self) -> None:
        """Test isolation: drop counters/report and unpin; the jax
        listener stays installed (idempotent, costs one substring
        check per compile) and re-reads ``self.enabled``."""
        with self._lock:
            self._transfers = {d: [0, 0] for d in _DIRECTIONS}
            self._coll.clear()
            self._compiles.clear()
            self._hygiene.clear()
            self._hygiene_checked.clear()
            self._rank_pinned = False
            self.rank = 0
            self._default_mesh = None
        self._tls = threading.local()
        self.enabled = True


class _HygieneScope:
    __slots__ = ("_ds", "_fn", "_label", "_wctx", "_caught", "_tap",
                 "_loggers", "_mesh_scope", "entries")

    def __init__(self, ds: DevStats, fn: str, label: str):
        self._ds = ds
        self._fn = fn
        self._label = label
        self.entries: List[Dict[str, Any]] = []

    def __enter__(self):
        self._wctx = warnings.catch_warnings(record=True)
        self._caught = self._wctx.__enter__()
        warnings.simplefilter("always")
        self._tap = _LogTap()
        # ONE tap on the root "jax" logger: every jax._src.* record
        # reaches it via logger propagation, and a second handler on
        # "jax._src" double-counted each SPMD diagnostic in the report
        self._loggers = [logging.getLogger("jax")]
        for lg in self._loggers:
            lg.addHandler(self._tap)
        self._mesh_scope = _MeshScope(self._ds, self._label)
        self._mesh_scope.__enter__()
        return self

    def __exit__(self, *exc):
        self._mesh_scope.__exit__()
        for lg in self._loggers:
            lg.removeHandler(self._tap)
        messages = [str(w.message) for w in self._caught]
        self._wctx.__exit__(*exc)
        messages += self._tap.messages
        self.entries = self._ds._hygiene_commit(
            self._fn, self._label, messages)
        return False


DEVSTATS = DevStats()


# module-level wrappers (the call-site idiom, like telemetry.trace)
def enabled() -> bool:
    return DEVSTATS.enabled


def configure(rank: Optional[int] = None) -> None:
    DEVSTATS.configure(rank)


def note_transfer(nbytes: int, direction: str = "h2d") -> None:
    DEVSTATS.note_transfer(nbytes, direction)


def collective_span(op: str, nbytes: int, mesh: Any = None):
    return DEVSTATS.collective_span(op, nbytes, mesh=mesh)


def mesh_scope(mesh: Any):
    return DEVSTATS.mesh_scope(mesh)


def set_default_mesh(mesh: Any) -> None:
    DEVSTATS.set_default_mesh(mesh)


def capture_hygiene(fn: str, mesh: Any = None):
    return DEVSTATS.capture_hygiene(fn, mesh=mesh)


def hygiene_report() -> Dict[str, Any]:
    return DEVSTATS.hygiene_report()


def dump_hygiene(directory: str, rank: Optional[int] = None) -> str:
    return DEVSTATS.dump_hygiene(directory, rank=rank)


def stats_snapshot() -> Optional[Dict[str, Any]]:
    return DEVSTATS.stats_snapshot()


def reset() -> None:
    DEVSTATS.reset()


# ---------------------------------------------------------------------- #
# the compiled program's map: instruction -> scope and pass
# ---------------------------------------------------------------------- #
PROGRAM_SPAN = "xla.program"
# where scope_seconds files an operation whose instruction has no mv.*
# scope in its path / is in no program's map / is claimed for two places
UNSCOPED, UNKNOWN, AMBIGUOUS = "_unscoped_", "_unknown_", "_ambiguous_"
PASSES = ("fwd", "remat", "bwd")
NO_PASS = "-"               # the pass of what is in no map
# a Pallas kernel is filed apart from XLA's instructions of its scope
# ("mv.lm.attn:kernel"): a kernel-only reader's sum then has its row
KERNEL = ":kernel"
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

# not operations of their own on the device
_NOT_OPS = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast"))
# the instructions whose called computations the device runs as
# operations of their own (a fusion's, a reduce's, a sort's are inside it)
_CALLERS = frozenset(("while", "call", "conditional", "async-start"))
_HEADER = re.compile(r"(ENTRY )?%?([^\s(]+) \(")
_INSTR = re.compile(r"\s+(?:ROOT )?%?(\S+) = ")
_TUPLE_OPCODE = re.compile(r"\) ([a-z][\w\-]*)\(")
# a result shape as benchmark/trace_reduce.result_shape reads it off a
# trace event: the first shape after " = ", layout and tiling left out
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16|c64|c128)\[([0-9,]*)\]")
_CALLED = re.compile(
    r"\b(?:condition|body|to_apply|calls|true_computation|false_computation)"
    r"=%?([^\s,)}]+)|branch_computations=\{([^}]*)\}")
_SCOPE = re.compile(r"(?<![\w.])mv\.[\w.\-]+")
_OP_NAME = 'op_name="'


def _shape_in(text: str, lo: int = 0, hi: Optional[int] = None) -> str:
    m = (_SHAPE.search(text, lo) if hi is None
         else _SHAPE.search(text, lo, hi))
    return f"{m.group(1)}[{m.group(2)}]" if m else ""


def place_of(op_name: str) -> Tuple[str, str]:
    """(scope, pass) of an instruction from its metadata's ``op_name``
    (``jit(step)/transpose(jvp(mv.lm.head))/dot_general``): the last
    ``mv.*`` name in the path, :data:`UNSCOPED` where there is none;
    ``remat`` under ``jax.checkpoint``'s ``rematted_computation``, else
    ``bwd`` under a ``transpose(``, else ``fwd``."""
    found = _SCOPE.findall(op_name)
    return (found[-1] if found else UNSCOPED,
            "remat" if "rematted_computation" in op_name
            else "bwd" if "transpose(" in op_name else "fwd")


def program_map(text: str) -> Dict[str, Any]:
    """What a compiled program's text (``Compiled.as_text()``) says of
    the instructions the device runs as operations of their own: those
    of the entry computation and of every ``while`` body and condition,
    called computation and branch reached from it, the insides of fused
    computations and the opcodes of :data:`_NOT_OPS` left out.

    A fusion whose own metadata is empty (one with several results: its
    root is a ``tuple``) takes the path of the last instruction inside
    it that has one; a Pallas kernel's scope ends in :data:`KERNEL`.

    Returns ``module``, ``instructions``, ``scoped`` (those with an
    ``mv.*`` scope) and ``scopes``: ``{scope: {pass: [[name, shape],
    ...]}}``, the shape as a trace prints it (``f32[4096]``; of a tuple
    its first)."""
    module = ""
    # a computation's instructions: (name, shape, op_name, the fused
    # computation to ask where op_name is empty, whether a kernel)
    bodies: Dict[str, List[Tuple[str, str, str, str, bool]]] = {}
    calls: Dict[str, List[str]] = {}
    last_path: Dict[str, str] = {}      # by computation, fused ones too
    entry = current = None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] != " ":
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
            elif line.endswith("{"):
                head = _HEADER.match(line)
                if head:
                    current = head.group(2)
                    bodies[current], calls[current] = [], []
                    if head.group(1):
                        entry = current
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        at = m.end()
        if line[at] == "(":         # a tuple: the opcode follows its ")"
            found = _TUPLE_OPCODE.search(line, at)
            if found is None:
                continue
            opcode, paren = found.group(1), found.end() - 1
        else:
            space = line.find(" ", at)
            paren = line.find("(", space)
            opcode = line[space + 1:paren]
        i = line.find(_OP_NAME, paren)
        op_name = (line[i + len(_OP_NAME):line.find('"', i + len(_OP_NAME))]
                   if i >= 0 else "")
        if op_name:
            last_path[current] = op_name
        if opcode in _NOT_OPS:
            continue
        inside = ""
        if opcode in _CALLERS:
            for one, many in _CALLED.findall(line, paren):
                calls[current] += [one] if one else [
                    c.strip().lstrip("%") for c in many.split(",")]
        elif opcode == "fusion" and not op_name:
            found = _CALLED.search(line, paren)
            inside = found.group(1) if found and found.group(1) else ""
        bodies[current].append(
            (m.group(1), _shape_in(line, at, paren), op_name, inside,
             opcode == "custom-call" and _KERNEL_TARGET in line))
    scopes: Dict[str, Dict[str, List[List[str]]]] = {}
    instructions = scoped = 0
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in bodies:
            continue
        seen.add(comp)
        todo += calls[comp]
        for name, shape, op_name, inside, kernel in bodies[comp]:
            scope, pas = place_of(op_name or last_path.get(inside, ""))
            instructions += 1
            if scope != UNSCOPED:
                scoped += 1
                scope += KERNEL if kernel else ""
            scopes.setdefault(scope, {}).setdefault(pas, []).append(
                [name, shape])
    return {"module": module, "instructions": instructions,
            "scoped": scoped, "scopes": scopes}


def describe_program(program: str, fn: Any, *args: Any
                     ) -> Optional[Dict[str, Any]]:
    """Record ONE coarse ``xla.program`` span for ``fn``, a ``jax.jit``
    function that has just run on arguments like ``args`` (the states
    its first call returned, not the donated ones): the map of its
    compiled program (:func:`program_map`), what the program reserves of
    the device (``Compiled.memory_analysis()``: ``argument_bytes``,
    ``output_bytes``, ``alias_bytes``, ``temp_bytes``, ``code_bytes``),
    under the name ``program``. The span's ``dur`` is what this cost;
    ``recompiled`` the compile events it started: 0 where ``args`` are
    like the first call's, since
    ``fn.lower(*args).compile()`` then finds the traced, lowered and
    compiled program in JAX's caches, and 1 where the SECOND call would
    have compiled anyway (its arguments' shardings are not the first's:
    the language-model ``Trainer``), which this then does in its place:
    the record describes the program every later call runs. A set-up
    site, once per program; returns the counts, ``None`` where the flag
    is off or the program will not say (logged as an error)."""
    ds = DEVSTATS
    if not ds.enabled:
        return None
    ds._install_listener()
    t0 = time.time_ns()
    before, led = ds.compile_events(), ds._take_led()
    try:
        compiled = fn.lower(*args).compile()
        counts = program_map(compiled.as_text())
        mem = compiled.memory_analysis()
        counts.update(
            program=program,
            argument_bytes=int(mem.argument_size_in_bytes),
            output_bytes=int(mem.output_size_in_bytes),
            alias_bytes=int(mem.alias_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
            code_bytes=int(mem.generated_code_size_in_bytes))
    except Exception:   # noqa: BLE001 — a program that will not describe
        # itself trains all the same, and says why its metrics read None
        log.error("xla.program: %s will not describe itself: %s",
                  program, traceback.format_exc(limit=3).strip())
        return None
    finally:
        # a cache hit still fires a trace event: not the next compile's
        ds._take_led(led)
    counts["recompiled"] = ds.compile_events() - before
    _trace.record(PROGRAM_SPAN, t0, time.time_ns(), **counts)
    return counts


def _leaves(ops: Sequence[Tuple[str, str, float, float]]
            ) -> List[Tuple[str, str, float, float]]:
    """The operations of one chip's line that hold no other (a ``while``
    holds its body's): on one line an operation that starts inside
    another ends inside it."""
    out, stack = [], []             # stack rows: [end, holds one, op]
    for op in sorted(ops, key=lambda o: (o[2], -o[3])):
        end = op[2] + op[3]
        while stack and (stack[-1][0] < end - 1e-12
                         or stack[-1][0] <= op[2]):
            _, holds, done = stack.pop()
            if not holds:
                out.append(done)
        if stack:
            stack[-1][1] = True
        stack.append([end, False, op])
    return out + [op for _, holds, op in stack if not holds]


def scope_seconds(ops: Dict[str, Sequence[Tuple[str, str, float, float]]],
                  records: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """A traced window's device seconds by scope and pass: the join of
    the trace's operations with the programs' maps.

    ``ops`` maps a chip to its operations ``(name, text, start_s,
    dur_s)``, the name and text as the trace has them (what
    ``benchmark/trace_reduce.read_xplane`` returns); ``records`` are the
    ring's ``xla.program`` records (their ``args``, or the events whole).
    Of each chip the operations that hold no other are looked up by name
    AND result shape: filed under the instruction's ``(scope, pass)``;
    under :data:`UNSCOPED` with its pass where the map has it with no
    ``mv.*`` in its path; under :data:`UNKNOWN` where no map has it;
    under :data:`AMBIGUOUS` where two maps would file it differently.

    Returns, as means over the chips that ran anything: ``seconds``
    ``{scope: {pass: s}}``, ``busy_s`` (the union of every operation's
    interval), ``filed_s`` (what lies under a scope or :data:`UNSCOPED`),
    ``longest`` ``{scope: [[name, shape, s], ...]}`` (three a scope),
    and ``chips``."""
    where: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for rec in records:
        for scope, by in rec.get("args", rec)["scopes"].items():
            for pas, rows in by.items():
                for name, shape in rows:
                    had = where.setdefault((name, shape), (scope, pas))
                    if had != (scope, pas):
                        where[(name, shape)] = (AMBIGUOUS, NO_PASS)
    seconds: Dict[str, Dict[str, float]] = {}
    by_op: Dict[str, Dict[Tuple[str, str], float]] = {}
    busy, chips = 0.0, 0
    for chip_ops in ops.values():
        if not chip_ops:
            continue
        chips += 1
        busy += union_length(
            [(o[2], o[2] + o[3]) for o in chip_ops])
        for name, text, _, dur in _leaves(chip_ops):
            key = (name, _shape_in(text))
            scope, pas = where.get(key, (UNKNOWN, NO_PASS))
            by = seconds.setdefault(scope, {})
            by[pas] = by.get(pas, 0.0) + dur
            top = by_op.setdefault(scope, {})
            top[key] = top.get(key, 0.0) + dur
    n = max(chips, 1)
    seconds = {scope: {pas: s / n for pas, s in by.items()}
               for scope, by in seconds.items()}
    return {
        "chips": chips, "busy_s": busy / n, "seconds": seconds,
        "filed_s": sum(s for scope, by in seconds.items()
                       if scope not in (UNKNOWN, AMBIGUOUS)
                       for s in by.values()),
        "longest": {scope: [[k[0], k[1], s / n] for k, s in
                            sorted(top.items(), key=lambda kv: -kv[1])[:3]]
                    for scope, top in by_op.items()}}
