"""Layer metrics read from the program's own spans and counts
(``multiverso_tpu/telemetry/trace.py``): what the program recorded about
itself, in memory, while it ran. ``mv.shutdown()`` leaves that ring as it
is when no ``metrics_dir`` is set, so it is read here after the run.

A span is a dict with ``name``, ``ts`` and ``dur`` (microseconds of
``time.time_ns()``), ``id``, ``parent``, ``request``, ``prof`` and its
counts under ``args``. ``prof`` is true on a span recorded while the
profiler was capturing, and ``run.py`` captures the measured window and
nothing else: the **window's spans** are those with ``prof`` true (the
calls a driver's ``check`` makes afterwards never leak in), and the
**set-up's spans** are those that began before the first of them. A
window that runs no span of the program (the DLRM step is one jitted
call) leaves no such mark, and everything recorded counts as set-up;
that cell's ``check`` builds and compiles nothing.

=========================  ================================================
``prog.fused_host_ms.*``   median over the window's ``we.fused`` calls of
                           the call minus its ``we.fused.wait`` child: the
                           host's part of a ``train_fused`` call
``prog.block_dev_ms.*``    median gap between consecutive ``we.block.device``
                           ends within one ``we.blocks`` call; the first
                           block of a call counts from its dispatch
``prog.block_dev_p95_ms``  95th percentile of the same gaps (20 or more)
``prog.prepare_wait_ms``   median ``we.block.wait_prepared``: how long the
                           consumer waited for a prepared block
``prog.pull_fill_share``   100 x sum ``rows_touched`` / sum ``rows_bucket``
                           over the window's ``we.prepare`` spans
``prog.table_init_s``      seconds in ``table.init`` spans during set-up
``prog.compile_s``         ``seconds`` of the ``xla.compile`` spans of set-up
=========================  ================================================

A reader that finds no such span (a program from before the spans, a
cell that does not run that path) returns ``None``.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional


def program_events() -> List[Dict[str, Any]]:
    """The program's recorded spans, oldest first; [] if it keeps none."""
    try:
        from multiverso_tpu.telemetry import trace
    except ImportError:
        return []
    tracer = getattr(trace, "TRACER", None)
    return list(tracer.events()) if tracer is not None else []


def _end(e: Dict[str, Any]) -> float:
    return e["ts"] + e["dur"]


def _named(events, name: str) -> List[Dict[str, Any]]:
    return [e for e in events if e.get("name") == name]


def _median_ms(spans) -> Optional[float]:
    return statistics.median(e["dur"] for e in spans) * 1e-3 if spans else None


def block_gaps_ms(window: List[Dict[str, Any]]) -> List[float]:
    """Per block, the time the device took for it as the program saw it:
    from the previous block's ``we.block.device`` end to this one's,
    within one ``we.blocks`` call; a call's first block from its own
    start (its dispatch)."""
    gaps: List[float] = []
    done = sorted(_named(window, "we.block.device"), key=_end)
    for call in _named(window, "we.blocks"):
        inside = [e for e in done if call["ts"] <= _end(e) <= _end(call)]
        for prev, e in zip([None] + inside[:-1], inside):
            start = e["ts"] if prev is None else _end(prev)
            gaps.append((_end(e) - start) * 1e-3)
    return gaps


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    """``name``'s value from a list of span records (``read`` without
    the program: what the tests drive)."""
    what = name.split(".")[1]
    window = [e for e in events if e.get("prof")]
    if what.endswith("_s"):          # the two set-up metrics
        cut = min((e["ts"] for e in window), default=float("inf"))
        setup = [e for e in events if e["ts"] < cut and not e.get("prof")]
        if what == "table_init_s":
            spans = _named(setup, "table.init")
            return sum(e["dur"] for e in spans) * 1e-6 if spans else None
        if what == "compile_s":
            spans = _named(setup, "xla.compile")
            return (sum(float(e["args"]["seconds"]) for e in spans)
                    if spans else None)
        return None
    if what == "fused_host_ms":
        waits = {e["parent"]: e["dur"]
                 for e in _named(window, "we.fused.wait")}
        host = [(e["dur"] - waits.get(e["id"], 0.0)) * 1e-3
                for e in _named(window, "we.fused")]
        return statistics.median(host) if host else None
    if what == "block_dev_ms":
        gaps = block_gaps_ms(window)
        return statistics.median(gaps) if gaps else None
    if what == "block_dev_p95_ms":
        gaps = block_gaps_ms(window)
        return (statistics.quantiles(gaps, n=20)[-1]
                if len(gaps) >= 20 else None)
    if what == "prepare_wait_ms":
        return _median_ms(_named(window, "we.block.wait_prepared"))
    if what == "pull_fill_share":
        prepared = [e["args"] for e in _named(window, "we.prepare")
                    if e["args"].get("rows_bucket")]
        bucket = sum(a["rows_bucket"] for a in prepared)
        return (100.0 * sum(a["rows_touched"] for a in prepared) / bucket
                if bucket else None)
    return None


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    return read_events(name, program_events())
