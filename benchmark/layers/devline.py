"""The device's timeline as the program's host knew it, from the device
spans in the program's ring (``telemetry/trace.py:DeviceWatcher``:
``we.fused.device``, ``we.block.device``, ``lm.step.device``; one a
dispatched program, ``cat`` ``"device"``, from the dispatch's start to
the ready time, with the count ``dispatched``: the ``time.time_ns()`` at
which the dispatch had returned). No xplane is read here: the arithmetic
is this file's own, on ``prog.program_events()``, and the tests hold it
equal to the program's ``trace.device_timeline`` on the same list.

A program is **in flight** from ``dispatched`` to its span's end. The
device is **starved** wherever no program is in flight between the start
of the window's earliest span and the last device span's end: the host
had given it nothing to do. The window is the spans with ``prof`` true,
as in ``layers/prog.py``; the group after the last dot picks the device
spans by their names' first part (``we.`` or ``lm.``).

================================  =========================================
``devline.starved_share.*``       100 x starved seconds / the traced window
                                  (``ctx["trace"]["window_s"]``, what
                                  ``device.idle_share`` divides by; the
                                  extent of the window's spans where the
                                  trace has none)
``devline.unfiled_idle_share.*``  ``device.idle_share`` of the same run less
                                  ``starved_share``: idle the device trace
                                  saw while the host believed a program was
                                  in flight (launch latency, a stall inside
                                  a read-back, chips waiting on each other)
``devline.run_max_over_p50.*``    the longest run of a device span over the
                                  median run; a run is the span's end less
                                  the later of its ``dispatched`` and the
                                  end before it
================================  =========================================

``None`` where the window holds no device span (a program from before
them; ``dlrm-step``, whose window runs no host code of the program).
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmark.layers import prog


def _end(e: Dict[str, Any]) -> float:
    return e["ts"] + e["dur"]


def timeline(events: List[Dict[str, Any]], group: str
             ) -> Optional[Dict[str, Any]]:
    """``starved_s``, ``extent_s`` (the window's spans, first start to
    last end) and ``runs_ms`` of the window's device spans of ``group``;
    ``None`` without one."""
    window = [e for e in events if e.get("prof")]
    devs = sorted((e for e in window if e.get("cat") == "device"
                   and e["name"].startswith(group + ".")), key=_end)
    if not devs:
        return None
    lo, hi = min(e["ts"] for e in window), _end(devs[-1])
    flights: List[Tuple[float, float]] = [
        (e["args"].get("dispatched", e["ts"] * 1e3) * 1e-3, _end(e))
        for e in devs]
    busy, reach = 0.0, lo           # in flight up to ``reach`` so far
    for a, b in sorted(flights):
        if b > reach:
            busy += b - max(a, reach)
            reach = b
    runs = [(b - max(a, prev)) * 1e-3 for (a, b), prev in
            zip(flights, [float("-inf")] + [b for _, b in flights[:-1]])]
    return {"starved_s": (hi - lo - busy) * 1e-6, "runs_ms": runs,
            "extent_s": (max(_end(e) for e in window) - lo) * 1e-6}


def read_events(name: str, events: List[Dict[str, Any]],
                trace: Dict[str, Any]) -> Optional[float]:
    """``name``'s value from span records and the trace's reduction
    (``read`` without the program: what the tests drive)."""
    _, what, group = name.split(".")
    line = timeline(events, group)
    if line is None:
        return None
    if what == "run_max_over_p50":
        return max(line["runs_ms"]) / statistics.median(line["runs_ms"])
    traced = trace["window_s"] > 0
    starved = 100.0 * line["starved_s"] / (
        trace["window_s"] if traced else line["extent_s"])
    if what == "starved_share":
        return starved
    if what == "unfiled_idle_share" and traced:
        return 100.0 * trace["idle_share"] - starved
    return None


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    return read_events(name, prog.program_events(), ctx["trace"])
