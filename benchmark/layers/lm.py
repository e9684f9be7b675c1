"""Layer "trainers": the language-model trainer's own step span
(``multiverso_tpu/models/mla_moe.Trainer.step``, recorded by
``telemetry/trace.py``).

``lm.step_host_ms.<group>``: the median over the window's ``lm.step``
spans of the span less its ``lm.step.wait`` child: what the host spends
on a step beside waiting for the device (dispatch of the donated
program, the span's own book-keeping). A program without the span
answers ``None``.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

from benchmark.layers import prog


def window_steps(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The ``lm.step`` spans recorded while the profiler captured (the
    measured window and nothing else)."""
    return [e for e in events if e.get("name") == "lm.step" and e.get("prof")]


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    if name.split(".")[1] != "step_host_ms":
        return None
    waits = {e["parent"]: e["dur"] for e in events
             if e.get("name") == "lm.step.wait"}
    host = [(e["dur"] - waits.get(e["id"], 0.0)) * 1e-3
            for e in window_steps(events)]
    return statistics.median(host) if host else None


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    return read_events(name, prog.program_events())
