"""Layer "device (one v5e chip)": what the profiler's trace says of the
chip itself. ``device.idle_share.<group>``: 100 x (1 - union of the
device-operation intervals / traced window), as ``trace_reduce`` has it.
The group after the last dot only says which end-to-end metric the
number should move."""

from __future__ import annotations

from typing import Any, Dict, Optional


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    trace = ctx["trace"]
    if name.split(".")[1] == "idle_share" and trace["window_s"] > 0:
        return 100.0 * trace["idle_share"]
    return None
