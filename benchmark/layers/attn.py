"""Layer "kernels (attention core)": the flash kernels of
``ops/attention_kernels.py`` as the language model calls them, named by
the ``mv.lm.attn`` scope in the device trace.

``attn.device_share.<group>``: their time over device busy time. A step
runs four such kernels a block, more than the ten operations the
reduction keeps, and ``run.py`` deletes the trace before a reader runs.
So the cell's driver calls :func:`kernel_seconds` from its ``check``
(which ``run.py`` calls between stopping the trace and reducing it) and
hands the answer over as ``run["attention_s"]``, beside the number of
kernels it expects (``attention_kernels``). Where not every one was seen
(a program that names them otherwise, a trace cut short) the sum would
read low, and the answer is ``None``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from benchmark import trace_reduce

SCOPE = "mv.lm.attn"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def kernels_in(device_ops, host_spans) -> Dict[str, float]:
    """From ``trace_reduce.read_xplane``'s lists: the seconds per chip of
    the kernels named in the scope that started inside the window, and
    how many there were."""
    windows = [s for s in host_spans if s.name == trace_reduce.WINDOW_SPAN]
    if not windows or not device_ops:
        return {}
    lo = min(s.start for s in windows)
    hi = max(s.start + s.dur for s in windows)
    mine = [o for ops in device_ops.values() for o in ops
            if lo <= o.start < hi and SCOPE in o.name
            and "custom-call" in o.text]
    return {"seconds": sum(o.dur for o in mine) / len(device_ops),
            "kernels": len(mine)}


def kernel_seconds(cell_name: str) -> Dict[str, float]:
    """:func:`kernels_in` of the trace that ``run.py`` has just stopped
    for this cell; nothing where no trace was taken."""
    try:
        path = trace_reduce.find_xplane(
            os.path.join(ROOT, ".bench_trace", cell_name))
        return kernels_in(*trace_reduce.read_xplane(path))
    except FileNotFoundError:      # no trace taken: nothing to read
        return {}


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    trace, run = ctx["trace"], ctx["run"]
    seen = run.get("attention_s") or {}
    if (name.split(".")[1] != "device_share" or trace["busy_s"] <= 0
            or not seen.get("kernels")
            or seen["kernels"] != run.get("attention_kernels")):
        return None
    return 100.0 * seen["seconds"] / trace["busy_s"]
