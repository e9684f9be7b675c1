"""Layers "kernels (attention core)" and "indexer and selection"
(``multiverso_tpu/models/keye_moe.py``): the flash kernels of a model that
attends over the keys a learned indexer selects, told by the device scope
``mv.lm.attn.sparse`` that lies inside ``mv.lm.attn``.

``sparse.core_device_share.<group>``  the kernels of that scope over device
    busy time.
``sparse.core_mxu_share.<group>``     what those cores must compute
    (``sparse_shapes.core_flops`` of the SELECTED positions for the window's
    steps, which the driver hands over as ``sparse_flops``) over those
    kernels' time, over the chip's bfloat16 peak (``peaks.json``): the
    selected-key kernels' share of their roofline. It counts what the
    mathematics needs: while the kernels walk the whole triangle and mask,
    it reads at most selected / causal of what a causal call reads, and
    that gap is what a kernel that skips is judged by.
``sparse.selected_share.<group>``     from the ``lm.step`` spans: 100 x
    ``attn_positions_selected`` / ``attn_positions_causal``: static for a
    cell, a guard on the cut and on ``topk``, as
    ``attnmix.band_pairs_share`` is on the band.

As for ``layers/attnmix``: the driver's ``check`` calls
:func:`kernel_seconds` before ``run.py`` deletes the trace and hands the
sums over as ``run["sparse_s"]`` beside the number of kernels it expects
(``sparse_kernels``). A sum that did not see every kernel, a program
without the scope or without the span's counts, answers ``None``. The
indexer's, the selection's and the target's device time are XLA's fusions
and have no reader here (``tools/dump_metrics.py scopes`` reads them).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from benchmark import shapes, trace_reduce
from benchmark.layers import lm, prog

SCOPE = "mv.lm.attn.sparse"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def kernels_in(device_ops, host_spans) -> Dict[str, float]:
    """From ``trace_reduce.read_xplane``'s lists: the seconds per chip of
    the kernels named in the scope that started inside the window, and how
    many there were."""
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
    """:func:`kernels_in` of the trace that ``run.py`` has just stopped for
    this cell; nothing where no trace was taken."""
    try:
        path = trace_reduce.find_xplane(
            os.path.join(ROOT, ".bench_trace", cell_name))
        return kernels_in(*trace_reduce.read_xplane(path))
    except FileNotFoundError:      # no trace taken: nothing to read
        return {}


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    if name.split(".")[1] != "selected_share":
        return None
    steps = [e["args"] for e in lm.window_steps(events)
             if e["args"].get("attn_positions_causal")
             and "attn_positions_selected" in e["args"]]
    if not steps:
        return None
    return (100.0 * sum(a["attn_positions_selected"] for a in steps)
            / sum(a["attn_positions_causal"] for a in steps))


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    if what == "selected_share":
        return read_events(name, prog.program_events())
    trace, run = ctx["trace"], ctx["run"]
    seen = run.get("sparse_s") or {}
    if (trace["busy_s"] <= 0 or not seen.get("kernels")
            or seen["kernels"] != run.get("sparse_kernels")
            or seen["seconds"] <= 0):
        return None
    if what == "core_device_share":
        return 100.0 * seen["seconds"] / trace["busy_s"]
    flops = run.get("sparse_flops")
    if what == "core_mxu_share" and flops:
        peak = shapes.peak(ctx["device_kind"], "bf16_flop_per_s")
        return 100.0 * flops / seen["seconds"] / peak
    return None
