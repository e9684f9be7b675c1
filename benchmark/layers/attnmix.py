"""Layer "kernels (attention core)": the flash kernels of a model whose
layers mix window and full attention (``models/gqa_moe.py``), told apart
by the device scopes ``mv.lm.attn.window`` and ``mv.lm.attn.full`` that
lie inside ``mv.lm.attn``.

``attnmix.<kind>_device_share.<group>``  the kernels of that scope over
    device busy time.
``attnmix.<kind>_mxu_share.<group>``     what those cores must compute
    (``attn_shapes.core_flops`` for the window's steps, which the driver
    hands over as ``attnmix_flops``) over those kernels' time, over the
    chip's bfloat16 peak (``peaks.json``): each kernel's share of its
    roofline. Scores recomputed in the backward kernels and the forward
    pass run again are time and not operations, so it reads low.
``attnmix.band_pairs_share.<group>``     from the ``lm.step`` spans: the
    (q block, k block) pairs a window layer's walk visits over the pairs
    a causal walk of the same blocks would (``attn_pairs_live_window`` /
    ``attn_pairs_causal_window``): static for a cell, a guard on the
    kernel's design.

As for ``layers/attn``: a step runs more kernels than the reduction
keeps operations, and ``run.py`` deletes the trace before a reader runs,
so the driver's ``check`` calls :func:`kernel_seconds` and hands the sums
over as ``run["attnmix_s"]`` beside the numbers of kernels it expects
(``attnmix_kernels``). A sum that did not see every kernel, a program
without the scopes or without the span's counts, answers ``None``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from benchmark import shapes, trace_reduce
from benchmark.layers import lm, prog

SCOPES = {"window": "mv.lm.attn.window", "full": "mv.lm.attn.full"}
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def kernels_in(device_ops, host_spans) -> Dict[str, Dict[str, float]]:
    """From ``trace_reduce.read_xplane``'s lists: by kind, the seconds per
    chip of the kernels named in that kind's scope that started inside
    the window, and how many there were."""
    windows = [s for s in host_spans if s.name == trace_reduce.WINDOW_SPAN]
    if not windows or not device_ops:
        return {}
    lo = min(s.start for s in windows)
    hi = max(s.start + s.dur for s in windows)
    out = {}
    for kind, scope in SCOPES.items():
        mine = [o for ops in device_ops.values() for o in ops
                if lo <= o.start < hi and scope in o.name
                and "custom-call" in o.text]
        out[kind] = {"seconds": sum(o.dur for o in mine) / len(device_ops),
                     "kernels": len(mine)}
    return out


def kernel_seconds(cell_name: str) -> Dict[str, Dict[str, float]]:
    """:func:`kernels_in` of the trace that ``run.py`` has just stopped
    for this cell; nothing where no trace was taken."""
    try:
        path = trace_reduce.find_xplane(
            os.path.join(ROOT, ".bench_trace", cell_name))
        return kernels_in(*trace_reduce.read_xplane(path))
    except FileNotFoundError:      # no trace taken: nothing to read
        return {}


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    if name.split(".")[1] != "band_pairs_share":
        return None
    steps = [e["args"] for e in lm.window_steps(events)
             if e["args"].get("attn_pairs_causal_window")
             and "attn_pairs_live_window" in e["args"]]
    if not steps:
        return None
    return (100.0 * sum(a["attn_pairs_live_window"] for a in steps)
            / sum(a["attn_pairs_causal_window"] for a in steps))


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    if what == "band_pairs_share":
        return read_events(name, prog.program_events())
    kind, _, quantity = what.partition("_")
    trace, run = ctx["trace"], ctx["run"]
    seen = (run.get("attnmix_s") or {}).get(kind) or {}
    if (kind not in SCOPES or trace["busy_s"] <= 0 or not seen.get("kernels")
            or seen["kernels"] != (run.get("attnmix_kernels") or {}).get(kind)
            or seen["seconds"] <= 0):
        return None
    if quantity == "device_share":
        return 100.0 * seen["seconds"] / trace["busy_s"]
    flops = (run.get("attnmix_flops") or {}).get(kind)
    if quantity == "mxu_share" and flops:
        peak = shapes.peak(ctx["device_kind"], "bf16_flop_per_s")
        return 100.0 * flops / seen["seconds"] / peak
    return None
