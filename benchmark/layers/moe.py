"""Layer "expert layer": routing, the sort into the held experts' buffer
and the grouped products (``multiverso_tpu/parallel/moe.held_expert_layer``).

From the device trace, through the reduction's ``table_s`` (the driver
names the held experts' stacked shapes, [held, dim, ffn] and [held, ffn,
dim], as its ``table_shapes``; the operations that mention them are the
grouped-product kernels, forward and backward, and one copy of a weight
gradient, 0.014 s a window. The casts of the weights to bfloat16 are
slices of the tables' rows, ``bf16[16384,1536]``, and the Adam pass works
on ``f32[16385,1536]``: neither answers to the stacked shapes, as the
trace of PR 33 shows):

``moe.expert_device_share.<group>``  their time over device busy time.
``moe.expert_mxu_share.<group>``     the products' operations
    (``lm_shapes.expert_products_flops`` of the window's held rows, which
    the driver hands over as ``expert_flops``) over that time, over the
    chip's bfloat16 peak (``peaks.json``). Recomputed products and the
    buffer's padding rows are time and not operations, so the share reads
    low, never high.

From the program's ``lm.step`` spans of the window (counts the trainer
set on them):

``moe.held_share.<group>``           100 x sum ``held_rows`` / sum
    ``routed_rows``: the part of all token-to-expert assignments that
    went to experts held here (12.5 is even for 8 of 64).
``moe.load_max_over_mean.<group>``   the window's loads as a whole: the
    steps' ``expert_rows`` added up, then the busiest of all a layer's
    experts over the layer's mean, worst layer. (A single step's worst
    layer, the span's own ``load_max_over_mean``, reads higher: a batch
    has runs of like tokens that the next batch has elsewhere.)

A program without the spans, or a trace without such operations, answers
``None``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import shapes
from benchmark.layers import lm, prog


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    what = name.split(".")[1]
    counts = [e["args"] for e in lm.window_steps(events)
              if e["args"].get("routed_rows")]
    if not counts:
        return None
    if what == "held_share":
        return (100.0 * sum(a["held_rows"] for a in counts)
                / sum(a["routed_rows"] for a in counts))
    if what == "load_max_over_mean" and all("expert_rows" in a
                                            for a in counts):
        rows = np.sum([a["expert_rows"] for a in counts], axis=0)
        return float(np.max(rows.max(1) / rows.mean(1)))
    return None


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    if what in ("held_share", "load_max_over_mean"):
        return read_events(name, prog.program_events())
    trace, run = ctx["trace"], ctx["run"]
    if trace["busy_s"] <= 0 or trace["table_s"] <= 0:
        return None
    if what == "expert_device_share":
        return 100.0 * trace["table_s"] / trace["busy_s"]
    if what == "expert_mxu_share" and run.get("expert_flops"):
        peak = shapes.peak(ctx["device_kind"], "bf16_flop_per_s")
        return 100.0 * run["expert_flops"] / trace["table_s"] / peak
    return None
