"""Layer "delta-rule mixer" (``multiverso_tpu/models/qwen3_next.py``,
``multiverso_tpu/ops/delta_rule.py``): the gated delta-rule
linear-attention mixers, told by the device scopes ``mv.lm.delta`` and,
inside it, ``mv.lm.delta.conv``, ``.gates``, ``.rule`` and ``.norm``.

``delta.mixer_flops_share.<group>``   from the window's ``lm.step`` spans,
    100 x ``delta_flops_token`` / ``step_flops_token``: the delta mixers'
    part of the operations a token needs in a forward pass of the whole
    step on this chip, as the program counts them from its configuration
    (``models/qwen3_next.Qwen3NextConfig.delta_grid``;
    ``benchmark/delta_shapes.py`` counts them again from the
    configuration's file, and a test holds the two equal). Static for a
    cell: a guard on the cut and on the rule's chunk, as
    ``conv.mixer_flops_share`` is.
``delta.mixer_device_share.<group>``  the device seconds filed under
    ``mv.lm.delta`` and its children, every pass, over device busy time.
``delta.rule_mxu_share.<group>``      what the chunked rule must compute
    (``delta_shapes.rule_flops`` for the window's steps and layers, which
    the driver hands over as ``delta_flops``) over the seconds under
    ``mv.lm.delta.rule`` over the chip's bfloat16 peak (``peaks.json``):
    the scan's share of its roofline, the number a later Pallas kernel is
    judged by. The rule made again in the backward pass and the making of
    ``T`` are time and not operations, so it reads low.

The mixer is XLA's fusions and products, which carry no scope into the
trace: the seconds come from the join of the trace's operations with the
step's ``xla.program`` record (``telemetry/devstats.scope_seconds``, what
``tools/dump_metrics.py scopes`` prints). ``run.py`` deletes the trace
before a reader runs, so the driver's ``check`` calls
:func:`scope_seconds` and hands the sums over as ``run["delta_s"]``. A join
that files under ``FILED_FLOOR`` of busy, a program without the record, the
scopes or the span's counts, answers ``None``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from benchmark import shapes, trace_reduce
from benchmark.layers import lm, prog

SCOPE, RULE = "mv.lm.delta", "mv.lm.delta.rule"
FILED_FLOOR = 0.99
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def scopes_in(device_ops, host_spans,
              events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """From ``trace_reduce.read_xplane``'s lists and the program's ring:
    the window's device seconds by scope and pass under :data:`SCOPE`
    (``seconds``; ``every_scope`` has the whole join's, for the run's
    detail line), their sum (``mixer_s``), the rule's (``rule_s``), and the
    join's ``filed_s`` and ``busy_s``; nothing where the ring has no
    ``xla.program`` record or the trace no window."""
    from multiverso_tpu.telemetry import devstats

    records = [e for e in events if e.get("name") == devstats.PROGRAM_SPAN]
    windows = [s for s in host_spans if s.name == trace_reduce.WINDOW_SPAN]
    if not records or not windows or not device_ops:
        return {}
    lo = min(s.start for s in windows)
    hi = max(s.start + s.dur for s in windows)
    ops = {chip: [(o.name, o.text, max(o.start, lo),
                   min(o.start + o.dur, hi) - max(o.start, lo))
                  for o in each if min(o.start + o.dur, hi) > max(o.start, lo)]
           for chip, each in device_ops.items()}
    got = devstats.scope_seconds(ops, records)
    mine = {scope: by for scope, by in got["seconds"].items()
            if scope == SCOPE or scope.startswith(SCOPE + ".")}
    return {"seconds": mine, "every_scope": got["seconds"],
            "filed_s": got["filed_s"], "busy_s": got["busy_s"],
            "mixer_s": sum(sum(by.values()) for by in mine.values()),
            "rule_s": sum(mine.get(RULE, {}).values())}


def scope_seconds(cell_name: str) -> Dict[str, Any]:
    """:func:`scopes_in` of the trace that ``run.py`` has just stopped for
    this cell; nothing where no trace was taken."""
    try:
        path = trace_reduce.find_xplane(
            os.path.join(ROOT, ".bench_trace", cell_name))
        return scopes_in(*trace_reduce.read_xplane(path),
                         prog.program_events())
    except FileNotFoundError:      # no trace taken: nothing to read
        return {}


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    if name.split(".")[1] != "mixer_flops_share":
        return None
    steps = [e["args"] for e in lm.window_steps(events)
             if e["args"].get("step_flops_token")
             and "delta_flops_token" in e["args"]]
    if not steps:
        return None
    return (100.0 * sum(a["delta_flops_token"] for a in steps)
            / sum(a["step_flops_token"] for a in steps))


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    if what == "mixer_flops_share":
        return read_events(name, prog.program_events())
    seen = ctx["run"].get("delta_s") or {}
    if (not seen.get("busy_s") or seen.get("rule_s", 0.0) <= 0
            or seen["filed_s"] < FILED_FLOOR * seen["busy_s"]):
        return None
    if what == "mixer_device_share":
        return 100.0 * seen["mixer_s"] / seen["busy_s"]
    flops = ctx["run"].get("delta_flops")
    if what == "rule_mxu_share" and flops:
        peak = shapes.peak(ctx["device_kind"], "bf16_flop_per_s")
        return 100.0 * flops / seen["rule_s"] / peak
    return None
