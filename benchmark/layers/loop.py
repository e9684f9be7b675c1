"""Layer "looped stack (passes over the same tables) and its exits"
(``multiverso_tpu/models/mla_moe._passes`` and ``_exit_loss``,
``models/ouro.py``): a stack of blocks run several times as one loop of the
program, told by the device scopes ``mv.lm.loop`` (round the passes; the
blocks keep ``mv.lm.attn*``, ``mv.lm.norm.*`` and ``mv.lm.dense`` inside
it), ``mv.lm.loop.exit`` (the gate, its distribution and entropy) and
``mv.lm.head`` (every exit through the chunked loss).

``loop.stack_mxu_share.<group>``    what the blocks' projections and MLPs
    compute, every pass of them that runs (``loop_shapes.stack_flops`` for
    the window's steps, which the driver hands over as
    ``loop_flops["stack"]``), over the seconds under the loop's scopes
    outside the attention core (:func:`stack_seconds`), over the chip's
    bfloat16 peak (``peaks.json``): the looped stack's share of its
    roofline. Norms, residual sums, the scan's own sums of the shared
    tables' gradients and what lies between a projection and the core are
    in the seconds and not in the operations, so it reads under what the
    matrix unit does.
``loop.head_device_share.<group>``  the seconds under ``mv.lm.head`` and
    ``mv.lm.loop.exit``, every pass, over device busy time: what four exits
    over the whole vocabulary cost.
``loop.head_mxu_share.<group>``     the exits' three products
    (``loop_shapes.head_flops``, ``loop_flops["head"]``) over those seconds
    over the peak.
``loop.exit_entropy_share.<group>`` from the window's ``lm.step`` spans, 100
    x the mean ``exit_entropy`` / ``ln(loop_passes)``: 100 where every pass
    is as likely an exit at every position, 0 where the gate has collapsed
    onto one pass (or died): it shows there first.

The stack is XLA's fusions and products, which carry no scope into the
trace: the seconds come from the join of the trace's operations with the
step's ``xla.program`` record (``layers/delta.scopes_in``'s join).
``run.py`` deletes the trace before a reader runs, so the driver's ``check``
calls :func:`scope_seconds` and hands the sums over as ``run["loop_s"]``. A
join that files under ``FILED_FLOOR`` of busy, a program without the record,
the scopes or the span's facts (the parent of the PR that brought them),
answers ``None``.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, List, Optional

from benchmark import shapes, trace_reduce
from benchmark.layers import delta, lm, prog

LOOP, EXIT, HEAD = "mv.lm.loop", "mv.lm.loop.exit", "mv.lm.head"
# what a pass runs under scopes of its own inside the loop's
INSIDE = ("mv.lm.attn", "mv.lm.norm", "mv.lm.dense")
# the attention core's kernels: ``layers/attn``'s and ``layers/attnmix``'s
CORE = "mv.lm.attn.full:kernel"
FILED_FLOOR = delta.FILED_FLOOR


def _of(scope: str, parent: str) -> bool:
    return scope == parent or scope.startswith((parent + ".", parent + ":"))


def stack_seconds(every_scope: Dict[str, Dict[str, float]]) -> float:
    """The seconds of the looped stack outside its attention cores: the
    loop's own scope (residual sums, the scan's sums) and what the blocks
    file under :data:`INSIDE`, every pass, less :data:`CORE` and the exits.
    In a program whose every block runs inside the loop, as this layer's
    does, those scopes are nowhere else."""
    return sum(sum(by.values()) for scope, by in every_scope.items()
               if scope != CORE and not _of(scope, EXIT)
               and (_of(scope, LOOP) or any(_of(scope, s) for s in INSIDE)))


def scopes_in(device_ops, host_spans, events) -> Dict[str, Any]:
    """``delta.scopes_in``'s join under this layer's scopes: the stack's
    seconds (``stack_s``), the exits' (``head_s``: the head's and the
    gate's), and the join's ``every_scope``, ``filed_s`` and ``busy_s``;
    nothing where the program ran no loop."""
    got = delta.scopes_in(device_ops, host_spans, events)
    if not got or not any(_of(s, LOOP) for s in got["every_scope"]):
        return {}
    every = got["every_scope"]
    return {"every_scope": every, "filed_s": got["filed_s"],
            "busy_s": got["busy_s"], "stack_s": stack_seconds(every),
            "head_s": sum(sum(by.values()) for scope, by in every.items()
                          if _of(scope, HEAD) or _of(scope, EXIT))}


def scope_seconds(cell_name: str) -> Dict[str, Any]:
    """:func:`scopes_in` of the trace that ``run.py`` has just stopped for
    this cell; nothing where no trace was taken."""
    try:
        path = trace_reduce.find_xplane(
            os.path.join(delta.ROOT, ".bench_trace", cell_name))
        return scopes_in(*trace_reduce.read_xplane(path),
                         prog.program_events())
    except FileNotFoundError:      # no trace taken: nothing to read
        return {}


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    if name.split(".")[1] != "exit_entropy_share":
        return None
    steps = [e["args"] for e in lm.window_steps(events)
             if e["args"].get("loop_passes", 0) > 1
             and "exit_entropy" in e["args"]]
    if not steps:
        return None
    return 100.0 * sum(a["exit_entropy"] / math.log(a["loop_passes"])
                       for a in steps) / len(steps)


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    if what == "exit_entropy_share":
        return read_events(name, prog.program_events())
    seen = ctx["run"].get("loop_s") or {}
    if (not seen.get("busy_s")
            or seen["filed_s"] < FILED_FLOOR * seen["busy_s"]):
        return None
    flops = ctx["run"].get("loop_flops") or {}
    peak = shapes.peak(ctx["device_kind"], "bf16_flop_per_s")
    if what == "stack_mxu_share" and seen["stack_s"] > 0 and flops.get("stack"):
        return 100.0 * flops["stack"] / seen["stack_s"] / peak
    if seen["head_s"] <= 0:
        return None
    if what == "head_device_share":
        return 100.0 * seen["head_s"] / seen["busy_s"]
    if what == "head_mxu_share" and flops.get("head"):
        return 100.0 * flops["head"] / seen["head_s"] / peak
    return None
