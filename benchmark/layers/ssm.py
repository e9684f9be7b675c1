"""Layer "state-space mixer (chunked scan)" (``multiverso_tpu/models/
nemotron_h.mamba2``, ``multiverso_tpu/ops/ssd.py``): a Mamba-2 mixer's
device time by its scopes (``mv.lm.ssm``: the in- and out-projections and
what lies between; inside it ``mv.lm.ssm.conv``, ``.scan`` and ``.norm``; a
Pallas call under a scope is filed apart as ``<scope>:kernel``), and, for
``layers/ffn.py``, the dense MLP's (``mv.lm.dense``). The scan's FIRST
reader in the benchmark: the driver of ``nemotron3n-train-16k`` hands no
scope seconds (PERF.md section 7).

``ssm.mixer_device_share.<group>``   the device seconds filed under
    ``mv.lm.ssm`` and its children, every pass (forward, the block made
    again, backward), over device busy time.
``ssm.scan_device_share.<group>``    those under ``mv.lm.ssm.scan`` and
    ``mv.lm.ssm.scan:kernel`` (the two kernels, and XLA's running sums and
    transposes round them) over busy.
``ssm.scan_roofline_share.<group>``  the two kernels' share of their
    roofline: the least time the chip could take for the window's scans,
    which is the larger of ``ssm_shapes.scan_flops`` over the chip's
    bfloat16 peak and ``ssm_shapes.scan_bytes`` over its HBM peak
    (``peaks.json``; the driver hands both over as ``ssm_work``), over the
    seconds under ``mv.lm.ssm.scan:kernel``. The forward kernel runs twice
    a step (the block is made again) and the kernels read float32 where the
    count reads the operands' two bytes: both are time and not work, so it
    reads under what the kernels move.
``ssm.proj_mxu_share.<group>``       the mixers' in- and out-projections
    (``ssmblock_shapes.proj_flops``) over the seconds under ``mv.lm.ssm``
    itself (its children left out) over the chip's bfloat16 peak. The
    products made again, the softplus and the splits are in the seconds.
``ssm.kernel_layers_share.<group>``  from the window's ``lm.step`` spans,
    100 x ``ssd_kernel_layers`` / ``ssm_layers``: 100, or the cell is
    running the plain form (``ssd_kernel_why`` says why).

The mixer's projections and norms are XLA's fusions, which carry no scope
into the trace: the seconds come from the join of the trace's operations
with the step's ``xla.program`` record (``layers/delta.scopes_in``'s join).
``run.py`` deletes the trace before a reader runs, so the driver's ``check``
calls :func:`scope_seconds` and hands the sums over as ``run["ssm_s"]``. A
cell whose driver hands none, a join that files under ``FILED_FLOOR`` of
busy, a program without the record, the scopes or the span's counts (the
parent of the PR that brought them), answers ``None``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from benchmark import shapes, trace_reduce
from benchmark.layers import delta, lm, prog

SSM, SCAN, DENSE = "mv.lm.ssm", "mv.lm.ssm.scan", "mv.lm.dense"
KERNEL = SCAN + ":kernel"
FILED_FLOOR = delta.FILED_FLOOR


def _of(scope: str, parent: str) -> bool:
    return scope == parent or scope.startswith((parent + ".", parent + ":"))


def _under(every_scope: Dict[str, Dict[str, float]], parent: str) -> float:
    return sum(sum(by.values()) for scope, by in every_scope.items()
               if _of(scope, parent))


def scopes_in(device_ops, host_spans, events) -> Dict[str, Any]:
    """``delta.scopes_in``'s join under this layer's scopes: the mixers'
    seconds (``mixer_s``), the scan's (``scan_s``), its kernels'
    (``kernel_s``), the projections' (``proj_s``: ``mv.lm.ssm`` itself), the
    dense MLPs' (``dense_s``), and the join's ``every_scope``, ``filed_s``
    and ``busy_s``; nothing where the program has no state-space mixer."""
    got = delta.scopes_in(device_ops, host_spans, events)
    if not got or not any(_of(s, SSM) for s in got["every_scope"]):
        return {}
    every = got["every_scope"]
    return {"every_scope": every, "filed_s": got["filed_s"],
            "busy_s": got["busy_s"], "mixer_s": _under(every, SSM),
            "scan_s": _under(every, SCAN),
            "kernel_s": sum(every.get(KERNEL, {}).values()),
            "proj_s": sum(every.get(SSM, {}).values()),
            "dense_s": _under(every, DENSE)}


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
    if name.split(".")[1] != "kernel_layers_share":
        return None
    steps = [e["args"] for e in lm.window_steps(events)
             if e["args"].get("ssm_layers")
             and "ssd_kernel_layers" in e["args"]]
    if not steps:
        return None
    return (100.0 * sum(a["ssd_kernel_layers"] for a in steps)
            / sum(a["ssm_layers"] for a in steps))


def joined(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The driver's join where it filed enough of the busy time, else
    nothing."""
    seen = ctx["run"].get("ssm_s") or {}
    if (not seen.get("busy_s")
            or seen["filed_s"] < FILED_FLOOR * seen["busy_s"]):
        return {}
    return seen


def mxu_share(flops, seconds: float, ctx: Dict[str, Any]) -> Optional[float]:
    if not flops or seconds <= 0:
        return None
    peak = shapes.peak(ctx["device_kind"], "bf16_flop_per_s")
    return 100.0 * flops / seconds / peak


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    if what == "kernel_layers_share":
        return read_events(name, prog.program_events())
    seen = joined(ctx)
    if not seen or seen["mixer_s"] <= 0:
        return None
    work = ctx["run"].get("ssm_work") or {}
    if what == "mixer_device_share":
        return 100.0 * seen["mixer_s"] / seen["busy_s"]
    if what == "scan_device_share" and seen["scan_s"] > 0:
        return 100.0 * seen["scan_s"] / seen["busy_s"]
    if what == "proj_mxu_share":
        return mxu_share(work.get("proj_flops"), seen["proj_s"], ctx)
    if (what == "scan_roofline_share" and seen["kernel_s"] > 0
            and work.get("scan_flops") and work.get("scan_bytes")):
        kind = ctx["device_kind"]
        least = max(
            work["scan_flops"] / shapes.peak(kind, "bf16_flop_per_s"),
            work["scan_bytes"] / shapes.peak(kind, "hbm_bytes_per_s"))
        return 100.0 * least / seen["kernel_s"]
    return None
