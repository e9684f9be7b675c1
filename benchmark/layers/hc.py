"""Layer "residual streams (hyper-connections)"
(``multiverso_tpu/models/mla_moe.block`` under ``cfg.streams`` > 1,
``models/xing4.py``): the maps that mix a position's residual streams round
every sublayer, told by the device scopes ``mv.lm.hc.expand``, ``.norm``,
``.project``, ``.sinkhorn``, ``.pre``, ``.post`` and ``.reduce``.

``hc.device_share.<group>``           the device seconds filed under
    ``mv.lm.hc.*``, every pass, over device busy time.
``hc.sinkhorn_device_share.<group>``  ``mv.lm.hc.sinkhorn`` alone (forward,
    forward again and backward): the 40 dependent normalisations a
    sublayer.
``hc.stream_hbm_share.<group>``       what the maps must move
    (``hc_shapes.step_bytes`` for the window's steps, which the driver
    hands over as ``hc_bytes``) over the seconds under ``mv.lm.hc.*`` over
    the chip's HBM peak (``peaks.json``): the maps' share of their
    roofline, on the least any implementation must move.

The maps are XLA's fusions, which carry no scope into the trace: the
seconds come from the join of the trace's operations with the step's
``xla.program`` record (``layers/delta.scopes_in``'s join, under this
layer's scope). ``run.py`` deletes the trace before a reader runs, so the
driver's ``check`` calls :func:`scope_seconds` and hands the sums over as
``run["hc_s"]``. A join that files under ``FILED_FLOOR`` of busy, a program
without the record or the scopes (the parent of the PR that brought them),
answers ``None``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from benchmark import shapes, trace_reduce
from benchmark.layers import delta, prog

SCOPE, SINKHORN = "mv.lm.hc", "mv.lm.hc.sinkhorn"
FILED_FLOOR = delta.FILED_FLOOR


def scopes_in(device_ops, host_spans, events) -> Dict[str, Any]:
    """``delta.scopes_in``'s join, summed under :data:`SCOPE`: the scopes'
    seconds by pass (``seconds``), their sum (``maps_s``), Sinkhorn's
    (``sinkhorn_s``), and the join's ``every_scope``, ``filed_s`` and
    ``busy_s``."""
    got = delta.scopes_in(device_ops, host_spans, events)
    if not got:
        return {}
    mine = {scope: by for scope, by in got["every_scope"].items()
            if scope.startswith(SCOPE + ".")}
    return {"seconds": mine, "every_scope": got["every_scope"],
            "filed_s": got["filed_s"], "busy_s": got["busy_s"],
            "maps_s": sum(sum(by.values()) for by in mine.values()),
            "sinkhorn_s": sum(mine.get(SINKHORN, {}).values())}


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


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    seen = ctx["run"].get("hc_s") or {}
    if (not seen.get("busy_s") or seen.get("maps_s", 0.0) <= 0
            or seen["filed_s"] < FILED_FLOOR * seen["busy_s"]):
        return None
    if what == "device_share":
        return 100.0 * seen["maps_s"] / seen["busy_s"]
    if what == "sinkhorn_device_share":
        return 100.0 * seen["sinkhorn_s"] / seen["busy_s"]
    moved = ctx["run"].get("hc_bytes")
    if what == "stream_hbm_share" and moved:
        peak = shapes.peak(ctx["device_kind"], "hbm_bytes_per_s")
        return 100.0 * moved / seen["maps_s"] / peak
    return None
