"""Layer "delta-rule mixer" (``multiverso_tpu/models/qwen3_next.py``,
``multiverso_tpu/ops/delta_rule.py``): the mixers' short causal
convolution, told by the device scope ``mv.lm.delta.conv`` (XLA's
instructions under it) and ``mv.lm.delta.conv:kernel`` (the Pallas calls of
``multiverso_tpu/ops/short_conv.py``, which the step's ``xla.program``
record files apart).

``shortconv.device_share.<group>``  100 x the device seconds filed under
    the two scopes, every pass (forward, the feed made again, backward),
    over device busy time: what the convolution of four taps and its silu
    cost of a step. One read and one write of its float32 array a pass is
    the floor (``lm.step``'s ``conv_bytes`` over the HBM peak).

The seconds are ``layers/delta.py``'s join (the driver's ``check`` hands it
over as ``run["delta_s"]`` before ``run.py`` deletes the trace): a cell
whose driver hands none, a join that files under ``delta.FILED_FLOOR`` of
busy, or a program without the scope, answers ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.layers import delta

SCOPES = ("mv.lm.delta.conv", "mv.lm.delta.conv:kernel")


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    if name.split(".")[1] != "device_share":
        return None
    seen = ctx["run"].get("delta_s") or {}
    if (not seen.get("busy_s")
            or seen.get("filed_s", 0.0) < delta.FILED_FLOOR * seen["busy_s"]):
        return None
    mine = sum(sum(seen.get("seconds", {}).get(scope, {}).values())
               for scope in SCOPES)
    return 100.0 * mine / seen["busy_s"] if mine > 0 else None
