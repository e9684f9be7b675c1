"""Layer "dense feed-forward (gated MLP)" (``multiverso_tpu/models/
mla_moe.dense_ffn``): the dense MLP behind every mixer of a block of two
branches, told by the device scope ``mv.lm.dense``.

``ffn.dense_device_share.<group>``  the device seconds filed under
    ``mv.lm.dense``, every pass (forward, the block made again, backward),
    over device busy time: the largest single part of ``granite4h-train-8k``'s
    step.
``ffn.dense_mxu_share.<group>``     the MLPs' operations
    (``ssmblock_shapes.dense_flops``: three matrices of hidden x
    intermediate, 2 operations a multiply-add, three products a matrix; the
    driver hands them over in ``ssm_work``) over those seconds over the
    chip's bfloat16 peak (``peaks.json``). A product made again is time
    and not operations, so it reads under what the matrix unit does.

The seconds are ``layers/ssm.py``'s join (the driver's ``check`` hands it
over as ``run["ssm_s"]`` before ``run.py`` deletes the trace): a cell whose
driver hands none, a join that files under ``ssm.FILED_FLOOR`` of busy, or
a program without the scope, answers ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.layers import ssm


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    seen = ssm.joined(ctx)
    if not seen or seen.get("dense_s", 0.0) <= 0:
        return None
    if what == "dense_device_share":
        return 100.0 * seen["dense_s"] / seen["busy_s"]
    if what == "dense_mxu_share":
        return ssm.mxu_share((ctx["run"].get("ssm_work") or {}).get(
            "dense_flops"), seen["dense_s"], ctx)
    return None
