"""Layer "short-convolution mixer" (``multiverso_tpu/models/lfm2_moe.py``):
the gated short convolution between two matrix products.

``conv.mixer_flops_share.<group>``: from the window's ``lm.step`` spans,
100 x ``mixer_flops_token`` / ``step_flops_token``: the conv mixers' part
of the matrix-product operations a token needs in a forward pass of the
whole step on this chip (the held experts at their even share), as the
program counts them from its configuration (``models/lfm2_moe.
LFM2MoEConfig.conv_grid``; ``benchmark/conv_shapes.py`` counts them again
from the configuration's file, and a test holds the two equal). Static for
a cell: a guard on the cut (which layers run, how many experts are held,
the vocabulary's slice) and on the mixer's two products, as
``attnmix.band_pairs_share`` is on the band.

The mixer's DEVICE time has no reader: its products and fusions are XLA's
and carry no scope into the trace; ``chip_smoke.py`` stage ``conv`` reads
the mixer alone. A program without the span's counts answers ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.layers import lm, prog


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    if name.split(".")[1] != "mixer_flops_share":
        return None
    steps = [e["args"] for e in lm.window_steps(events)
             if e["args"].get("step_flops_token")
             and "mixer_flops_token" in e["args"]]
    if not steps:
        return None
    return (100.0 * sum(a["mixer_flops_token"] for a in steps)
            / sum(a["step_flops_token"] for a in steps))


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    return read_events(name, prog.program_events())
