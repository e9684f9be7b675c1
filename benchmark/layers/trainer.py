"""Layer "trainers": host-side timers around the training loop.

``trainer.prepare_ms``: the median of the program's own ``we.prepare``
monitor (``utils/dashboard``) over the window, as its latency histogram
estimates it (about one bucket of relative error). The others are the
benchmark's own spans, which a driver hands over in ``spans_ms``:
``trainer.block_ms`` the median of ``block`` (a call's seconds over its
blocks; a call is seconds long, so the host clock is good for it),
``trainer.feed_ms`` the median of ``feed`` (host to device copy of one
batch), ``trainer.step_p95_ms`` the 95th percentile of ``step`` (feed +
step + loss read-back). The sample count of each is on the run's detail
line.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, Optional

MONITORS = {"prepare_ms": "we.prepare"}


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    run = ctx["run"]
    what = name.split(".")[1]
    if what in MONITORS:
        m = run.get("monitors", {}).get(MONITORS[what])
        return float(m["p50_ms"]) if m and m["count"] else None
    spans = run.get("spans_ms", {})
    if what in ("feed_ms", "block_ms") and spans.get(what[:-3]):
        return float(statistics.median(spans[what[:-3]]))
    if what == "step_p95_ms" and len(spans.get("step", ())) >= 20:
        return float(statistics.quantiles(spans["step"], n=20)[-1])
    return None
