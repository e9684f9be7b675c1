"""Layer "row apply": the scatter-add into a table and the updater rule
(``models/word2vec.py`` fused scatters, ``table.py:functional_add`` /
``functional_add_rows``, ``updaters/``), seen from the device trace as
the operations that have a table-shaped operand or result (the padded
row count and width of the cell's tables, which the driver reports).

``rowapply.device_share.<group>``: their self time over device busy time.
``rowapply.hbm_share.<group>``: the bytes they must move, which the
driver reckons from its own counts with ``shapes.py`` and hands over as
``must_move_bytes`` (this file knows no driver), over their self time,
over the chip's HBM peak (``peaks.json``). A run without that key
reports no share.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import shapes


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    trace, run = ctx["trace"], ctx["run"]
    what = name.split(".")[1]
    if trace["busy_s"] <= 0 or trace["table_s"] <= 0:
        return None
    if what == "device_share":
        return 100.0 * trace["table_s"] / trace["busy_s"]
    if what == "hbm_share":
        moved = run.get("must_move_bytes")
        if not moved:
            return None
        # the traced window is the measured window: same counts
        peak = shapes.peak(ctx["device_kind"], "hbm_bytes_per_s")
        return 100.0 * moved / trace["table_s"] / peak
    return None
