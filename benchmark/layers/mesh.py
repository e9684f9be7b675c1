"""Layer "mesh": the sharded table path. A table row-sharded over the
chips of a host is gathered from and scattered into by a partitioned
program: every chip gathers the batch's rows from its own shard (masked),
an all-reduce rebuilds the rows on every chip, every chip computes the
whole gradient, and every chip scatters the whole update with the rows
it does not own masked.

``mesh.collective_share.<group>``: self time of the collective
operations over device busy time, both per chip, from the device trace.
An operation counts whose name starts ``all-reduce``, ``all-gather``,
``all-to-all``, ``reduce-scatter`` or ``collective-permute``. It is read
from the reduction's ``device_ops``, which keeps the ten operations with
the most self time and no more: a collective below the tenth place is
not seen, so the share is a FLOOR, and 0 says "none among the ten".

``mesh.owner_skew.<group>``: the busiest shard's share of the update
rows of the window (100 x max / sum of ``update_rows_by_shard`` over the
window's ``we.fused`` spans in the program's ring: the centres, contexts
and pool rows each contiguous row shard owns). 100 / shards is even (25
on four chips); near 100 one shard owns nearly every update.

A reader that finds nothing to read (no device operations in the trace;
a program that records no such count) returns ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.layers.prog import program_events

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")
SPAN, COUNT = "we.fused", "update_rows_by_shard"


def collective_share(trace: Dict[str, Any]) -> Optional[float]:
    if trace["busy_s"] <= 0 or not trace["device_ops"]:
        return None
    seconds = sum(s for label, s in trace["device_ops"]
                  if label.startswith(COLLECTIVES))
    return 100.0 * seconds / trace["busy_s"]


def owner_skew(events: List[Dict[str, Any]]) -> Optional[float]:
    by_shard: List[int] = []
    for e in events:
        rows = e.get("args", {}).get(COUNT)
        if e.get("name") == SPAN and e.get("prof") and rows:
            by_shard = [a + b for a, b in
                        zip(rows, by_shard or [0] * len(rows))]
    total = sum(by_shard)
    return 100.0 * max(by_shard) / total if total else None


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    what = name.split(".")[1]
    if what == "collective_share":
        return collective_share(ctx["trace"])
    if what == "owner_skew":
        return owner_skew(program_events())
    return None
