"""Ratios of the counts the program's spans carry and no other reader
takes (``multiverso_tpu/telemetry/trace.py``'s ring, the window's spans:
``prof`` true, as in ``layers/prog.py``). Counts repeat exactly for a
seed: they say what the traffic asked of a layer, not how fast it went.

==============================  ===========================================
``counts.unique_share.*``       100 x sum ``unique_rows`` / sum
                                ``update_rows`` over the window's
                                ``we.fused`` spans: the distinct rows the
                                table writes were handed, of the pairs'
                                update rows before combining
``counts.head_share.*``         100 x ``head_rows`` / ``unique_rows``: the
                                distinct rows the dense adds of the tables'
                                heads took
``counts.walk_fill_share.*``    100 x (``unique_rows`` - ``head_rows``) /
                                sum of ``walk_slots_by_shard``: the slots
                                of the walks that held a row (a walk costs
                                its SLOTS, a last chunk's pads included)
``counts.overflow_rows.*``      sum of ``overflow_rows`` over the window's
                                ``lm.step`` spans: rows routed to a held
                                expert that its buffer had no room for (a
                                run with any is not ``correct``)
==============================  ===========================================

A reader that finds no such count (a program from before it, a cell
that does not run that path) returns ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.layers import prog


def _sum(events: List[Dict[str, Any]], span: str, count: str
         ) -> Optional[float]:
    """``count`` added up over the window's ``span`` records that carry
    it (a list, such as ``walk_slots_by_shard``, by its own sum);
    ``None`` if none does."""
    values = [e["args"][count] for e in events
              if e.get("name") == span and e.get("prof")
              and count in e.get("args", {})]
    if not values:
        return None
    return sum(sum(v) if isinstance(v, list) else v for v in values)


def _share(part: Optional[float], whole: Optional[float]) -> Optional[float]:
    return 100.0 * part / whole if part is not None and whole else None


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    what = name.split(".")[1]
    if what == "overflow_rows":
        return _sum(events, "lm.step", "overflow_rows")
    unique, head = (_sum(events, "we.fused", c)
                    for c in ("unique_rows", "head_rows"))
    if what == "unique_share":
        return _share(unique, _sum(events, "we.fused", "update_rows"))
    if what == "head_share":
        return _share(head, unique)
    if what == "walk_fill_share" and None not in (unique, head):
        return _share(unique - head,
                      _sum(events, "we.fused", "walk_slots_by_shard"))
    return None


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    return read_events(name, prog.program_events())
