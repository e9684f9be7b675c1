"""Which shard owns a row, plainly: the reference's ``MatrixTable``
splits its rows contiguously over its servers and routes a row id to
``row_id / rows_per_server`` (ref src/table/matrix_table.cpp:24-45,
:266-313). Independent of ``multiverso_tpu``: what the program's
``update_rows_by_shard`` counts are held to."""

from __future__ import annotations

import numpy as np


def owner_rows(ids, rows: int, shards: int) -> np.ndarray:
    """The shard (0..shards-1) that owns each of the row ids ``ids`` of
    a table of ``rows`` rows split contiguously into ``shards`` equal
    parts. The table keeps one spare row past its last and pads to a
    multiple of ``shards``, so a part is ceil((rows + 1) / shards) rows
    (3,000,001 for 12,000,000 rows on four chips)."""
    per = -(-(int(rows) + 1) // int(shards))
    return np.asarray(ids, np.int64) // per
