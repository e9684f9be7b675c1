"""Bytes that the table-shaped operations must move through HBM, from
shapes alone, so that ``rowapply.hbm_share.*`` can be checked by hand.

"Must move" is the least the operation as the program states it can do:
every byte counted once per read and once per write, at the logical row
width (300 floats are 1,200 bytes; the 384-lane tile padding the chip
adds is the chip's cost, not the algorithm's, and counting it would
flatter the share). All sizes in bytes; ``width`` in elements.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peak(device_kind: str, key: str) -> float:
    """A published peak of ``device_kind`` from ``peaks.json``; an
    unlisted device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return float(table[device_kind][key])


def row_gather_bytes(n_rows: int, width: int, itemsize: int = 4) -> int:
    """Gather n rows out of a table: read each row, write it to the
    batch-shaped result. 2 * n * width * itemsize."""
    return 2 * n_rows * width * itemsize


def scatter_add_bytes(n_rows: int, width: int, itemsize: int = 4) -> int:
    """Duplicate-accumulating scatter-add of n row updates into a table in
    place: read the update, read the row, write the row.
    3 * n * width * itemsize (the untouched rows do not move)."""
    return 3 * n_rows * width * itemsize


def table_fill_bytes(rows: int, width: int, itemsize: int = 4) -> int:
    """Write a table-shaped array of zeros (the dense delta a step starts
    from): one write pass. rows * width * itemsize."""
    return rows * width * itemsize


def table_copy_bytes(rows: int, width: int, itemsize: int = 4) -> int:
    """Copy a whole table: one read pass, one write pass."""
    return 2 * rows * width * itemsize


def dense_update_bytes(rows: int, width: int, state_arrays: int,
                       itemsize: int = 4) -> int:
    """Apply an updater rule over a whole table from a dense delta: read
    data, delta and each state array, write data and each state array.
    (2 + 1 + 2 * state_arrays) passes; AdaGrad has one state array (5
    passes), plain SGD none (3 passes)."""
    return (3 + 2 * state_arrays) * rows * width * itemsize


def row_update_bytes(n_rows: int, width: int, state_arrays: int,
                     itemsize: int = 4) -> int:
    """Row-sparse updater application (``functional_add_rows``): gather
    data and state rows, read the delta, scatter data and state rows
    back. Same passes as the dense rule, over n rows only."""
    return (3 + 2 * state_arrays) * n_rows * width * itemsize
