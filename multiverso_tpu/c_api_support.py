"""Python side of the C ABI shim (see native/mv_capi.cpp).

The C layer passes raw pointers as integers; this module wraps them with
ctypes into zero-copy numpy views and forwards to the real tables. Handles
are small integers into a registry (the reference's ``TableHandler = void*``,
ref include/multiverso/c_api.h:14).
"""

from __future__ import annotations

import ctypes
from typing import Dict

import numpy as np

import multiverso_tpu as mv

_tables: Dict[int, object] = {}
_next_handle = 1


def _view(addr: int, size: int) -> np.ndarray:
    return np.ctypeslib.as_array(
        (ctypes.c_float * size).from_address(addr))


def _iview(addr: int, size: int) -> np.ndarray:
    return np.ctypeslib.as_array(
        (ctypes.c_int32 * size).from_address(addr))


def init() -> None:
    mv.init()


def shutdown() -> None:
    mv.shutdown()


def barrier() -> None:
    # The C ABI has no flush entry point; FFI clients (the reference's Lua
    # test battery) use MV_Barrier as the fence after async adds. Sync
    # tables are fenced by mv.barrier()'s dirty-shard walk; async-plane
    # tables need an explicit flush of this process's outstanding ops.
    # The barrier itself must run even if a flush raises (a swept
    # fire-and-forget failure or dead peer): aborting early would leave
    # the other ranks blocked in mv.barrier() forever — the C layer only
    # prints-and-clears Python errors, it cannot unwind the peers.
    errors = []
    for t in list(_tables.values()):
        flush = getattr(t, "flush", None)
        if callable(flush):
            try:
                flush()
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append(exc)
    mv.barrier()
    if errors:
        # Surface EVERY flush failure, not just the first: the remaining
        # ones are logged (a multi-table flush failure must not vanish
        # behind the one that raises) and chained onto the raised
        # exception as its __cause__ so tracebacks show at least two.
        from multiverso_tpu.utils import log
        for exc in errors[1:]:
            log.error("barrier: additional async-table flush failure "
                      "(first one is raised): %s: %s",
                      type(exc).__name__, exc)
        if len(errors) > 1:
            raise errors[0] from errors[1]
        raise errors[0]


def num_workers() -> int:
    return mv.num_workers()


def worker_id() -> int:
    return mv.worker_id()


def server_id() -> int:
    return mv.server_id()


def _register(table) -> int:
    global _next_handle
    handle = _next_handle
    _next_handle += 1
    _tables[handle] = table
    return handle


def new_array_table(size: int) -> int:
    return _register(mv.ArrayTable(size, dtype=np.float32,
                                   name=f"c_array_{_next_handle}"))


def array_get(handle: int, addr: int, size: int) -> None:
    _tables[handle].get(out=_view(addr, size))


def array_add(handle: int, addr: int, size: int, do_wait: int) -> None:
    t = _tables[handle]
    data = _view(addr, size).copy()
    if do_wait:
        t.add(data)
    else:
        t.add_async(data)


def new_matrix_table(num_row: int, num_col: int) -> int:
    return _register(mv.MatrixTable(num_row, num_col, dtype=np.float32,
                                    name=f"c_matrix_{_next_handle}"))


def new_async_array_table(size: int) -> int:
    """Uncoordinated-plane array table for FFI clients (beyond the
    reference C API, which only reached the sync tables): every process
    owns a row range served by its PSService, ops ride the native C++
    transport where built. The generic array_get/array_add accessors
    work unchanged — the async tables share the op surface."""
    return _register(mv.AsyncArrayTable(size, dtype=np.float32,
                                        name=f"c_async_array_{_next_handle}"))


def new_async_matrix_table(num_row: int, num_col: int) -> int:
    """Uncoordinated-plane matrix table for FFI clients (see
    new_async_array_table); matrix_* accessors work unchanged."""
    return _register(mv.AsyncMatrixTable(
        num_row, num_col, dtype=np.float32,
        name=f"c_async_matrix_{_next_handle}"))


def matrix_get_all(handle: int, addr: int, size: int) -> None:
    t = _tables[handle]
    _view(addr, size)[:] = t.get().reshape(-1)[:size]


def matrix_add_all(handle: int, addr: int, size: int, do_wait: int) -> None:
    t = _tables[handle]
    data = _view(addr, size).copy().reshape(t.num_row, t.num_col)
    if do_wait:
        t.add(data)
    else:
        t.add_async(data)


def matrix_get_rows(handle: int, addr: int, size: int, ids_addr: int,
                    ids_n: int) -> None:
    t = _tables[handle]
    ids = _iview(ids_addr, ids_n).copy()
    rows = t.get_rows(ids)
    _view(addr, size)[:] = rows.reshape(-1)[:size]


def matrix_add_rows(handle: int, addr: int, size: int, ids_addr: int,
                    ids_n: int, do_wait: int) -> None:
    t = _tables[handle]
    ids = _iview(ids_addr, ids_n).copy()
    vals = _view(addr, size).copy().reshape(ids_n, t.num_col)
    if do_wait:
        t.add_rows(ids, vals)
    else:
        t.add_rows_async(ids, vals)
