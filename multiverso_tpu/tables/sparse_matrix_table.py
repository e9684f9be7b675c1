"""SparseMatrixTable: stale-row tracking + minimal host transfer.

TPU-native equivalent of the reference sparse matrix protocol
(ref: include/multiverso/table/matrix.h + src/table/matrix.cpp:432-572 and the
older src/table/sparse_matrix_table.cpp). The reference server keeps
``up_to_date_[worker][row]`` dirty bits: a Get returns *only the rows that are
stale for the requesting worker* (caller passes worker_id in GetOption,
matrix.cpp:475-483), and an Add marks the touched rows stale for every worker
(:516-540). The SparseFilter additionally compresses the wire payload to
(index, value) pairs (sparse_matrix_table.cpp:147-153).

Here the expensive "wire" is device<->host transfer (HBM -> host DMA), and the
protocol becomes two-phase:

1. a jitted op gathers the dirty bits for the requested rows for this worker
   and clears them (one tiny bool vector to host);
2. only the stale rows are gathered and transferred (bucketed, so XLA shapes
   stay static), then merged into a worker-side host cache.

Fresh rows never cross the wire — the same bandwidth win the reference gets,
achieved with ICI/DMA instead of MPI messages. The (index, value) pairing of
the SparseFilter is inherent in the row-batch encoding.

``is_pipeline`` parity (matrix.cpp:407-418 doubles per-worker state slots to
tolerate double-buffered prefetch): JAX async dispatch already sequences the
clear-bits op against later adds, so no extra slots are needed; the
double-buffer utility lives in utils/async_buffer.py.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from multiverso_tpu import updaters as updaters_lib
from multiverso_tpu.tables.matrix_table import MatrixTable
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils.dashboard import monitor
from multiverso_tpu.zoo import Zoo


class SparseMatrixTable(MatrixTable):
    def __init__(self, num_row: int, num_col: int, dtype=jnp.float32,
                 updater: Union[str, updaters_lib.Updater, None] = None,
                 name: str = "sparse_matrix",
                 init=None, seed: Optional[int] = None,
                 init_scale: float = 0.0,
                 num_workers: Optional[int] = None):
        super().__init__(num_row, num_col, dtype=dtype, updater=updater,
                         name=name, init=init, seed=seed,
                         init_scale=init_scale)
        self._n_workers = num_workers or Zoo.get().num_workers()
        # dirty[worker, row]: True = row changed since this worker last pulled
        # it. Starts all-True so the first Get pulls everything
        # (ref matrix.cpp: up_to_date_ starts false).
        dirty_spec = NamedSharding(self._mesh, P(None, self._axis))
        self._dirty = jax.device_put(
            np.ones((self._n_workers, self._padded_rows), dtype=bool),
            dirty_spec)
        # Worker-side row caches (the reference worker's local buffer the
        # sparse Get merges into), allocated lazily per worker AND keyed by
        # row: the workload class this table exists for (21M vocab x 300 dim,
        # ref Applications/WordEmbedding/README.md) makes a dense
        # (num_row, num_col) host mirror ~25 GB per worker — the cache must
        # cost O(rows actually pulled), not O(table).
        self._cache: dict = {}

    def _worker_cache(self, worker_id: int) -> "_RowCache":
        if not (0 <= worker_id < self._n_workers):
            raise IndexError(
                f"worker_id {worker_id} out of range [0, {self._n_workers})")
        cache = self._cache.get(worker_id)
        if cache is None:
            cache = self._cache[worker_id] = _RowCache(self.num_col,
                                                       self.dtype)
        return cache

    def cache_nbytes(self, worker_id: int) -> int:
        """Host bytes held by ``worker_id``'s row cache (diagnostic)."""
        return self._worker_cache(worker_id).nbytes

    # ------------------------------------------------------------------ #
    # jitted helpers
    # ------------------------------------------------------------------ #
    def _mark_dirty_fn(self, bucket: int):
        key = ("mark_dirty", bucket)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = jax.jit(lambda dirty, ids: dirty.at[:, ids].set(True),
                         donate_argnums=(0,))
            self._jit_cache[key] = fn
        return fn

    def _take_stale_fn(self, bucket: int):
        key = ("take_stale", bucket)
        fn = self._jit_cache.get(key)
        if fn is None:
            def _take(dirty, ids, wid):
                mask = dirty[wid, ids]
                dirty = dirty.at[wid, ids].set(False)
                return dirty, mask
            fn = jax.jit(_take, donate_argnums=(0,))
            self._jit_cache[key] = fn
        return fn

    # ------------------------------------------------------------------ #
    # ops
    # ------------------------------------------------------------------ #
    def _rows_applied(self, ids: np.ndarray) -> None:
        """Mark the applied rows stale for every worker. Fed the CROSS-
        PROCESS UNION by MatrixTable.add_rows_async, so rows contributed
        only by other workers still invalidate this worker's cache (ref
        matrix.cpp:516-540 marks on the server, which sees the union by
        construction). Pad slots point at the scratch row — marking it is
        harmless (it is never a visible row)."""
        self._dirty = self._mark_dirty_fn(ids.size)(
            self._dirty, jax.device_put(ids, self._replicated))

    def add_async(self, delta, opt: Optional[AddOption] = None) -> int:
        msg_id = super().add_async(delta, opt)
        # Whole-table add dirties every row for every worker. The reference's
        # sparse mode auto-detects nonzero rows of a full add
        # (matrix.cpp:147-182); callers with sparse deltas should use
        # add_rows, which is that detection done at the source.
        fn = self._jit_cache.get("dirty_all")
        if fn is None:
            fn = self._jit_cache["dirty_all"] = jax.jit(jnp.ones_like)
        self._dirty = fn(self._dirty)
        return msg_id

    def get_rows_sparse(self, row_ids, worker_id: int = 0) -> np.ndarray:
        """Pull rows, transferring only the ones stale for ``worker_id``.

        Returns the requested rows (fresh ones served from the worker cache).
        ref matrix.cpp:475-483 (GetOption.worker_id) + :540-572 (stale-only
        reply).
        """
        with monitor(f"table[{self.name}].get_rows_sparse"), self._dispatch_lock:
            cache = self._worker_cache(worker_id)
            ids = np.asarray(row_ids, dtype=np.int64).reshape(-1)
            uids, _, k, inv = self._prep_ids(row_ids)
            dev_ids = jax.device_put(uids, self._replicated)
            self._dirty, mask = self._take_stale_fn(uids.size)(
                self._dirty, dev_ids, worker_id)
            mask_host = self._to_host(mask)[:k]
            stale = uids[:k][mask_host]
            if stale.size:
                rows = super().get_rows(stale)
                cache.put(stale, rows)
            return cache.take(ids)

    def stale_fraction(self, row_ids, worker_id: int = 0) -> float:
        """Diagnostic: fraction of the requested rows that would transfer."""
        self._worker_cache(worker_id)  # validates worker_id
        if np.asarray(row_ids).size == 0:
            return 0.0
        uids, _, k, _ = self._prep_ids(row_ids)
        key = ("stale_frac", uids.size)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = self._jit_cache[key] = jax.jit(
                lambda dirty, ids, wid: dirty[wid, ids])
        mask = self._to_host(fn(self._dirty,
                                jax.device_put(uids, self._replicated),
                                worker_id))[:k]
        return float(mask.mean()) if k else 0.0


class _RowCache:
    """Row-keyed worker cache: a sorted-key index (row_id -> slot, resolved
    with ``np.searchsorted`` so lookups stay vectorized) over a growable
    (slots, num_col) buffer. Memory is O(distinct rows pulled) with amortized
    doubling — the sparse analogue of the reference worker's local row buffer
    (ref src/table/matrix.cpp worker side), sized for 21M-vocab tables."""

    def __init__(self, num_col: int, dtype):
        self._num_col = int(num_col)
        self._dtype = dtype
        self._keys = np.empty(0, np.int64)    # sorted distinct row ids
        self._slots = np.empty(0, np.int64)   # buffer slot per sorted key
        self._buf = np.empty((0, self._num_col), dtype)
        self._n = 0                           # slots in use

    @property
    def nbytes(self) -> int:
        return self._buf.nbytes + self._keys.nbytes + self._slots.nbytes

    def _ensure(self, extra: int) -> None:
        need = self._n + extra
        if need <= self._buf.shape[0]:
            return
        cap = max(8, self._buf.shape[0])
        while cap < need:
            cap *= 2
        buf = np.empty((cap, self._num_col), self._dtype)
        buf[: self._buf.shape[0]] = self._buf
        self._buf = buf

    def _locate(self, ids: np.ndarray):
        """(insertion positions, found mask) of ``ids`` in the key index."""
        pos = np.searchsorted(self._keys, ids)
        if self._keys.size == 0:
            return pos, np.zeros(ids.size, bool)
        clip = np.minimum(pos, self._keys.size - 1)
        return clip, self._keys[clip] == ids

    def put(self, ids: np.ndarray, rows: np.ndarray) -> None:
        """Insert/overwrite rows; ``ids`` must be distinct (callers pass the
        unique stale subset of an already-deduped batch)."""
        ids = np.asarray(ids, np.int64)
        clip, found = self._locate(ids)
        n_new = int(ids.size - found.sum())
        self._ensure(n_new)
        slots = np.empty(ids.size, np.int64)
        slots[found] = self._slots[clip[found]]
        if n_new:
            new_slots = np.arange(self._n, self._n + n_new)
            slots[~found] = new_slots
            # insert at their searchsorted positions: O(K + n log n), not a
            # full re-sort of the K cached keys per pull
            order = np.argsort(ids[~found], kind="stable")
            nk, ns = ids[~found][order], new_slots[order]
            at = np.searchsorted(self._keys, nk)
            self._keys = np.insert(self._keys, at, nk)
            self._slots = np.insert(self._slots, at, ns)
            self._n += n_new
        self._buf[slots] = rows

    def take(self, ids: np.ndarray) -> np.ndarray:
        """Rows for ``ids``; every id must be cached (fresh rows were pulled
        by an earlier sparse Get — dirty bits start all-True, so a never-
        pulled row is always stale and lands in the cache first)."""
        ids = np.asarray(ids, np.int64)
        clip, found = self._locate(ids)
        if not found.all():
            raise KeyError(
                f"rows {ids[~found][:5].tolist()}... not cached (stale "
                "protocol invariant violated)")
        return self._buf[self._slots[clip]]


class SparseMatrixTableOption:
    def __init__(self, num_row: int, num_col: int, dtype=jnp.float32,
                 updater=None, init=None, seed=None, init_scale: float = 0.0,
                 num_workers: Optional[int] = None):
        self.num_row, self.num_col = num_row, num_col
        self.dtype = dtype
        self.updater = updater
        self.init = init
        self.seed = seed
        self.init_scale = init_scale
        self.num_workers = num_workers

    def build(self, name: str = "sparse_matrix") -> SparseMatrixTable:
        return SparseMatrixTable(
            self.num_row, self.num_col, dtype=self.dtype,
            updater=self.updater, name=name, init=self.init, seed=self.seed,
            init_scale=self.init_scale, num_workers=self.num_workers)
