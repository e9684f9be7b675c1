"""MatrixTable: 2-D row-sharded parameter matrix with row-batch Add/Get.

TPU-native equivalent of the reference MatrixTable family
(ref: include/multiverso/table/matrix_table.h, src/table/matrix_table.cpp and
the newer include/multiverso/table/matrix.h / src/table/matrix.cpp). The
reference row-shards across servers in contiguous blocks
(src/table/matrix_table.cpp:24-45) and routes row ids to servers by
``row_id / rows_per_server`` (:266-313). Here the same layout is
``NamedSharding(mesh, P(axis, None))`` and row routing is XLA gather/scatter
over ICI.

Row-batch ops and XLA static shapes: row-id sets have dynamic size, which
fights jit compilation (SURVEY §7 "hard parts"). We bucket the batch size to
the next power of two, pad the id list with a dedicated *scratch row* that
lives in the table's row padding (never logically visible), and mask nothing:
padded entries gather the scratch row, compute garbage, and scatter garbage
back into the scratch row only. One compiled program per bucket size.

Updater locality parity: the reference server applies the updater only to the
*received* rows of a row Add (untouched rows keep their momentum/adagrad state
frozen). We reproduce that with gather -> per-row updater -> scatter, instead
of a full-table update with a zero-padded delta (which would decay untouched
rows under momentum).

Duplicate row ids within one call are pre-aggregated host-side
(``np.add.at``), matching the reference's per-row accumulation order-free.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu import updaters as updaters_lib
from multiverso_tpu.ops import row_assemble as _rowasm
from multiverso_tpu.serving import hotcache as _hotcache
from multiverso_tpu.table import ArrayLike, Table
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import config
from multiverso_tpu.utils.dashboard import monitor

# NOTE: the hand-written Pallas row gather/scatter kernels that once sat
# behind a "pallas" flag were REMOVED (r4): measured on-chip, XLA's native
# gather/scatter beat them at every bucket size tried (375 vs 408 us row
# add at 4k rows; 1.1 vs 3.2 ms scatter at 49k), so they were dead weight.
# The winning Pallas kernels live in ops/attention_kernels.py (flash
# attention fwd+bwd, default ON in the transformer).


def _bucket_size(k: int, cap: int) -> int:
    # one bucketing rule repo-wide (ops/row_assemble.bucket_rows is the
    # shared home): the cache mirror's jit-trace buckets and the table
    # layer's must never drift apart, or warm programs retrace
    return min(_rowasm.bucket_rows(k), cap)


class MatrixTable(Table):
    def __init__(self, num_row: int, num_col: int, dtype=jnp.float32,
                 updater: Union[str, updaters_lib.Updater, None] = None,
                 name: str = "matrix",
                 init=None, seed: Optional[int] = None,
                 init_scale: float = 0.0):
        super().__init__((int(num_row), int(num_col)), dtype=dtype,
                         updater=updater, name=name, init=init, seed=seed,
                         init_scale=init_scale)
        # hot-row training cache (flag train_cache_rows; ISSUE 11): a
        # full-hit get serves host rows with no device gather/transfer.
        # Write-through is exact here even multi-process: the collective
        # row add hands every process the UNION delta the updater applies,
        # so a plain-add table's cached copy tracks the device rows
        # bit-for-bit
        self._train_cache = _hotcache.make_train_cache(
            name, int(num_col), self.dtype,
            writethrough_ok=(getattr(self.updater, "name", "")
                             == "default"))

    @property
    def num_row(self) -> int:
        return self.shape[0]

    @property
    def num_col(self) -> int:
        return self.shape[1]

    @property
    def _scratch_row(self) -> int:
        # Table.__init__ pads rows to a multiple of shards with >= 1 spare.
        return self._padded_rows - 1

    # ------------------------------------------------------------------ #
    # jitted row programs (one per bucket size)
    # ------------------------------------------------------------------ #
    def _state_row_axis(self, leaf) -> Optional[int]:
        """Axis of ``leaf`` that corresponds to the table row axis, or None."""
        nd, pd = np.ndim(leaf), len(self._padded_shape)
        if nd >= pd and tuple(np.shape(leaf)[nd - pd:]) == self._padded_shape:
            return nd - pd
        return None

    def _row_update_fn(self, bucket: int):
        key = ("row_update", bucket)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn

        def _update(data, ustate, ids, vals, opt):
            state = self.functional_add_rows(
                {"data": data, "ustate": ustate}, ids, vals, opt,
                sorted_ids=True)    # _prep_ids: np.unique, then scratch rows
            token = jnp.ravel(state["data"])[0]
            return state["data"], state["ustate"], token

        fmt = self.state_format     # a row program: see program_state
        fn = jax.jit(_update, donate_argnums=(0, 1),
                     out_shardings=(fmt["data"], fmt["ustate"], None))
        self._jit_cache[key] = fn
        return fn

    def _row_get_fn(self):
        # one cached fn: jit's own shape-keyed trace cache handles the
        # per-bucket variation
        fn = self._jit_cache.get("row_get")
        if fn is None:
            fn = jax.jit(lambda data, ids: jnp.take(data, ids, axis=0))
            self._jit_cache["row_get"] = fn
        return fn

    def _prep_ids(self, row_ids, values: Optional[np.ndarray] = None
                  ) -> Tuple[np.ndarray, Optional[np.ndarray], int,
                             Optional[np.ndarray]]:
        """Dedupe, validate, and bucket-pad a row-id batch.

        Returns (padded_ids, padded_vals, unique_count, inverse) where
        ``inverse`` maps each original position to its unique slot (used by
        get_rows to re-expand duplicates). Deduping both directions keeps the
        unique count <= num_row <= padded_rows, so the bucket cap can never
        underflow the pad.
        """
        raw = np.asarray(row_ids)
        if raw.size == 0:
            raise ValueError("empty row_ids")
        if not np.issubdtype(raw.dtype, np.integer):
            raise TypeError(f"row_ids must be integers, got dtype "
                            f"{raw.dtype} (silent float truncation would "
                            f"hit arbitrary rows)")
        ids = raw.astype(np.int32).reshape(-1)
        if np.any((ids < 0) | (ids >= self.num_row)):
            raise IndexError(f"row id out of range [0, {self.num_row})")
        uids, inv = np.unique(ids, return_inverse=True)
        if values is not None:
            vals = np.asarray(values, dtype=self.dtype).reshape(
                ids.size, self.num_col)
            acc = np.zeros((uids.size, self.num_col), dtype=np.float64)
            np.add.at(acc, inv, vals.astype(np.float64))
            vals = acc.astype(self.dtype)
        else:
            vals = None
        ids = uids.astype(np.int32)
        k = ids.size
        bucket = _bucket_size(k, self._padded_rows)
        pad = bucket - k
        if pad:
            ids = np.concatenate(
                [ids, np.full(pad, self._scratch_row, np.int32)])
            if vals is not None:
                vals = np.concatenate(
                    [vals, np.zeros((pad, self.num_col), self.dtype)])
        return ids, vals, k, inv

    def _union_across_processes(self, ids: np.ndarray, vals: np.ndarray
                                ) -> Tuple[np.ndarray, np.ndarray]:
        """Merge per-process (ids, vals) into the deduped union with summed
        values, identically on every process. Ids arrive bucket-padded with
        the scratch row; sizes differ per process, so the allgather pads to
        the global max bucket with scratch/zero first."""
        from jax.experimental import multihost_utils
        n = np.array([ids.size], np.int64)
        max_n = int(np.max(multihost_utils.process_allgather(n, tiled=False)))
        if ids.size < max_n:
            pad = max_n - ids.size
            ids = np.concatenate(
                [ids, np.full(pad, self._scratch_row, np.int32)])
            vals = np.concatenate(
                [vals, np.zeros((pad, self.num_col), self.dtype)])
        gids = np.asarray(multihost_utils.process_allgather(ids, tiled=False))
        gvals = np.asarray(multihost_utils.process_allgather(vals,
                                                             tiled=False))
        flat_ids = gids.reshape(-1)
        flat_vals = gvals.reshape(-1, self.num_col)
        keep = flat_ids != self._scratch_row
        uids, inv = np.unique(flat_ids[keep], return_inverse=True)
        acc = np.zeros((uids.size, self.num_col), np.float64)
        np.add.at(acc, inv, flat_vals[keep].astype(np.float64))
        ids = uids.astype(np.int32)
        vals = acc.astype(self.dtype)
        bucket = _bucket_size(ids.size, self._padded_rows)
        if bucket > ids.size:
            pad = bucket - ids.size
            ids = np.concatenate(
                [ids, np.full(pad, self._scratch_row, np.int32)])
            vals = np.concatenate(
                [vals, np.zeros((pad, self.num_col), self.dtype)])
        return ids, vals

    # ------------------------------------------------------------------ #
    # public row ops (ref matrix_table.h:26-75 overload family)
    # ------------------------------------------------------------------ #
    def add_rows_async(self, row_ids, values,
                       opt: Optional[AddOption] = None) -> int:
        opt = opt or AddOption()
        self._mark_mutated()
        with monitor(f"table[{self.name}].add_rows"), self._dispatch_lock:
            ids, vals, _, _ = self._prep_ids(row_ids, values)
            if self._zoo.size() > 1:
                # collective row add; per-process id sets may DIFFER (the
                # WordEmbedding traffic pattern, ref communicator.cpp:
                # 104-142): processes agree on the union of their ids and
                # sum the contributions. Still lockstep (every process must
                # call) — the uncoordinated path is multiverso_tpu.ps.
                ids, vals = self._union_across_processes(ids, vals)
            if self._train_cache is not None:
                # the UNION delta — exactly what the updater applies (pad
                # slots point at scratch_row >= num_row: never cached, so
                # their zero vals are ignored by the cache)
                self._train_cache.on_push(ids, vals)
            fn = self._row_update_fn(ids.size)
            self._lay_out(own=True)
            self._data, self._ustate, token = fn(
                self._data, self._ustate,
                jax.device_put(ids, self._replicated),
                jax.device_put(vals, self._replicated), opt)
            # subclass hook, fed the ids ACTUALLY applied (the cross-process
            # union, not just this worker's set): the sparse table's dirty
            # bits must cover rows other workers contributed
            self._rows_applied(ids)
        return self._track(token)

    def _rows_applied(self, ids: np.ndarray) -> None:
        """Called under the dispatch lock with the final (deduped, padded,
        cross-process-unioned) row ids of an add. Default: nothing."""

    def add_rows(self, row_ids, values, opt: Optional[AddOption] = None) -> None:
        self.wait(self.add_rows_async(row_ids, values, opt))

    def get_rows_async(self, row_ids) -> int:
        with monitor(f"table[{self.name}].get_rows"), self._dispatch_lock:
            ids, _, k, inv = self._prep_ids(row_ids)
            tc = self._train_cache
            uids = ids[:k]
            token = 0
            if tc is not None:
                tc.on_get()
                # serve_full: token + membership + gather in ONE cache
                # lock hold (a wait()-thread fill_since cannot skew
                # positions mid-serve); pushes order against the token
                # via _dispatch_lock, which both paths hold. All-or-
                # nothing: the partial path below refetches ALL k rows
                # from the device, so a partial host gather is wasted
                token, buf = tc.serve_full(uids.astype(np.int64))
                if buf is not None:
                    # full hit: serve the host copy — no device gather,
                    # no device->host transfer (write-through keeps it
                    # bit-identical to the device rows; invalidate
                    # guarantees pushed rows can't be here)
                    tc.count(k, 0)
                    return self._track(buf, lambda b: b[inv])
                tc.count(0, k)
            fn = self._row_get_fn()
            rows = fn(self._data, jax.device_put(ids, self._replicated))
            try:
                rows.copy_to_host_async()
            except AttributeError:
                pass

            def _fin(r):
                host = self._to_host(r)[:k]
                if tc is not None:
                    # warm for the next block, reconciled against pushes
                    # dispatched since the token (fill_since replay)
                    tc.fill_since(uids.astype(np.int64), host, token)
                return host[inv]

            return self._track(rows, _fin)

    def get_rows(self, row_ids, out: Optional[np.ndarray] = None) -> np.ndarray:
        host = self.wait(self.get_rows_async(row_ids))
        if out is not None:
            np.copyto(out.reshape(host.shape), host)
            return out
        return host

    def get_row(self, row_id: int, out: Optional[np.ndarray] = None) -> np.ndarray:
        row = self.get_rows([row_id])
        if out is not None:
            np.copyto(out.reshape(self.num_col), row[0])
            return out
        return row[0]

    def add_row(self, row_id: int, values,
                opt: Optional[AddOption] = None) -> None:
        self.add_rows([row_id], np.asarray(values).reshape(1, -1), opt)

    # ------------------------------------------------------------------ #
    # hot-row training cache (serving/hotcache.TrainRowCache) — same
    # surface as AsyncMatrixTable so the WE block driver is plane-blind
    # ------------------------------------------------------------------ #
    def train_cache_stats(self) -> Optional[Dict]:
        tc = self._train_cache
        return None if tc is None else tc.stats()

    def train_cache_device_block(self, row_ids, bucket: int):
        """Fused gather+pad device serve when EVERY id is cached (see
        AsyncMatrixTable.train_cache_device_block); None = fall back to
        get_rows_async, which counts its own hit/miss."""
        tc = self._train_cache
        if tc is None:
            return None
        return tc.device_block_counted(row_ids, bucket)

    # ------------------------------------------------------------------ #
    # functional plane for in-graph row traffic (used by word2vec)
    # ------------------------------------------------------------------ #
    def functional_add_rows(self, state: Dict[str, Any], ids: jax.Array,
                            vals: jax.Array,
                            opt: Optional[AddOption] = None,
                            sorted_ids: bool = False) -> Dict[str, Any]:
        """Pure row-batch add; ``ids``/``vals`` static-shaped, caller masks
        unused slots by pointing them at scratch_row with zero vals.
        ``sorted_ids`` promises ids in ascending order (scratch-row slots
        last: it is the highest row), which the compiler is told
        (``indices_are_sorted``): without it the v5e compiler takes 12 s
        over the write-back of a 524,288-row bucket into 1.8M rows, with
        it 0.4 s."""
        opt = opt or AddOption()
        row_axes = jax.tree.map(self._state_row_axis, state["ustate"])

        def gather(leaf, axis):
            if axis is None:
                return leaf
            return jnp.take(leaf, ids, axis=axis,
                            indices_are_sorted=sorted_ids)

        def scatter(leaf, new_leaf, axis):
            if axis is None:
                return new_leaf
            idx = (slice(None),) * axis + (ids,)
            return leaf.at[idx].set(new_leaf, indices_are_sorted=sorted_ids)

        # device-trace names (metadata only): the updater's apply is
        # mv.rowapply.rule
        with jax.named_scope("mv.rowapply.gather"):
            rows = gather(state["data"], 0)
            gstate = jax.tree.map(gather, state["ustate"], row_axes)
        new_rows, new_gstate = self.updater.apply(rows, gstate, vals, opt)
        with jax.named_scope("mv.rowapply.scatter"):
            data = scatter(state["data"], new_rows, 0)
            ustate = jax.tree.map(scatter, state["ustate"], new_gstate,
                                  row_axes)
        return {"data": data, "ustate": ustate}

    @property
    def scratch_row(self) -> int:
        return self._scratch_row


class MatrixTableOption:
    """ref DEFINE_TABLE_TYPE option parity for mv.create_table."""

    def __init__(self, num_row: int, num_col: int, dtype=jnp.float32,
                 updater=None, init=None, seed=None, init_scale: float = 0.0):
        self.num_row, self.num_col = num_row, num_col
        self.dtype = dtype
        self.updater = updater
        self.init = init
        self.seed = seed
        self.init_scale = init_scale

    def build(self, name: str = "matrix") -> MatrixTable:
        return MatrixTable(self.num_row, self.num_col, dtype=self.dtype,
                           updater=self.updater, name=name, init=self.init,
                           seed=self.seed, init_scale=self.init_scale)
