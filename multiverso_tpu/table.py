"""Table core: device-sharded parameter tables with Add/Get semantics.

TPU-native re-design of the reference table stack
(ref: include/multiverso/table_interface.h:24-75, src/table.cpp,
src/worker.cpp, src/server.cpp). The reference splits a table into a
WorkerTable (client: partitions requests per server, tracks msg_id Waiters)
and a ServerTable (storage shard + updater), connected by an actor/MPI message
path. On TPU both halves collapse into ONE object:

* storage     -> a single ``jax.Array`` sharded over the mesh's table axis;
                 each device shard IS the reference's "server shard".
* Add         -> a jitted, donated update: delta is scattered shard-wise over
                 ICI and the updater runs element-wise on every shard in
                 parallel (the Worker->Communicator->Server hop disappears
                 into XLA's sharding machinery).
* Get         -> device->host gather of the sharded array (XLA all-gather /
                 per-shard DMA instead of per-server reply messages).
* AddAsync /
  GetAsync    -> JAX async dispatch. Every op returns a msg-id; ``wait(id)``
                 blocks on the underlying arrays (the reference's msg_id ->
                 Waiter bookkeeping, src/table.cpp:27-97, maps onto XLA's
                 future machinery).
* updater     -> a pure function applied in-graph (see updaters/__init__.py).

Sync (BSP) semantics are *free*: program order on a single stream of donated
arrays gives every Get the state after all previously issued Adds — exactly
what the reference's SyncServer vector-clock machinery enforces
(src/server.cpp:68-222). Async mode is the JAX dispatch queue itself.

Tables also expose a **functional plane** for in-graph use: ``state`` /
``functional_add`` / ``adopt`` let a jitted training loop thread the table
through ``lax.scan`` at full speed, which is how the bundled apps hit the
hardware roofline rather than paying a host round-trip per step.
"""

from __future__ import annotations

import concurrent.futures
import functools
import os
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, PartitionSpec as P

from multiverso_tpu import updaters as updaters_lib
from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import config
from multiverso_tpu.utils import platform as _platform
from multiverso_tpu.utils.dashboard import Dashboard, monitor
from multiverso_tpu.zoo import Zoo

ArrayLike = Union[np.ndarray, jax.Array, Sequence]


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _uniform(seed: int, scale: float, shape: Tuple[int, ...], dtype,
             offset: int = 0) -> np.ndarray:
    """``default_rng(seed).uniform(-scale, scale, n).astype(dtype)``'s
    elements ``offset`` to ``offset + prod(shape)``, value for value, as
    an array of ``shape``; drawn by every core at once and a
    megabyte-sized piece at a time: a uniform double takes one step of
    the PCG64 stream, so a generator advanced by a piece's offset draws
    that piece (any row range of a table is addressable so, which is how
    a sharded table is drawn a shard at a time), and NumPy fills without
    the interpreter lock. (Drawn in one piece, a 1.8M x 300 table took
    9 s, most of it the page faults of a 4.3 GB ``float64`` temporary:
    most of a word2vec build.)"""
    out = np.empty(shape, dtype)
    flat = out.reshape(-1)
    workers = min(32, os.cpu_count() or 1)
    share, piece = -(-flat.size // workers), 1 << 20

    def draw(lo: int) -> None:
        bits = np.random.PCG64(seed)
        bits.advance(offset + lo)
        rng = np.random.Generator(bits)
        hi = min(lo + share, flat.size)
        for a in range(lo, hi, piece):
            b = min(a + piece, hi)
            flat[a:b] = rng.uniform(-scale, scale, b - a)

    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        list(pool.map(draw, range(0, flat.size, share)))
    return out


@functools.lru_cache(maxsize=256)
def _default_order(shape: Tuple[int, ...], dtype,
                   sharding) -> Optional[Tuple[int, ...]]:
    """``major_to_minor`` of the layout ``sharding``'s devices give a shard
    of ``shape`` by default; ``None`` where the backend has no layouts."""
    device = min(sharding.device_set, key=lambda d: d.id)
    try:
        return Layout.from_pjrt_layout(device.client.get_default_layout(
            jnp.dtype(dtype), sharding.shard_shape(shape),
            device)).major_to_minor
    except jax.errors.JaxRuntimeError as e:
        if str(e).startswith("UNIMPLEMENTED"):
            return None
        raise


@functools.lru_cache(maxsize=64)
def _zeros_program(shape: Tuple[int, ...], dtype, sharding):
    """A program that fills an array of ``shape`` with zeros on
    ``sharding``'s devices: one per table shape, not one per table."""
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


def _row_probe(data, ids):
    """What every row program does to a table: gather rows, scatter-add
    them back. Compiled, never run (:func:`row_program_layout`)."""
    return data.at[ids].add(jnp.take(data, ids, axis=0))


@functools.lru_cache(maxsize=64)
def row_program_layout(shape: Tuple[int, ...], dtype,
                       sharding) -> Optional[Layout]:
    """The layout the row programs of a table of ``shape`` run in on
    ``sharding``'s devices, where that is not the device's default for
    the shape; ``None`` where the default serves.

    Two questions to the device, and no width rule here. First its
    default layout for a shard of the shape: where rows are the major
    dimension (row-major: every backend but the TPU, and there a
    ``float32`` width that fills its 128 lanes) nothing more is asked.
    Otherwise a row gather and scatter-add on the table
    (:func:`_row_probe`) is compiled with the table's layout left to the
    compiler (``Layout.AUTO``), and what it picks is the answer. On a v5e
    that is row-major for widths such as 64, 100 and 300: the default
    there has rows as the MINOR dimension, and a program that takes such
    a table whole transposes it on the way in and again on the way out.
    For a million rows of width 2, 10 or 32 it is the default again:
    row-major tiles would pad those 4 to 64 times over."""
    default = _default_order(shape, dtype, sharding)
    if (default is None or default == tuple(range(len(shape)))
            # a program that returns a chosen layout has to be compiled in
            # this process (the probe below is the first of them)
            or not _platform.compile_result_layouts_in_process()):
        return None
    auto = Format(Layout.AUTO, sharding)
    compiled = jax.jit(
        _row_probe, donate_argnums=0, in_shardings=(auto, None),
        out_shardings=auto).lower(
            jax.ShapeDtypeStruct(shape, dtype, sharding=sharding),
            jax.ShapeDtypeStruct((8,), jnp.int32)).compile()
    picked = compiled.input_formats[0][0].layout.major_to_minor
    return None if picked == default else Layout(major_to_minor=picked)


class Table:
    """Base sharded table. Subclasses fix dimensionality and op surface."""

    def __init__(self, shape: Tuple[int, ...], dtype=jnp.float32,
                 updater: Union[str, updaters_lib.Updater, None] = None,
                 name: str = "table",
                 init: Optional[ArrayLike] = None,
                 seed: Optional[int] = None,
                 init_scale: float = 0.0):
        zoo = Zoo.get()
        self._zoo = zoo
        self.name = name
        self.dtype = jnp.dtype(dtype)
        self.shape = tuple(int(s) for s in shape)
        mesh = zoo.mesh()
        self._mesh = mesh
        self._axis = zoo.shard_axis()
        self._num_shards = mesh.shape[self._axis]

        # Row-padding so the leading dim splits evenly across shards; at least
        # one spare row is kept as scatter scratch space for masked row ops.
        self._padded_rows = _ceil_to(self.shape[0] + 1, self._num_shards)
        self._padded_shape = (self._padded_rows,) + self.shape[1:]

        self._data_spec = P(self._axis, *([None] * (len(self.shape) - 1)))
        self._sharding = NamedSharding(mesh, self._data_spec)
        self._replicated = NamedSharding(mesh, P())
        # what the table's own row programs take and return it in; the
        # layout is None where that is the device's default
        self._format = Format(
            row_program_layout(self._padded_shape, self.dtype,
                               self._sharding), self._sharding)

        if updater is None:
            updater = config.get_flag("updater_type")
        if isinstance(updater, str):
            updater = updaters_lib.get_updater(
                updater, num_workers=zoo.num_workers(), dtype=self.dtype)
        self.updater = updater

        with _trace.span(
                "table.init", table=name, rows=self._padded_rows,
                width=int(np.prod(self.shape[1:])),
                bytes=int(np.prod(self._padded_shape)) * self.dtype.itemsize,
                row_major=int(self._format.layout is not None)) as built:
            self._data, host_bytes = self._build_data(init, seed, init_scale)
            built.set(shards=self._num_shards, host_bytes=host_bytes)
            self._ustate = jax.tree.map(
                self._place_state,
                updater.init_state(self._padded_shape, self.dtype))
        self.table_id = zoo.register_table(self)

        self._pending: Dict[int, Any] = {}
        self._next_msg_id = 0
        self._lock = threading.Lock()
        # Serializes op *dispatch* (not device execution): a donating add on
        # one thread must not delete the data buffer while another thread
        # (e.g. an AsyncBuffer prefetch pull) is snapshotting it.
        self._dispatch_lock = threading.RLock()
        self._jit_cache: Dict[Any, Any] = {}
        # hot-row training cache (serving/hotcache; row-table subclasses
        # create it behind the train_cache_rows flag — base ops only need
        # to INVALIDATE on coarse mutations)
        self._train_cache = None

    # ------------------------------------------------------------------ #
    # construction helpers
    # ------------------------------------------------------------------ #
    def _build_data(self, init, seed, init_scale) -> Tuple[jax.Array, int]:
        """The table's first data on its devices, in the default layout,
        and the most host memory the build held for it at once.

        One path for one chip and for many: a table of zeros is filled
        on the devices and costs the host nothing; one with values (an
        ``init`` array, or the seeded draw) is made a shard at a time,
        the rows of one shard on the host, copied to the device(s) that
        hold them, and dropped before the next shard's are made, so the
        host holds one shard and never the table (a 12M x 300 table is
        14.4 GB, its four shards 3.6 GB each). One copy is in flight at
        a time: two at once take seven times what they take in turn
        (PERF.md, PR 25)."""
        if init is not None:
            init = np.asarray(init, dtype=self.dtype)
            if init.shape != self.shape:
                raise ValueError(
                    f"init shape {init.shape} != table shape {self.shape}")
        elif seed is None or init_scale == 0.0:
            with _trace.span("table.init.zeros"):
                return jax.block_until_ready(_zeros_program(
                    self._padded_shape, self.dtype, self._sharding)()), 0
        # devices by the rows they hold: replicas (a mesh with more axes
        # than the table's) share one draw
        holders: Dict[Tuple[int, int], list] = {}
        for device, index in self._sharding.addressable_devices_indices_map(
                self._padded_shape).items():
            lo, hi, _ = index[0].indices(self._padded_rows)
            holders.setdefault((lo, hi), []).append(device)
        placed, host_bytes = {}, 0
        for k, ((lo, hi), devices) in enumerate(sorted(holders.items())):
            with _trace.span("table.init.shard", shard=k, rows=hi - lo) as sp:
                with _trace.span("table.init.host"):
                    rows = self._shard_rows(init, seed, init_scale, lo, hi)
                with _trace.span("table.init.put"):
                    for device in devices:
                        placed[device] = jax.block_until_ready(
                            jax.device_put(rows, device))
                sp.set(host_bytes=int(rows.nbytes))
                host_bytes = max(host_bytes, int(rows.nbytes))
                del rows
        return jax.make_array_from_single_device_arrays(
            self._padded_shape, self._sharding,
            [placed[d] for d in sorted(placed, key=lambda d: d.id)]
        ), host_bytes

    def _shard_rows(self, init: Optional[np.ndarray], seed, init_scale,
                    lo: int, hi: int) -> np.ndarray:
        """Rows ``lo`` to ``hi`` of the padded table on the host: the
        caller's ``init`` or, with none, Uniform(-scale, scale) from
        ``seed`` (the reference's word2vec input-embedding server init,
        ref src/table/matrix_table.cpp:372-384 and
        Applications/WordEmbedding/src/communicator.cpp:20), value for
        value the rows the whole table's draw gives; padding rows zero."""
        live = max(min(hi, self.shape[0]) - lo, 0)
        shape = (hi - lo,) + self.shape[1:]
        if init is not None:
            out = np.zeros(shape, self.dtype)
            out[:live] = init[lo:lo + live]
            return out
        out = _uniform(seed, init_scale, shape, self.dtype,
                       lo * int(np.prod(self.shape[1:])))
        out[live:] = 0
        return out

    def _leaf_format(self, x) -> Format:
        """How the table's own row programs hold the data, or a leaf of
        updater state: sharded and laid out like the data where shapes
        line up (leading axes, such as a per-worker history's, stay
        major), else replicated in the default layout."""
        nd, pd = np.ndim(x), len(self._padded_shape)
        if nd >= pd and tuple(np.shape(x)[nd - pd:]) == self._padded_shape:
            lead = nd - pd
            spec = P(*([None] * lead), self._axis, *([None] * (pd - 1)))
            layout = self._format.layout
            if layout is not None and lead:
                layout = Layout(major_to_minor=tuple(range(lead)) + tuple(
                    lead + a for a in layout.major_to_minor))
            return Format(layout, NamedSharding(self._mesh, spec))
        return Format(None, self._replicated)

    def _place_state(self, x: jax.Array) -> jax.Array:
        """Shard updater state like the data where shapes line up, else
        replicate; in the default layout, as the data is built."""
        return jax.device_put(x, self._leaf_format(x).sharding)

    def _mark_mutated(self) -> None:
        """Entry of every table mutation path: dirty-mark for the Zoo
        barrier fence."""
        self._zoo.mark_dirty(self.table_id)

    # ------------------------------------------------------------------ #
    # msg-id / Waiter bookkeeping (ref src/table.cpp:27-97)
    # ------------------------------------------------------------------ #
    def _track(self, arrays: Any, finalize=None) -> int:
        with self._lock:
            # opportunistic sweep of completed fire-and-forget adds: an
            # add whose msg id is never wait()ed (finalize is None and the
            # completion token is already ready) would otherwise pin its
            # device buffer in _pending forever. Swept ids behave exactly
            # like already-waited ones (wait returns None).
            done = [mid for mid, (arrs, fin) in self._pending.items()
                    if fin is None and all(
                        hasattr(a, "is_ready") and a.is_ready()
                        for a in jax.tree.leaves(arrs)
                        if isinstance(a, jax.Array))]
            for mid in done:
                del self._pending[mid]
            msg_id = self._next_msg_id
            self._next_msg_id += 1
            self._pending[msg_id] = (arrays, finalize)
            return msg_id

    def wait(self, msg_id: int) -> Any:
        """Block until the op behind ``msg_id`` is complete; return its result.

        For get-style ops the result is the materialized host array (the ref's
        Wait(GetAsync) leaves the data in the user buffer, src/table.cpp:27-97);
        for adds it is the completion token — or ``None`` when the add already
        completed (its token may have been swept by :meth:`_track`, which is
        indistinguishable from waiting on an already-waited id).
        """
        with self._lock:
            entry = self._pending.pop(msg_id, None)
        if entry is None:
            return None
        arrays, finalize = entry
        arrays = jax.tree.map(
            lambda a: a.block_until_ready() if isinstance(a, jax.Array) else a,
            arrays)
        return finalize(arrays) if finalize is not None else arrays

    # ------------------------------------------------------------------ #
    # functional plane (in-graph use)
    # ------------------------------------------------------------------ #
    @property
    def state(self) -> Dict[str, Any]:
        """Current table pytree {data, ustate}, in the device's default
        layout; safe to close over in jit."""
        self._lay_out(own=False)
        return {"data": self._data, "ustate": self._ustate}

    def program_state(self) -> Dict[str, Any]:
        """:attr:`state` as the table's own row programs take it: laid out
        in :attr:`state_format`. Such a program donates the state, names
        :attr:`state_format` as the ``out_shardings`` of the state it
        returns, and its caller holds ``_dispatch_lock`` from this call
        until the table has adopted that. A chain of such calls copies no
        table; handing the table to anyone else (:attr:`state`,
        :meth:`raw`) or back costs one copy of it, counted
        (``table.relayout``)."""
        self._lay_out(own=True)
        return {"data": self._data, "ustate": self._ustate}

    def _lay_out(self, own: bool) -> None:
        """Hold the data and the row-shaped updater state in the layout of
        the table's own row programs (``own``) or in the device's
        default. Where the two are one layout (``format.layout`` is None:
        every table on a CPU) there is nothing to do; otherwise what lies
        in the other layout is copied over, each copy a ``table.relayout``
        span with its direction and bytes."""
        if self._format.layout is None:
            return
        with self._dispatch_lock:
            self._data = self._relaid(self._data, own)
            self._ustate = jax.tree.map(
                lambda x: self._relaid(x, own), self._ustate)

    def _relaid(self, x: jax.Array, own: bool) -> jax.Array:
        fmt = self._leaf_format(x)
        if fmt.layout is None:          # a leaf with one layout
            return x
        if own:
            want = fmt.layout.major_to_minor
        else:
            fmt = Format(None, fmt.sharding)
            want = _default_order(x.shape, x.dtype, fmt.sharding)
        if x.format.layout.major_to_minor == want:
            return x
        with _trace.span("table.relayout", table=self.name,
                         to="rows" if own else "default", relayouts=1,
                         bytes=int(x.nbytes)):
            # the source goes as a donated buffer goes, whoever else still
            # holds it: what follows is a donating program (or the reader
            # who asked), and two live copies of a table are what a 16 GB
            # chip has no room for. Waited for, so that it can go now.
            laid = jax.block_until_ready(jax.device_put(x, fmt))
            x.delete()
            return laid

    def functional_add(self, state: Dict[str, Any], delta: jax.Array,
                       opt: Optional[AddOption] = None) -> Dict[str, Any]:
        """Pure add for use inside a user's jitted step. ``delta`` must be
        padded-shape (use :meth:`pad_delta`)."""
        opt = opt or AddOption()
        data, ustate = self.updater.apply(state["data"], state["ustate"],
                                          delta, opt)
        return {"data": data, "ustate": ustate}

    def adopt(self, state: Dict[str, Any]) -> None:
        """Commit an externally-advanced table state (end of in-graph loop)."""
        self._mark_mutated()
        self._data = state["data"]
        self._ustate = state["ustate"]
        if self._train_cache is not None:
            # wholesale rewrite: all rows stale. AFTER the rebind — a
            # clear logged before the mutation is visible lets a racing
            # get re-fill pre-adopt rows under a current fill token,
            # and nothing would ever invalidate them again
            self._train_cache.clear()

    def pad_delta(self, delta: jax.Array) -> jax.Array:
        pad = self._padded_rows - self.shape[0]
        if pad == 0:
            return delta
        widths = [(0, pad)] + [(0, 0)] * (len(self.shape) - 1)
        return jnp.pad(delta, widths)

    @property
    def sharding(self) -> NamedSharding:
        return self._sharding

    @property
    def format(self) -> Format:
        """Sharding and layout the table's own row programs hold the data
        in; the layout is ``None`` where it is the device's default
        (:func:`row_program_layout`)."""
        return self._format

    @property
    def state_format(self) -> Dict[str, Any]:
        """:meth:`program_state`'s pytree with a ``Format`` at every
        leaf."""
        return {"data": self._format,
                "ustate": jax.tree.map(self._leaf_format, self._ustate)}

    @property
    def padded_shape(self) -> Tuple[int, ...]:
        return self._padded_shape

    @property
    def num_shards(self) -> int:
        """How many contiguous row shards the table is split into (the
        size of the mesh's table axis; the reference's server count)."""
        return self._num_shards

    @property
    def rows_per_shard(self) -> int:
        """Padded rows a shard holds: row ``r`` lives in shard
        ``r // rows_per_shard`` (ref matrix_table.cpp:266-313)."""
        return self._padded_rows // self._num_shards

    def raw(self) -> jax.Array:
        """The live padded, sharded data array (graph-plane read), in the
        device's default layout."""
        self._lay_out(own=False)
        return self._data

    # ------------------------------------------------------------------ #
    # whole-table ops (host plane)
    # ------------------------------------------------------------------ #
    def _full_update_fn(self):
        key = "full"
        fn = self._jit_cache.get(key)
        if fn is None:
            updater = self.updater

            def _update(data, ustate, delta, opt):
                data, ustate = updater.apply(data, ustate, delta, opt)
                # Tiny completion token: later adds donate (and delete) the
                # data buffer, so pending waits block on this instead.
                token = jnp.ravel(data)[0]
                return data, ustate, token

            fn = jax.jit(_update, donate_argnums=(0, 1))
            self._jit_cache[key] = fn
        return fn

    def _snapshot_fn(self):
        key = "snapshot"
        fn = self._jit_cache.get(key)
        if fn is None:
            # Non-donating identity: the output is a fresh buffer that stays
            # valid when subsequent adds donate the live data array.
            fn = jax.jit(jnp.copy)
            self._jit_cache[key] = fn
        return fn

    @staticmethod
    def _to_host(data: jax.Array) -> np.ndarray:
        """Device -> host, including multi-controller arrays whose shards
        live on other processes (ICI/DCN allgather instead of local DMA)."""
        if getattr(data, "is_fully_addressable", True):
            return np.asarray(data)
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(data, tiled=True))

    def _host_delta(self, delta: ArrayLike) -> jax.Array:
        """Pad + shard-place a host/device delta of logical table shape.

        Multi-controller: host-plane Add is a *collective* — every process
        calls it with its own worker's delta, and the effective delta is the
        SUM over processes (reference semantics: N workers each pushed
        theirs). A plain global device_put would instead mosaic each
        process's rows into its local shards, silently dropping the other
        workers' contributions.
        """
        if isinstance(delta, jax.Array) and delta.shape == self._padded_shape:
            return delta
        if isinstance(delta, jax.Array):
            return jax.device_put(self.pad_delta(delta), self._sharding)
        arr = np.asarray(delta, dtype=self.dtype).reshape(self.shape)
        if self._zoo.size() > 1:
            # device AllReduce, not allgather+numpy-sum: per-host transfer
            # stays O(size) as the world grows (VERDICT r3 item 7)
            from multiverso_tpu.parallel.collectives import process_sum
            arr = process_sum(arr)
        padded = np.zeros(self._padded_shape, dtype=self.dtype)
        padded[: self.shape[0]] = arr
        return jax.device_put(padded, self._sharding)

    def add_async(self, delta: ArrayLike,
                  opt: Optional[AddOption] = None) -> int:
        """ref WorkerTable::AddAsync — dispatch the update, return a msg id."""
        opt = opt or AddOption()
        self._mark_mutated()
        try:
            with monitor(f"table[{self.name}].add"), self._dispatch_lock:
                delta_dev = self._host_delta(delta)
                self._data, self._ustate, token = self._full_update_fn()(
                    self._data, self._ustate, delta_dev, opt)
            return self._track(token)
        finally:
            if self._train_cache is not None:
                # whole-table delta: conservative wholesale drop, AFTER
                # the delta is applied — a clear logged before the
                # mutation is visible lets a get racing into the window
                # re-fill pre-add rows under a current fill token,
                # permanently stale
                self._train_cache.clear()

    def add(self, delta: ArrayLike, opt: Optional[AddOption] = None) -> None:
        """ref WorkerTable::Add — blocking add (Wait(AddAsync(...)))."""
        self.wait(self.add_async(delta, opt))

    def get_async(self) -> int:
        """ref WorkerTable::GetAsync — start device->host transfer, return
        id."""
        with monitor(f"table[{self.name}].get"), self._dispatch_lock:
            snap = self._snapshot_fn()(self._data)
            try:
                snap.copy_to_host_async()
            except AttributeError:
                pass
            return self._track(
                snap, lambda s: self._to_host(s)[: self.shape[0]])

    def get(self, out: Optional[np.ndarray] = None) -> np.ndarray:
        """ref WorkerTable::Get — blocking pull of the whole logical table.

        Reads the live array directly instead of dispatching a snapshot
        copy — safe because the transfer completes under the dispatch
        lock, before any later donating add can delete the buffer (saves
        one dispatch round-trip per get; get_async keeps the snapshot
        since its read is deferred)."""
        with monitor(f"table[{self.name}].get"), self._dispatch_lock:
            host = self._to_host(self._data)[: self.shape[0]]
        if out is not None:
            np.copyto(out.reshape(self.shape), host)
            return out
        return host

    def read(self, msg_id: int, out: Optional[np.ndarray] = None) -> np.ndarray:
        """Materialize the result of a previous :meth:`get_async`."""
        with self._lock:
            entry = self._pending.get(msg_id)
        if entry is not None and entry[1] is None:
            raise TypeError(
                f"msg_id {msg_id} is an add, not a get; use wait()")
        host = self.wait(msg_id)
        if host is None:
            raise KeyError(f"msg_id {msg_id} unknown or already consumed")
        if out is not None:
            np.copyto(out.reshape(self.shape), host)
            return out
        return host

    # ------------------------------------------------------------------ #
    # checkpoint (ref ServerTable Store/Load, table_interface.h:61-75)
    # ------------------------------------------------------------------ #
    def store(self, stream) -> None:
        """Write raw table + updater state (ref array_table.cpp:143-151).
        Multi-controller: fetching sharded state is a collective, so every
        process must call this together (checkpoint.save does)."""
        np.save(stream, self._to_host(self._data), allow_pickle=False)
        flat, _ = jax.tree.flatten(self._ustate)
        np.save(stream, np.asarray(len(flat)), allow_pickle=False)
        for leaf in flat:
            np.save(stream, self._to_host(leaf), allow_pickle=False)

    def load(self, stream) -> None:
        self._mark_mutated()
        data = np.load(stream)
        if data.shape != self._padded_shape:
            raise ValueError(
                f"checkpoint shape {data.shape} != table {self._padded_shape}")
        self._data = jax.device_put(data.astype(self.dtype), self._sharding)
        n = int(np.load(stream))
        flat, treedef = jax.tree.flatten(self._ustate)
        if n != len(flat):
            raise ValueError("checkpoint updater state mismatch")
        leaves = [np.load(stream) for _ in range(n)]
        self._ustate = jax.tree.unflatten(
            treedef, [self._place_state(l) for l in leaves])
        if self._train_cache is not None:
            self._train_cache.clear()   # after the load is visible (the
            #  adopt()/add_async() clear-after-mutate ordering rule)
