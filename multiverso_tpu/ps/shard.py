"""RowShard: the owner-side storage of an async table's row range.

TPU-native equivalent of the reference ServerTable shard
(ref: src/server.cpp:36-58 ProcessAdd/ProcessGet dispatching into the
table's shard; src/table/matrix_table.cpp:98-141 server-side row storage +
Updater::Update over the received rows). The shard lives as a device array
on the owner process's local accelerator; Adds run the table's updater as a
jitted, donated program (gather touched rows -> updater -> scatter), so the
optimizer math happens on the TPU even though requests arrive over TCP.

Shape discipline: row batches are bucketed to the next power of two and
padded with a scratch row (same trick as the sync MatrixTable,
tables/matrix_table.py) so there is one compiled program per bucket size.

Thread-safety: requests arrive on per-connection service threads; a lock
serializes state transitions (JAX arrays are immutable, so readers always
see a consistent snapshot; the lock orders the donated updates).

Read path (off-lock snapshot serving): gets do NOT hold the lock across
the row gather, the device->host transfer, or the reply wire-encode.
A reader briefly takes the lock to PIN the current data epoch (a
refcounted handle on the buffer object, :meth:`RowShard._pin_data`) and
then computes outside it. The apply path donates its input buffer only
when no reader pins the current epoch; while pinned it updates into a
FRESH buffer instead (non-donating jit / numpy copy-on-write), so the
pinned snapshot stays valid and the last releasing reader simply drops
the retired buffer to the GC. Applies therefore never wait on a reader,
and a multi-hundred-ms gather/encode no longer serializes the shard —
the read/write symmetry the reference's one-Server-actor-thread design
never had. Shards registered with the native C++ server keep the locked
path: C++ holds the raw buffer pointer, so the buffer must never be
swapped (the punt path already serializes on the native shard mutex).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.ps import service as svc
from multiverso_tpu.ps import wire
from multiverso_tpu.table import _ceil_to
from multiverso_tpu.telemetry import flightrec as _flight
from multiverso_tpu.telemetry import hotkeys as _hotkeys
from multiverso_tpu.telemetry import memstats as _memstats
from multiverso_tpu.telemetry import tenants as _tenants
from multiverso_tpu.tables.matrix_table import _bucket_size
from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.updaters import AddOption, Updater
from multiverso_tpu.utils import config as _config
from multiverso_tpu.utils.dashboard import Dashboard

# updater classification (see updaters.STATELESS_LINEAR /
# OPT_INSENSITIVE): linear stateless updaters apply with in-place numpy
# on host-backed shards (~20 us vs ~60 us jit dispatch for a 128-row
# batch); opt-insensitive ones coalesce across senders.
from multiverso_tpu.updaters import (OPT_INSENSITIVE as _OPT_INSENSITIVE,
                                     ROW_LOCAL_STATE as _ROW_LOCAL_STATE,
                                     STATELESS_LINEAR as _LINEAR_SIGN)


class _SeqChannel:
    """Per-client applied-sequence tracker for exactly-once replay
    (docs/FAILOVER.md): ``floor`` means every sequence at or below it
    has applied; ``above`` is the sparse set of applied sequences past
    a gap. The gap shape exists because a frame re-sent across a
    connection change can arrive after a later frame sent on the fresh
    conn — a plain high-water mark would then dedupe the LATE frame as
    already-applied and lose it. Memory is bounded by the client's
    in-flight pipeline depth (the set drains into the floor as gaps
    close)."""

    __slots__ = ("floor", "above", "failed")

    # frames that applied with per-sub-op failures, kept so a DUP ack
    # can echo the same "failed" indices (a replayed batch whose first
    # ack was lost must not resolve its failed sub-ops as successes);
    # bounded — failures are rare and only the recent replay window
    # can ever be re-asked
    _MAX_FAILED = 64

    def __init__(self, floor: int = -1, above=(), failed=None):
        self.floor = int(floor)
        self.above = set(int(s) for s in above)
        self.failed: Dict[int, Dict] = {
            int(k): v for k, v in (failed or {}).items()}

    def seen(self, seq: int) -> bool:
        return seq <= self.floor or seq in self.above

    def note_failed(self, seq: int, rmeta: Dict) -> None:
        self.failed[int(seq)] = {"failed": list(rmeta.get("failed", ())),
                                 "error": rmeta.get("error", "")}
        while len(self.failed) > self._MAX_FAILED:
            del self.failed[min(self.failed)]

    @staticmethod
    def _max_above() -> int:
        """Gap-set bound: a client never has more frames outstanding
        than its retention cap (flag ``ps_replay_max_frames``), so a
        set larger than that means some sequence was permanently
        abandoned (the client dropped its frame after exhausting
        ``ps_replay_timeout`` — logged loudly there) and the gap will
        never fill. Floored at the flag's default so a tiny/zero knob
        can never make live out-of-order pipelines jump the floor."""
        try:
            return max(int(_config.get_flag("ps_replay_max_frames")),
                       4096)
        except Exception:   # noqa: BLE001 — flag registry unavailable
            return 4096     # (standalone channel use in tests/tools)

    def commit(self, seq: int) -> None:
        if seq == self.floor + 1:
            self.floor += 1
            while self.floor + 1 in self.above:
                self.floor += 1
                self.above.discard(self.floor)
        elif seq > self.floor:
            self.above.add(seq)
            if len(self.above) > self._max_above():
                # jump past the abandoned gap instead of growing the
                # set (and every checkpoint's replay block) forever
                self.floor = min(self.above) - 1
                while self.floor + 1 in self.above:
                    self.floor += 1
                    self.above.discard(self.floor)

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"floor": self.floor,
                               "above": sorted(self.above)}
        if self.failed:
            out["failed"] = {str(k): v for k, v in self.failed.items()}
        return out

    @classmethod
    def from_dict(cls, d: Dict) -> "_SeqChannel":
        return cls(d.get("floor", -1), d.get("above", ()),
                   d.get("failed"))


class _DataPin:
    """A pinned read epoch of a shard's data buffer: holds the buffer
    object alive (plain Python reference) and marks it so the apply path
    neither donates nor mutates it in place while any reader computes on
    it. Release via :meth:`RowShard._release_data` — dropping the last
    pin of a retired epoch frees the buffer through ordinary GC."""

    __slots__ = ("data", "version")

    def __init__(self, data, version: int):
        self.data, self.version = data, version


class _PendingAdd:
    """One queued row-add awaiting the shard's applier (coalescing path).
    ``trace`` is the request's client-minted trace ID (wire meta "tr"),
    echoed into the apply-wave spans so a client enqueue span and the
    shard apply span stitch by ID; None = untraced (the default)."""

    __slots__ = ("local", "vals", "opt", "event", "error", "trace")

    def __init__(self, local: np.ndarray, vals: np.ndarray, opt: AddOption,
                 trace: Optional[int] = None):
        self.local, self.vals, self.opt = local, vals, opt
        self.event = threading.Event()
        self.error: Optional[Exception] = None
        self.trace = trace


class RowShard:
    """Rows ``[lo, hi)`` of a logical ``(num_row, num_col)`` table."""

    def __init__(self, lo: int, hi: int, num_col: int, dtype,
                 updater: Updater, name: str,
                 init: Optional[np.ndarray] = None,
                 seed: Optional[int] = None, init_scale: float = 0.0,
                 num_workers: int = 0):
        """``num_workers > 0`` enables per-worker dirty-bit tracking for the
        sparse stale-row protocol (ref src/table/matrix.cpp:432-572 — the
        reference's ASYNC server kept up_to_date_[worker][row] bits; a
        sparse Get returns only rows stale for the asking worker and an Add
        marks its rows stale for everyone). Bits live host-side on the
        owner: they are control metadata consulted per request, not tensor
        math."""
        self.lo, self.hi = int(lo), int(hi)
        self.n = self.hi - self.lo
        self.num_col = int(num_col)
        self.name = name
        self.dtype = jnp.dtype(dtype)
        self.updater = updater
        # mesh-stacked group membership (ps/spmd.py, flag
        # ps_spmd_stack): when a plane adopts this shard, its storage
        # lives as one lane of the group's (S, R, C) stacked device
        # array and the _data/_ustate properties below serve lazily
        # materialized per-epoch slab views; None = classic standalone
        # storage. Set/cleared by MeshStack.admit/evict under this
        # shard's lock.
        self._plane = None
        self._plane_slot: Optional[int] = None
        self._view_cache = None
        self._view_epoch = -1
        self._ustate_view_cache = None
        self._mem_state_bytes = 0
        # shard this process's rows over its LOCAL devices: on a real
        # multi-host TPU every host owns several chips, and its row range
        # should live (and its updater run) across all of them — the
        # process-level partition (ps/tables.py) composes with this
        # device-level one. Rows pad to a device multiple (>= +1 scratch).
        # Tiny shards stay single-device: GSPMD partitioning would cost
        # more (compile + per-op overhead) than it buys below ~1 MB
        # (ps_local_shard_min_mb).
        local = jax.local_devices()
        min_bytes = _config.get_flag("ps_local_shard_min_mb") * 1e6
        self._local_sharding = None
        if (len(local) > 1
                and self.n * self.num_col * self.dtype.itemsize
                >= min_bytes):
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            padded_rows = _ceil_to(self.n + 1, len(local))
            mesh = Mesh(np.asarray(local), ("rows",))
            self._local_sharding = NamedSharding(
                mesh, PartitionSpec("rows", None))
        else:
            padded_rows = self.n + 1
        self._padded = (padded_rows, self.num_col)
        host = np.zeros(self._padded, self.dtype)
        if init is not None:
            host[: self.n] = np.asarray(init, self.dtype)
        elif seed is not None and init_scale != 0.0:
            # random init of exactly this shard's rows, seeded by (seed, lo)
            # so the global init is deterministic for a given partition
            # (ref src/table/matrix_table.cpp:372-384 server-side init)
            rng = np.random.default_rng([seed, self.lo])
            host[: self.n] = rng.uniform(
                -init_scale, init_scale, (self.n, self.num_col)
            ).astype(self.dtype)
        # host-backed single-device shards (CPU backend: tests, loopback
        # serving, CPU parameter hosts) answer reads with numpy straight
        # off the zero-copy buffer view — a 128-row gather costs ~10 us
        # vs ~40 us XLA dispatch, and the view is safe even across
        # donation (the buffer protocol export pins the XLA buffer)
        self._host_serve = (self._local_sharding is None
                            and jax.default_backend() == "cpu")
        # ...and when the updater is a stateless signed accumulate, the
        # shard stores plain numpy and applies adds in place — no XLA in
        # the loop at all (the reference server was exactly this: a C++
        # array += over received rows, src/table/matrix_table.cpp:98-141)
        self._np_mode = (self._host_serve
                         and type(updater) in _LINEAR_SIGN)
        self._data = self._place_rows(host)
        self._ustate = updater.init_state(self._padded, self.dtype)
        if self._local_sharding is not None:
            self._ustate = jax.tree.map(self._place_state_local,
                                        self._ustate)
        # RLock: HashShard wraps handle() in the same lock to make its
        # key->slot translation atomic with the update it guards
        self._lock = threading.RLock()
        self._jit: Dict[Any, Any] = {}
        # request-coalescing apply queue (flag ps_coalesce): adds arriving
        # on concurrent connection threads enqueue here; whichever thread
        # finds the queue idle becomes the applier and drains it, merging
        # everything queued meanwhile into one batched update. Self-
        # clocking: at low load each add applies immediately (no added
        # latency), under contention batch size grows with the backlog.
        self._addq: List[_PendingAdd] = []
        self._addq_lock = threading.Lock()
        self._addq_draining = False
        # observability: adds received vs. jitted updates actually run —
        # the coalescing ratio the bench asserts on. Python-path counters;
        # the stat_adds/stat_applies properties add the native server's
        # counters when the shard is natively registered.
        self._stat_adds = 0
        self._stat_applies = 0
        # first-class server-side stats (MSG_STATS / exporter):
        # _version counts applied mutations (the owner-side analogue of
        # the client get-cache version in table.py); _wave_ops is the
        # merged-ops-per-apply distribution in power-of-two buckets
        # (batch waves AND queue-coalesce groups — the realized server-
        # side batching the mean hides). Both mutate under self._lock.
        self._version = 0
        self._wave_ops: Dict[int, int] = {}
        self._wave_max = 0
        # off-lock read epochs: _cur_pins counts readers pinning _pin_buf
        # (identity-checked against the live _data, so a buffer swap
        # implicitly retires the count — no per-site bookkeeping). The
        # counters feed stats(): cow_applies = applies that had to copy /
        # skip donation because a reader held the epoch; served gets and
        # streamed chunks measure the read plane.
        self._pin_buf: Optional[Any] = None
        self._cur_pins = 0
        self._stat_cow = 0
        self._stat_gets = 0
        self._stat_chunks = 0
        # replica snapshot pulls served (MSG_SNAPSHOT; serving plane) —
        # counted apart from gets: a full-table replica pull must not
        # read as row-get traffic in rates/skew, and its ids never feed
        # the hot-key sketch (a periodic full sweep would drown the
        # workload's zipf signal the sketch exists to surface)
        self._stat_snapshots = 0
        self._stat_snapshot_unchanged = 0
        # wire-traffic byte counters (stats()["get_bytes"/"add_bytes"]):
        # the cluster aggregator derives wire bytes/s from their deltas.
        # Benign-race increments, same tolerance as _stat_gets above.
        self._stat_get_bytes = 0
        self._stat_add_bytes = 0
        # heavy-hitter sketch over served GLOBAL row ids (telemetry/
        # hotkeys.py): always-on like the flight recorder, bounded
        # memory, O(1) per recorded op. Feeds stats()["hotkeys"] and the
        # aggregator's cluster top-K + cache-hit-if-cached curve — the
        # sizing input for a device-resident hot-row cache. Python-plane
        # only (natively-served ops bypass it, same rule as tracing).
        cap = _config.get_flag("hotkeys_capacity")
        self._hotkeys = (_hotkeys.SpaceSaving(cap) if cap > 0 else None)
        # tenant attribution (telemetry/tenants.py): per-tenant op/byte
        # counters at the same chokepoints as the byte counters above.
        # Default-tenant path is one attribute read + one dict increment
        # (benign-race, same tolerance as _stat_gets); named tenants —
        # the wire-stamped minority — pay the meter's lock and feed its
        # Space-Saving ranking. Python-plane only, same rule as the
        # hot-key sketch (stamped frames always punt).
        self._tenants = _tenants.TenantMeter()
        # apply latency histogram (the p50/p99 of one updater dispatch)
        self._mon_apply = Dashboard.get(f"ps[{name}].apply")
        # native shard PIN once the native server serves this shard's hot
        # ops (service._try_register_native); Python then only sees punted
        # messages for it, already holding the native shard mutex. The pin
        # addresses this exact shard object in C++ and outlives the server
        # (freed in __del__, along with pins retired by re-registration).
        self._native_ref: Optional[int] = None
        self._retired_pins: List[int] = []
        # dirty[worker, local_row]: starts all-True so a worker's first
        # sparse Get pulls everything (ref matrix.cpp up_to_date_ = false)
        self._dirty = (np.ones((num_workers, self.n), bool)
                       if num_workers > 0 else None)
        # exactly-once replay plane (docs/FAILOVER.md): per-client
        # applied-sequence channels. _replay_seq tracks which stamped
        # frames each client has APPLIED (a frame already in its
        # channel is a duplicate — replay racing a late ack, or a
        # survivor re-flushing to this restored incarnation — and is
        # acked without applying); _durable_floor is the channel floor
        # at the last CHECKPOINT (ShardCheckpointer.mark_durable),
        # echoed in every stamped reply as the client's retention-prune
        # signal. _stamp_lock makes (dup check, apply, commit) atomic
        # against checkpoint_state()'s snapshot: without it a frame
        # could apply before the snapshot but commit its mark after,
        # and the restored state would replay-apply it twice.
        self._replay_seq: Dict[str, _SeqChannel] = {}
        self._durable_floor: Dict[str, int] = {}
        self._stamp_lock = threading.Lock()
        self._stat_dup_frames = 0
        # memory ledger (telemetry/memstats.py): live pins by identity,
        # id(pin) -> (t0 mono, buffer bytes, id(buffer)). The registry
        # records bytes AT PIN TIME and never references the buffer —
        # a ledger entry keeping a retired epoch alive would be this
        # plane's own leak. One dict store/pop per get, under the lock
        # the pin already takes; the gauges themselves are pull-only.
        self._pin_reg: Dict[int, Tuple[float, int, int]] = {}
        # last successful gauge pull, served when the shard lock is
        # contended (see memory_stats): the LIVENESS sweep drives the
        # ledger, and a sweep that blocked on a wedged apply would
        # hang the watchdog on exactly the wedge it exists to report
        self._mem_cache: Dict[str, Any] = {
            "table_bytes": int(getattr(self._data, "nbytes", 0)),
            "ustate_bytes": 0, "dtype": str(self.dtype),
            "pins": 0, "pinned_epochs": 0, "retired_epochs": 0,
            "retired_bytes": 0, "oldest_pin_age_s": 0.0}
        _memstats.register(f"shard[{name}:{self.lo}-{self.hi}]", self)

    # ------------------------------------------------------------------ #
    # storage indirection (mesh-stacked groups, ps/spmd.py): classic
    # shards read/write `_data_raw`/`_ustate_raw` straight through these
    # properties; a grouped shard's storage lives as one lane of its
    # plane's stacked array, and reads materialize a lazily-sliced slab
    # view (cached per plane epoch — a slice is its own buffer, so
    # pinned views survive the stack's donated swaps untouched). Every
    # existing read/rebind site keeps its spelling.
    # ------------------------------------------------------------------ #
    @property
    def _data(self):
        p = getattr(self, "_plane", None)
        if p is not None:
            return p.view(self)
        return self._data_raw

    @_data.setter
    def _data(self, v):
        self._data_raw = v

    @property
    def _ustate(self):
        p = getattr(self, "_plane", None)
        if p is not None:
            return p.ustate_view(self)
        return self._ustate_raw

    @_ustate.setter
    def _ustate(self, v):
        self._ustate_raw = v

    def _plane_lock(self):
        """The plane's lock as a context when grouped (nests INSIDE the
        shard lock — the one global order), else a no-op. Read paths
        that must see (bytes, version) atomically vs grouped applies
        hold it across both reads."""
        import contextlib
        p = self._plane
        return p.lock if p is not None else contextlib.nullcontext()

    def _plane_evict(self) -> None:
        """Fall back to classic per-shard storage before an exotic
        mutation (set_rows / whole-table add / state restore) — the
        always-safe path; row add/get traffic never needs it."""
        p = self._plane
        if p is not None:
            p.evict(self)

    def _place_rows(self, host):
        """Place a row buffer honoring the size-gated local-device sharding
        (numpy-mode shards keep a writable host buffer instead)."""
        if self._np_mode:
            return np.ascontiguousarray(np.asarray(host, self.dtype))
        if self._local_sharding is not None:
            return jax.device_put(host, self._local_sharding)
        return jnp.asarray(host)

    def _place_state_local(self, x):
        """Shard updater-state leaves over the local device mesh where the
        shape lines up (per-worker adagrad g² etc.), else replicate.
        Row-axis detection reuses :meth:`_state_row_axis` — one shape rule."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self._local_sharding.mesh
        axis = self._state_row_axis(x)
        if axis >= 0:
            nd = np.ndim(x)
            spec = P(*([None] * axis), "rows", *([None] * (nd - axis - 1)))
            return jax.device_put(x, NamedSharding(mesh, spec))
        return jax.device_put(x, NamedSharding(mesh, P()))

    # ------------------------------------------------------------------ #
    def bind_native(self, pin: int) -> None:
        if self._native_ref is not None:
            # re-registration: the OLD pin must not be freed yet — the
            # previously installed locked_handler closure still holds it
            # and may be mid-request; retire it and free at shard death
            self._retired_pins.append(self._native_ref)
        self._native_ref = pin

    def __del__(self):
        try:
            pins = getattr(self, "_retired_pins", [])
            if getattr(self, "_native_ref", None) is not None:
                pins = pins + [self._native_ref]
                self._native_ref = None
            if pins:
                from multiverso_tpu.ps import native as ps_native
                for p in pins:
                    ps_native.shard_pin_free(p)
        except Exception:   # noqa: BLE001 — interpreter teardown
            pass

    def _native_stats(self) -> Tuple[int, int]:
        if self._native_ref is None:
            return 0, 0
        from multiverso_tpu.ps import native as ps_native
        return ps_native.shard_pin_stats(self._native_ref)

    @property
    def stat_adds(self) -> int:
        return self._stat_adds + self._native_stats()[0]

    @property
    def stat_applies(self) -> int:
        return self._stat_applies + self._native_stats()[1]

    def stats(self) -> Dict[str, Any]:
        """First-class server-side stats (MSG_STATS reply / exporter):
        JSON-safe scalars + the wave distribution. Cheap — reads
        counters and queue lengths, never touches the data buffer."""
        with self._addq_lock:
            queue_depth = len(self._addq)
            pending_bytes = sum(e.local.nbytes + e.vals.nbytes
                                for e in self._addq)
        # ONE native crossing: the stat_adds/stat_applies properties
        # would each call shard_pin_stats again, and three racing reads
        # could mix counter states within one snapshot
        n_adds, n_applies = self._native_stats()
        adds = self._stat_adds + n_adds
        applies = self._stat_applies + n_applies
        native_applies = n_applies
        with self._lock:
            wave_ops = {str(k): v
                        for k, v in sorted(self._wave_ops.items())}
            wave_max = self._wave_max
            # natively-served applies never touch Python, so the zero-
            # Python C++ counter folds into the mutation version (both
            # only grow — monotonicity holds); the wave distribution
            # stays a python-path view by design (same rule as the
            # dashboard's native_served note)
            version = self._version + native_applies
            # rows stale for AT LEAST one worker (any-axis, not the raw
            # flag sum — a (workers, rows) flag count would exceed the
            # shard's row count and mislead staleness sizing)
            dirty_rows = (int(self._dirty.any(axis=0).sum())
                          if self._dirty is not None else None)
        out = {
            "kind": "row",
            "lo": self.lo, "rows": self.n, "cols": self.num_col,
            "bytes": int(self._padded[0] * self.num_col
                         * self.dtype.itemsize),
            "adds": adds, "applies": applies,
            "version": version,
            "queue_depth": queue_depth,
            "pending_bytes": pending_bytes,
            "wave_ops": wave_ops,       # pow2-bucketed ops-per-apply
            "wave_max_ops": wave_max,
            "apply": self._mon_apply.snapshot().hist_dict(),
            # read plane: gets served off-lock, chunks streamed, applies
            # that copied/skipped donation because a reader pinned the
            # epoch, and readers pinning it right now
            "gets": self._stat_gets,
            "get_chunks": self._stat_chunks,
            "cow_applies": self._stat_cow,
            "read_pins": self._cur_pins,
            # cumulative ENCODED wire bytes served/received (python
            # plane); the aggregator's wire-bytes/s comes from deltas
            "get_bytes": self._stat_get_bytes,
            "add_bytes": self._stat_add_bytes,
            # replay plane (docs/FAILOVER.md): stamped frames dedup'd
            # as duplicates, and how many clients hold a sequence
            # channel here — non-zero dup_frames after a failover is
            # the exactly-once machinery WORKING, not an error
            "dup_frames": self._stat_dup_frames,
            "replay_clients": len(self._replay_seq),
            # serving plane: replica snapshot pulls answered (and how
            # many were since-version deduped to an 'unchanged' frame)
            "snapshots": self._stat_snapshots,
            "snapshots_unchanged": self._stat_snapshot_unchanged,
        }
        if dirty_rows is not None:
            out["dirty_rows"] = dirty_rows   # sparse-protocol staleness
        if self._hotkeys is not None:
            out["hotkeys"] = self._hotkeys.to_dict()
        # per-tenant op/byte counters (telemetry/tenants.py): omitted
        # until the meter counts — the aggregator sums these per rank,
        # unlike the process-global "tenants" MSG_STATS block
        tm = self._tenants.to_dict()
        if tm:
            out["tenants"] = tm
        # mesh-stacked group placement (ps/spmd.py): slot -> device plus
        # this shard's share of the plane's grouped applies — mvtop's
        # shard-placement panel renders skew from bad placement off it
        p = self._plane
        if p is not None:
            sp = p.stats_for(self)
            if sp is not None:
                out["spmd"] = sp
        return out

    def queue_depth(self) -> int:
        """Lock-free apply-queue depth for the health plane (len() is
        GIL-atomic; the verdict tolerates ±1). MSG_HEALTH must never
        take a shard lock — it answers precisely when the shard is
        wedged — so this is deliberately NOT the stats() path."""
        return len(self._addq)

    def memory_stats(self) -> Dict[str, Any]:
        """Byte-ledger gauges (telemetry/memstats.py, pull-only): the
        live data buffer, updater state, the pinned read epochs — how
        many DISTINCT buffers pins hold, how many of those are RETIRED
        (COW-swapped out, alive only through their pins: the exact
        hoard the ``_pin_buf`` anchor bug silently carried) and their
        deduped bytes, the oldest pin's age — and the apply queue's
        pending payload. Counters and attr reads only; never touches
        buffer contents.

        NON-BLOCKING on the shard lock: the watchdog's liveness sweep
        drives the verdict engine, and a pull that blocked behind a
        multi-second (or wedged) apply would hang the watchdog on
        exactly the condition it exists to report. A contended pull
        serves the last successful reading marked ``"stale": True`` —
        the ledger tolerates a one-sweep-old figure."""
        if self._lock.acquire(blocking=False):
            try:
                p = self._plane
                if p is not None:
                    # grouped (ps/spmd.py): report the slab SHARE of the
                    # pooled stack from cached static sizes — the pull
                    # must never materialize a view (that would pay a
                    # device slice per ledger sweep) nor block on the
                    # plane lock mid-apply. The stack itself has its own
                    # spmd[table] ledger component.
                    data_nb = int(self._padded[0] * self.num_col
                                  * self.dtype.itemsize)
                    vc = self._view_cache
                    live_id = id(vc) if vc is not None else -1
                    ustate_nb = int(self._mem_state_bytes)
                else:
                    data_nb = int(getattr(self._data_raw, "nbytes", 0))
                    live_id = id(self._data_raw)
                    ustate_nb = sum(
                        int(getattr(l, "nbytes", 0))
                        for l in jax.tree.leaves(self._ustate_raw))
                pins = list(self._pin_reg.values())
            finally:
                self._lock.release()
            now = time.monotonic()
            epochs: Dict[int, int] = {}
            for _t0, nb, buf_id in pins:
                epochs.setdefault(buf_id, nb)
            retired = {b: nb
                       for b, nb in epochs.items() if b != live_id}
            oldest = max((now - t0 for t0, _nb, _b in pins),
                         default=0.0)
            core = {
                "table_bytes": data_nb,
                "ustate_bytes": int(ustate_nb),
                "dtype": str(self.dtype),
                "pins": len(pins),
                "pinned_epochs": len(epochs),
                "retired_epochs": len(retired),
                "retired_bytes": int(sum(retired.values())),
                "oldest_pin_age_s": round(oldest, 3),
            }
            if self._plane is not None:
                # pooled storage: these bytes are the shard's SHARE of
                # the plane's stack (which carries its own spmd[table]
                # ledger component)
                core["spmd"] = True
            self._mem_cache = core
        else:
            core = dict(self._mem_cache)
            core["stale"] = True
        with self._addq_lock:   # short holds only — never spans a jit
            qd = len(self._addq)
            qb = sum(e.local.nbytes + e.vals.nbytes for e in self._addq)
        out = dict(core)
        out["queue_depth"] = qd
        out["queue_pending_bytes"] = int(qb)
        return out

    @property
    def scratch(self) -> int:
        return self.n

    def _note_rows(self, local: np.ndarray) -> None:
        """Feed the heavy-hitter sketch with this op's GLOBAL row ids
        (shard-local + ``lo``). Called on the get/add serve paths AFTER
        id validation; HashShard overrides — its inherited call sites
        carry slot ids, and the sketch wants the workload's keys."""
        if self._hotkeys is not None:
            self._hotkeys.observe(local, offset=self.lo)

    # ------------------------------------------------------------------ #
    # off-lock read epochs (snapshot serving)
    # ------------------------------------------------------------------ #
    def _pin_data_locked(self) -> _DataPin:
        """Pin the current data epoch (caller holds ``self._lock``): the
        returned handle references the live buffer, and the apply path
        will not donate/mutate that buffer in place while the pin count
        is non-zero. The count is tied to BUFFER IDENTITY (_pin_buf), so
        any site that rebinds ``self._data`` implicitly retires it —
        stale releases become no-ops and retired buffers free through
        the pins' own references."""
        if self._pin_buf is not self._data:
            self._pin_buf = self._data
            self._cur_pins = 0
        self._cur_pins += 1
        pin = _DataPin(self._data, self._version)
        self._pin_reg[id(pin)] = (time.monotonic(),
                                  int(getattr(self._data, "nbytes", 0)),
                                  id(self._data))
        return pin

    def _pin_data(self) -> _DataPin:
        with self._lock:
            return self._pin_data_locked()

    def _release_data(self, pin: _DataPin) -> None:
        with self._lock:
            self._pin_reg.pop(id(pin), None)
            if pin.data is self._pin_buf and self._cur_pins > 0:
                self._cur_pins -= 1
                if self._cur_pins == 0:
                    # drop the identity anchor too: after a copy-on-write
                    # swap it would otherwise keep the RETIRED buffer
                    # alive until the next pin — a full extra table of
                    # memory in an add-heavy, rarely-read workload
                    self._pin_buf = None
        pin.data = None   # last holder of a retired epoch frees it

    def _data_pinned(self) -> bool:
        """True when a reader pins the LIVE buffer (caller holds the
        lock): the apply must then swap to a fresh buffer instead of
        donating or mutating in place."""
        return self._pin_buf is self._data and self._cur_pins > 0

    def _writable_data(self):
        """The buffer an in-place numpy mutation may write (caller holds
        ``self._lock``): copy-on-write when a reader pins the current
        epoch. Natively-registered shards never swap — C++ holds the raw
        pointer — and never need to: every python-plane op on them runs
        under the native shard mutex, so a pin cannot coexist with an
        apply there."""
        if self._native_ref is None and self._data_pinned():
            self._data = self._data.copy()
            self._stat_cow += 1
        return self._data

    def _state_row_axis(self, leaf) -> int:
        """Axis of ``leaf`` matching the table row axis; -1 = row-free leaf
        (-1, not None: None is not a pytree leaf, so it would corrupt the
        row_axes tree structure)."""
        nd, pd = np.ndim(leaf), len(self._padded)
        if nd >= pd and tuple(np.shape(leaf)[nd - pd:]) == self._padded:
            return nd - pd
        return -1

    def _row_update_fn(self, bucket: int, donate: bool = True):
        """Jitted row update; ``donate=False`` compiles a variant that
        does NOT donate the data buffer (updater state still donates —
        no reader ever pins it) for applies racing a pinned read epoch:
        the pinned snapshot must survive the update."""
        key = ("row_update", bucket, donate)
        fn = self._jit.get(key)
        if fn is not None:
            return fn
        updater = self.updater

        def _update(data, ustate, ids, vals, opt):
            row_axes = jax.tree.map(self._state_row_axis, ustate)
            rows = jnp.take(data, ids, axis=0)

            def gather(leaf, axis):
                return jnp.take(leaf, ids, axis=axis) if axis >= 0 else leaf

            gstate = jax.tree.map(gather, ustate, row_axes)
            new_rows, new_gstate = updater.apply(rows, gstate, vals, opt)
            data = data.at[ids].set(new_rows)

            def scatter(leaf, new_leaf, axis):
                if axis < 0:
                    return new_leaf
                idx = (slice(None),) * axis + (ids,)
                return leaf.at[idx].set(new_leaf)

            ustate = jax.tree.map(scatter, ustate, new_gstate, row_axes)
            return data, ustate

        fn = jax.jit(_update, donate_argnums=(0, 1) if donate else (1,))
        self._jit[key] = fn
        return fn

    def _full_update_fn(self, donate: bool = True):
        key = ("full", donate)
        fn = self._jit.get(key)
        if fn is None:
            updater = self.updater

            def _update(data, ustate, delta, opt):
                return updater.apply(data, ustate, delta, opt)

            fn = self._jit[key] = jax.jit(
                _update, donate_argnums=(0, 1) if donate else (1,))
        return fn

    def _get_fn(self, bucket: int):
        key = ("get", bucket)
        fn = self._jit.get(key)
        if fn is None:
            fn = self._jit[key] = jax.jit(
                lambda data, ids: jnp.take(data, ids, axis=0))
        return fn

    def _pad_to_bucket(self, local: np.ndarray) -> np.ndarray:
        """Pad a local-id batch to its power-of-two bucket with the scratch
        row (the one shape-discipline rule, shared by every row path)."""
        b = _bucket_size(local.size, self.n + 1)
        if b > local.size:
            local = np.concatenate(
                [local, np.full(b - local.size, self.scratch, np.int64)])
        return local.astype(np.int32)

    def _localize_raw(self, ids: np.ndarray) -> np.ndarray:
        """Global ids -> validated local ids (unpadded)."""
        local = np.asarray(ids, np.int64) - self.lo
        if local.size == 0 or np.any((local < 0) | (local >= self.n)):
            raise IndexError(
                f"row ids outside shard [{self.lo}, {self.hi}) of "
                f"{self.name}")
        return local

    def _localize(self, ids: np.ndarray) -> Tuple[np.ndarray, int]:
        """Global ids -> bucket-padded local ids (+ true count)."""
        local = self._localize_raw(ids)
        return self._pad_to_bucket(local), local.size

    def _gather_rows(self, local: np.ndarray,
                     data: Optional[Any] = None) -> np.ndarray:
        """Gather shard rows for a reply from ``data`` (a pinned epoch
        buffer; defaults to the live buffer for callers that hold the
        lock). Host-backed shards read via numpy off the zero-copy view;
        device-backed shards run the bucketed jitted take. Always returns
        an OWNED host array (fancy indexing / np.asarray of a jit result
        copy), so the caller may release its pin before encoding."""
        if data is None:
            data = self._data
        if self._host_serve:
            return np.asarray(data)[local]
        padded = self._pad_to_bucket(local)
        return np.asarray(
            self._get_fn(padded.size)(data, padded))[: local.size]

    # ------------------------------------------------------------------ #
    # coalescing apply queue (ps_coalesce)
    # ------------------------------------------------------------------ #
    def _apply_add_group(self, entries: List[_PendingAdd],
                         opt: AddOption) -> int:
        """Apply one opt-group of queued adds as ONE jitted update (caller
        holds ``self._lock``). Cross-request duplicate rows sum their
        deltas (float64 accumulation, same rule as the client-side
        ``_dedupe_batch``) — semantically the deltas arrived in a single
        message, which is the associativity async mode already grants.
        Updaters with GLOBAL state (adam's step counter advances once per
        apply) never merge: K adds must count K steps. Returns the number
        of updates actually dispatched (the ``stat_applies`` unit, so the
        reported coalescing ratio stays honest for non-merging
        updaters)."""
        if len(entries) > 1 and type(self.updater) not in _ROW_LOCAL_STATE:
            # per-entry errors: entry k failing must not mark the k-1
            # already-committed entries lost (a blanket group error would
            # invite retries that double-apply; same contract as
            # _apply_batch_adds' per-wave failure reporting)
            applies = 0
            for e in entries:
                self._record_wave(1)
                try:
                    self._apply_rows(e.local, e.vals, e.opt)
                    applies += 1
                except Exception as err:  # noqa: BLE001 — per-entry
                    e.error = err
            return applies
        if len(entries) == 1:
            local, vals = entries[0].local, entries[0].vals
        else:
            cat_ids = np.concatenate([e.local for e in entries])
            local, inv = np.unique(cat_ids, return_inverse=True)
            acc = np.zeros((local.size, self.num_col), np.float64)
            np.add.at(acc, inv,
                      np.concatenate([e.vals for e in entries])
                      .astype(np.float64))
            vals = acc.astype(self.dtype)
        self._record_wave(len(entries))
        self._apply_rows(local, vals, opt)
        return 1

    def _record_wave(self, ops: int) -> None:
        """Merged-ops-per-apply distribution (under ``self._lock``):
        power-of-two buckets keep it a tiny exact dict — wave sizes are
        bounded by MAX_BATCH_OPS, so log-scale bucketing buys nothing."""
        b = 1 << max(ops - 1, 0).bit_length()
        self._wave_ops[b] = self._wave_ops.get(b, 0) + 1
        if ops > self._wave_max:
            self._wave_max = ops

    def _apply_rows(self, local: np.ndarray, vals: np.ndarray,
                    opt: AddOption) -> None:
        """One merged, deduped row-delta batch -> the updater (under
        ``self._lock``). Times itself into the ``ps[name].apply``
        histogram and bumps the shard mutation version."""
        p = self._plane
        if p is not None:
            # mesh-stacked group (ps/spmd.py): the update runs as one
            # lane of the plane's SPMD program — the plane owns the
            # version bump (under its lock, atomic with the stack swap),
            # the apply histogram sample, and the flight-recorder edge.
            # Wave/stat recording stays with this path's callers, who
            # hold self._lock exactly as they do classically.
            p.apply_rows(self, local, vals, opt)
            return
        t0 = time.perf_counter()
        if self._np_mode:
            data = self._writable_data()   # copy-on-write vs pinned reads
            sign = _LINEAR_SIGN[type(self.updater)]
            if sign > 0:
                data[local] += vals   # merged ids are unique
            else:
                data[local] -= vals
            if self._dirty is not None:
                self._dirty[:, local] = True
        else:
            ids = self._pad_to_bucket(local)
            if vals.shape[0] < ids.size:   # zero-pad to the bucket
                vals = np.concatenate(
                    [vals,
                     np.zeros((ids.size - vals.shape[0], self.num_col),
                              self.dtype)])
            # a pinned read epoch forbids donating the data buffer: the
            # non-donating variant writes a fresh buffer and the pinned
            # one retires to its readers (freed on their last release)
            donate = not self._data_pinned()
            if not donate:
                self._stat_cow += 1
            self._data, self._ustate = self._row_update_fn(
                ids.size, donate)(self._data, self._ustate, ids, vals, opt)
            if self._dirty is not None:
                self._dirty[:, local] = True   # stale for everyone
        self._version += 1
        self._mon_apply.observe_ms((time.perf_counter() - t0) * 1e3)
        # black box: one apply edge + the shard-liveness heartbeat (a
        # queue that stops draining shows up as a stale "apply" beat in
        # MSG_HEALTH even before any request ages past the watchdog)
        _flight.beat("apply")
        _flight.record(_flight.EV_APPLY, nbytes=vals.nbytes)

    # shared continuation pool for drain hand-off (class-level: shards are
    # many, the pool is one; drain passes never block on anything but the
    # shard lock, so two threads cannot deadlock across shards)
    _drain_pool: Optional[Any] = None
    _drain_pool_lock = threading.Lock()

    @classmethod
    def _handoff_pool(cls):
        with cls._drain_pool_lock:
            if cls._drain_pool is None:
                import concurrent.futures as cf
                cls._drain_pool = cf.ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="ps-drain")
            return cls._drain_pool

    def _drain_adds(self, rounds: int = 8) -> None:
        """Applier loop: drain everything queued, merging per opt-group,
        until the queue is observed empty (checked atomically with the
        drainer-slot release, so no entry is ever orphaned). Bounded at
        ``rounds`` passes: the drainer is usually a connection thread
        serving ONE rank's whole request stream, and under sustained
        cross-rank load the queue may never be observed empty — after the
        bound, the remaining backlog hands off to the shared drain pool so
        the captured thread can reply to its own rank again."""
        normal_exit = False
        try:
            while True:
                handoff = False
                with self._addq_lock:
                    if not self._addq:
                        self._addq_draining = False
                        normal_exit = True
                        return
                    if rounds <= 0:
                        handoff = True   # drainer slot stays claimed
                    else:
                        rounds -= 1
                        batch, self._addq = self._addq, []
                if handoff:
                    # outside the queue lock: a failed submit falls through
                    # to the finally, which needs that lock to fail the
                    # backlog rather than wedge it
                    self._handoff_pool().submit(self._drain_adds)
                    normal_exit = True
                    return
                # opt-insensitive updaters merge across senders (one
                # group); the rest group by the full AddOption so e.g.
                # per-worker AdaGrad g2 stays per-worker
                merge_all = type(self.updater) in _OPT_INSENSITIVE
                groups: Dict[Any, List[_PendingAdd]] = {}
                for e in batch:
                    groups.setdefault(
                        None if merge_all else e.opt, []).append(e)
                with self._lock:
                    applies = 0
                    for entries in groups.values():
                        try:
                            applies += self._apply_add_group(
                                entries, entries[0].opt)
                        except Exception as err:
                            for e in entries:
                                e.error = err
                    self._stat_adds += len(batch)
                    self._stat_applies += applies
                for e in batch:
                    e.event.set()
        finally:
            if not normal_exit:   # crashed out: fail queued entries rather
                with self._addq_lock:   # than wedge their waiters forever
                    self._addq_draining = False
                    orphans, self._addq = self._addq, []
                for e in orphans:
                    e.error = svc.PSError(
                        f"{self.name}: add applier died")
                    e.event.set()

    def _enqueue_add(self, local: np.ndarray, vals: np.ndarray,
                     opt: AddOption) -> None:
        """Queue a validated, shard-local add and block until applied (the
        reply must mean durably-applied, or a worker's add->get would not
        read its own write). MUST NOT be called holding ``self._lock``: a
        waiter holding it would deadlock the applier."""
        entry = _PendingAdd(local, vals, opt)
        with self._addq_lock:
            self._addq.append(entry)
            drainer = not self._addq_draining
            if drainer:
                self._addq_draining = True
        if drainer:
            self._drain_adds()
        entry.event.wait()
        if entry.error is not None:
            raise entry.error

    def _prep_add(self, meta: Dict, arrays: Sequence[np.ndarray]
                  ) -> Tuple[np.ndarray, np.ndarray, AddOption]:
        """Validate an ADD_ROWS request into (local ids, vals, opt). The
        value payload decodes ONCE here, straight from the frame blob
        into the apply (a bf16 blob casts back to the table's dtype)."""
        opt = AddOption(**meta.get("opt", {}))
        local = self._localize_raw(arrays[0])
        self._note_rows(local)   # one sketch record per add (plain+batch)
        if meta.get("wire", "none") not in wire.WIRE_MODES:
            raise ValueError(f"unknown wire {meta['wire']!r}")
        vals = np.asarray(arrays[1], self.dtype)[: local.size]
        # ENCODED payload bytes (the blobs as they crossed the wire —
        # a bf16 add must not count as 4 bytes/element), per REQUEST
        # (like _stat_adds counts requests): the coalescing queue
        # merges K overlapping adds into one deduped apply, and
        # counting at apply time would underreport by up to Kx
        nbytes = sum(int(getattr(a, "nbytes", 0)) for a in arrays[1:])
        self._stat_add_bytes += nbytes
        # tenant attribution rides the SAME per-request chokepoint (an
        # unstamped frame is the default tenant — one dict increment)
        self._tenants.note(meta.get(wire.TENANT_META_KEY),
                           add_bytes=nbytes)
        return local, vals, opt

    def _prep_add_entry(self, meta: Dict, arrays: Sequence[np.ndarray]
                        ) -> _PendingAdd:
        """One MSG_BATCH sub-op -> a validated pending entry (HashShard
        overrides: its entries carry keys, translated at apply time)."""
        local, vals, opt = self._prep_add(meta, arrays)
        return _PendingAdd(local, vals, opt,
                           trace=meta.get(wire.TRACE_META_KEY))

    def _apply_batch_adds(self, entries: List[_PendingAdd]
                          ) -> Tuple[List[int], List[str]]:
        """Apply one window's adds as conflict-free WAVES: consecutive
        entries whose row sets are disjoint (and whose opts agree, unless
        the updater is opt-insensitive) concatenate into ONE bucketed
        scatter; a conflicting entry closes the wave, so overlapping rows
        still apply in arrival order with per-op arithmetic. Disjoint
        grouping is what keeps a batched window BIT-IDENTICAL to the same
        ops arriving as N separate frames — the queue's f64 duplicate
        merge (:meth:`_apply_add_group`) is reserved for genuinely
        concurrent senders, where no order was ever promised. Global-
        state updaters (adam: one step-counter bump per apply) never
        wave-merge: every entry applies alone, K adds = K steps.

        Returns ``(failed_indices, error_strings)``: a wave that fails
        marks ONLY its entries failed and the later waves still apply —
        exactly window-off semantics, where each op is an independent
        request and op K failing does not stop op K+1. The caller
        reports failures PER SUB-OP so the client can never mistake an
        applied delta for a lost one (a blanket error would invite a
        retry that double-applies the deltas that DID land)."""
        failed: List[int] = []
        errors: List[str] = []
        if not entries:
            return failed, errors
        mergeable = type(self.updater) in _ROW_LOCAL_STATE
        merge_all = type(self.updater) in _OPT_INSENSITIVE
        with self._lock:
            wave: List[Tuple[int, _PendingAdd]] = []
            seen: set = set()

            def flush_wave():
                if not wave:
                    return
                traced = (_trace.enabled()
                          and any(e.trace is not None for _, e in wave))
                t0 = time.time() if traced else 0.0
                self._record_wave(len(wave))
                _flight.record(_flight.EV_WAVE,
                               nbytes=sum(e.vals.nbytes for _, e in wave),
                               note=f"ops={len(wave)}")
                try:
                    if len(wave) == 1:
                        e = wave[0][1]
                        self._apply_rows(e.local, e.vals, e.opt)
                    else:
                        self._apply_rows(
                            np.concatenate([e.local for _, e in wave]),
                            np.concatenate([e.vals for _, e in wave]),
                            wave[0][1].opt)
                    self._stat_applies += 1
                except Exception as err:   # noqa: BLE001 — reported per op
                    failed.extend(i for i, _ in wave)
                    errors.append(f"{type(err).__name__}: {err}")
                if traced:
                    # ONE span per wave, correlated to every sub-op it
                    # applied: "trace" carries the first ID (timeline
                    # stitching), "traces" the full set
                    tids = [e.trace for _, e in wave
                            if e.trace is not None]
                    _trace.add_span(
                        "shard.wave_apply", t0, time.time(),
                        trace=tids[0],
                        args={"table": self.name, "ops": len(wave),
                              "traces": tids})
                wave.clear()
                seen.clear()

            for i, e in enumerate(entries):
                ids = e.local.tolist()
                if wave and (not mergeable
                             or any(x in seen for x in ids)
                             or (not merge_all
                                 and e.opt != wave[0][1].opt)):
                    flush_wave()
                wave.append((i, e))
                seen.update(ids)
            flush_wave()
            self._stat_adds += len(entries)
        return failed, errors

    def _handle_batch(self, meta: Dict, arrays: Sequence[np.ndarray]
                      ) -> Tuple[Dict, List[np.ndarray]]:
        """One MSG_BATCH frame: the client send window's sub-ops, applied
        in window order with one ack for the lot. Windows carry row adds
        only (gets fence the window client-side), so anything else in a
        batch is a framing error, not a dispatch case. Validation
        failures (unknown sub-op type, bad ids) raise BEFORE anything
        applies — a whole-frame error then means nothing landed; apply
        failures after that point come back per sub-op in the reply meta
        ("failed" indices), never as a blanket error."""
        subs = wire.unpack_batch(arrays)
        entries = []
        for mt, m, arrs in subs:
            if mt != svc.MSG_ADD_ROWS:
                raise svc.PSError(
                    f"{self.name}: batch frames carry MSG_ADD_ROWS only "
                    f"(got type {mt})")
            entries.append(self._prep_add_entry(m, arrs))
        failed, errors = self._apply_batch_adds(entries)
        rmeta: Dict = {"n": len(subs)}
        if failed:
            rmeta["failed"] = failed
            rmeta["error"] = "; ".join(errors[:3])
        return rmeta, []

    def _add_rows(self, local: np.ndarray, vals: np.ndarray,
                  opt: AddOption) -> None:
        if self._native_ref is not None:
            # natively-served shard: this is a PUNTED add (compressed wire
            # payload), already running under the native shard mutex via
            # the service's locked handler. Apply directly — the queue's
            # drain handoff runs on a pool thread that would NOT hold the
            # native mutex, racing C++ applies on the same buffer.
            with self._lock:
                self._apply_add_group([_PendingAdd(local, vals, opt)], opt)
                self._stat_adds += 1
                self._stat_applies += 1
        elif _config.get_flag("ps_coalesce"):
            self._enqueue_add(local, vals, opt)
        else:
            with self._lock:
                entry = _PendingAdd(local, vals, opt)
                self._apply_add_group([entry], opt)
                self._stat_adds += 1
                self._stat_applies += 1

    # ------------------------------------------------------------------ #
    # off-lock get serving (snapshot pin -> gather -> encode, all outside
    # the shard lock; applies keep flowing while a reply is computed)
    # ------------------------------------------------------------------ #
    def _serve_get_rows(self, meta: Dict, arrays: Sequence[np.ndarray]
                        ) -> Tuple[Dict, Any]:
        local = self._localize_raw(arrays[0])
        self._note_rows(local)
        tr = meta.get(wire.TRACE_META_KEY) if _trace.enabled() else None
        t0 = time.time() if tr is not None else 0.0
        pin = self._pin_data()
        if tr is not None:
            _trace.add_span("shard.get_pin", t0, time.time(), trace=tr,
                            args={"table": self.name,
                                  "rows": int(local.size)})
        return self._serve_rows_from_pin(pin, local, meta, tr)

    def _serve_rows_from_pin(self, pin: _DataPin, local: np.ndarray,
                             meta: Dict, tr: Optional[int]
                             ) -> Tuple[Dict, Any]:
        """The shared off-lock serve body once an epoch is pinned and
        ids resolved (RowShard localizes, HashShard translates key->slot
        atomically with its pin): flight edge, gather off-lock, release,
        counters, encode. ONE implementation, so new read-path
        instrumentation cannot drift between the planes."""
        _flight.record(_flight.EV_GET_SERVE,
                       nbytes=local.size * self.num_col
                       * self.dtype.itemsize)
        t1 = time.time() if tr is not None else 0.0
        try:
            rows = self._gather_rows(local, data=pin.data)
        finally:
            self._release_data(pin)
        self._stat_gets += 1
        if tr is not None:
            _trace.add_span("shard.get_gather", t1, time.time(), trace=tr,
                            args={"table": self.name})
        return self._encode_reply(rows, meta, tr)

    def _serve_get_full(self, meta: Dict) -> Tuple[Dict, Any]:
        tr = meta.get(wire.TRACE_META_KEY) if _trace.enabled() else None
        t0 = time.time() if tr is not None else 0.0
        pin = self._pin_data()
        _flight.record(_flight.EV_GET_SERVE,
                       nbytes=self.n * self.num_col * self.dtype.itemsize)
        try:
            # np_mode: the pin guarantees the buffer is not mutated in
            # place while held (copy-on-write applies swap instead), but
            # the reply outlives the pin — own the bytes. Device-backed:
            # np.asarray is already an owned host copy.
            full = (pin.data[: self.n].copy() if self._np_mode
                    else np.asarray(pin.data)[: self.n])
        finally:
            self._release_data(pin)
        self._stat_gets += 1
        if tr is not None:
            _trace.add_span("shard.get_gather", t0, time.time(), trace=tr,
                            args={"table": self.name, "full": True})
        return self._encode_reply(full, meta, tr)

    def export_snapshot(self, meta: Dict) -> Tuple[Dict, Any]:
        """Replica subscription snapshot (MSG_SNAPSHOT; the serving
        plane's pull primitive, docs/SERVING.md): the shard's committed
        rows plus the mutation version they correspond to, version and
        epoch pin taken atomically so the advertised version is exactly
        the copied bytes'. ``meta["since"]`` = the version the replica
        already holds — an unchanged shard answers a tiny meta-only
        frame instead of re-shipping its rows (the epoch cadence is
        then nearly free on an idle table). The copy runs OFF the shard
        lock under the pin (applies keep flowing, PR-5), and big
        snapshots chunk-stream when the request asked
        (``meta["chunk"]``). Natively-registered shards are safe here
        because MSG_SNAPSHOT always punts: the punt path's
        locked_handler holds the native shard mutex around this whole
        call, so C++ applies cannot mutate the buffer mid-copy (same
        argument as checkpoint_state, same lock order — native mutex
        first). Snapshot ids never feed the hot-key sketch: a periodic
        full sweep would drown the workload's zipf signal."""
        since = int(meta.get("since", -1))
        # the dedupe token is (generation, version), never version
        # alone: a respawned incarnation restores an older checkpoint
        # and re-applies different ops — its counter can coincide with
        # the replica's last-seen version while the CONTENT diverged.
        # The failover plane already stamps each incarnation
        # (ps_generation, PR 7); a replica holding a different
        # generation's version must be shipped rows, not "unchanged".
        gen = int(_config.get_flag("ps_generation"))
        since_gen = int(meta.get("since_gen", -1))
        tr = meta.get(wire.TRACE_META_KEY) if _trace.enabled() else None
        t0 = time.time() if tr is not None else 0.0
        with self._lock, self._plane_lock():
            # plane lock (grouped shards only): a cross-shard SPMD apply
            # bumps _version under the PLANE lock, so the pin and the
            # advertised version must be read under it to stay the same
            # epoch — serving new bytes under an old version only costs
            # a redundant re-pull, but old bytes under a NEW version
            # would let the replica dedupe real changes away
            version = self._version + self._native_stats()[1]
            if since >= 0 and version == since and since_gen == gen:
                self._stat_snapshots += 1
                self._stat_snapshot_unchanged += 1
                return {"version": version, "gen": gen, "lo": self.lo,
                        "rows": self.n, "cols": self.num_col,
                        "unchanged": True}, []
            pin = self._pin_data_locked()
        # serving traffic on the SAME tape as gets/adds (PR-8 coverage
        # gap): a replica refresh storm must be visible in a fault dump
        _flight.record(_flight.EV_SNAPSHOT_SERVE,
                       nbytes=self.n * self.num_col * self.dtype.itemsize)
        try:
            full = (pin.data[: self.n].copy() if self._np_mode
                    else np.asarray(pin.data)[: self.n])
        finally:
            self._release_data(pin)
        self._stat_snapshots += 1
        if tr is not None:
            _trace.add_span("snapshot.serve", t0, time.time(), trace=tr,
                            args={"table": self.name,
                                  "version": int(version)})
        rmeta = {"version": int(version), "gen": gen, "lo": self.lo,
                 "rows": self.n, "cols": self.num_col}
        emeta, payload = self._encode_reply(full, meta, tr)
        if isinstance(payload, wire.ChunkedReply):
            # the service sends ChunkedReply.meta as the closing OK —
            # the version must ride THAT frame
            payload.meta.update(rmeta)
            return payload.meta, payload
        emeta = dict(emeta)
        emeta.update(rmeta)
        return emeta, payload

    def _encode_reply(self, rows: np.ndarray, meta: Dict,
                      tr: Optional[int]) -> Tuple[Dict, Any]:
        """Wire-encode a gathered get reply — chunk-streamed when the
        client asked for it (meta["chunk"] rows per sub-frame) and the
        reply is big enough, one payload otherwise. Runs OFF the shard
        lock either way."""
        w = meta.get("wire", "none")
        chunk = int(meta.get("chunk", 0) or 0)
        if chunk > 0 and rows.shape[0] > chunk:
            return self._chunked_reply(rows, w, chunk, tr,
                                       meta.get(wire.TENANT_META_KEY))
        t0 = time.time() if tr is not None else 0.0
        payload = wire.encode_payload(rows, w)
        # ENCODED reply bytes (what actually crosses the wire — a bf16
        # reply is half the gathered f32 rows); feeds the aggregator's
        # wire-bytes/s honestly
        nbytes = sum(int(a.nbytes) for a in payload)
        self._stat_get_bytes += nbytes
        # every reply-encoded read (get, full-get, snapshot pull) is one
        # tenant op at the same chokepoint as the byte counter above
        self._tenants.note(meta.get(wire.TENANT_META_KEY),
                           get_bytes=nbytes)
        if tr is not None:
            _trace.add_span("shard.get_encode", t0, time.time(), trace=tr,
                            args={"table": self.name, "wire": w})
        return {}, payload

    def _chunked_reply(self, rows: np.ndarray, w: str, chunk: int,
                       tr: Optional[int],
                       tn: Optional[str] = None) -> Tuple[Dict, Any]:
        """Stream a big get as self-describing sub-frames: the service
        sends each (MSG_REPLY_CHUNK) as the generator yields, so the
        client's decode + out= scatter overlaps the network receive
        instead of buffering one mega-frame. Encode is lazy per chunk —
        chunk k+1 encodes while chunk k drains into the socket."""
        n = rows.shape[0]
        nchunks = -(-n // chunk)
        self._stat_chunks += nchunks
        # one tenant op per streamed request (bytes ride per chunk below
        # — counted as they encode, same lazy cadence as the byte stat)
        self._tenants.note(tn)
        shard = self

        def gen():
            for i in range(nchunks):
                a, b = i * chunk, min((i + 1) * chunk, n)
                cmeta: Dict = {"seq": i, "row0": a, "rows": b - a}
                if w != "none":
                    cmeta["wire"] = w
                t0 = time.time() if tr is not None else 0.0
                payload = wire.encode_payload(rows[a:b], w)
                cbytes = sum(int(x.nbytes) for x in payload)
                shard._stat_get_bytes += cbytes
                shard._tenants.note(tn, ops=0, get_bytes=cbytes)
                if tr is not None:
                    _trace.add_span("shard.get_encode", t0, time.time(),
                                    trace=tr,
                                    args={"table": shard.name, "wire": w,
                                          "seq": i})
                yield cmeta, payload

        final = {"chunks": nchunks, "rows": n}
        if w != "none":
            final["wire"] = w
        return final, wire.ChunkedReply(final, gen())

    # ------------------------------------------------------------------ #
    # request handler (runs on service connection threads)
    # ------------------------------------------------------------------ #
    def handle(self, msg_type: int, meta: Dict,
               arrays: Sequence[np.ndarray]
               ) -> Tuple[Dict, List[np.ndarray]]:
        if (msg_type in (svc.MSG_ADD_ROWS, svc.MSG_BATCH)
                and wire.REPLAY_CLIENT_KEY in meta):
            return self._handle_stamped(msg_type, meta, arrays)
        return self._handle(msg_type, meta, arrays)

    def _handle_stamped(self, msg_type: int, meta: Dict,
                        arrays: Sequence[np.ndarray]
                        ) -> Tuple[Dict, List[np.ndarray]]:
        """Dedupe gate for replay-stamped add frames (wire.REPLAY_*
        meta): a frame at or below the client's applied high-water mark
        acks as a duplicate without touching the data — the exactly-
        once half of elastic failover (a survivor re-flushing its
        retained window to a restored incarnation must never double-
        apply the prefix the checkpoint already holds, and a replay
        racing a late ack on a live shard must apply once). Stamped
        frames serialize on ``_stamp_lock`` so the check, the apply,
        and the mark commit are one atomic unit against concurrent
        same-client replays AND against checkpoint_state()'s snapshot.
        Replies echo the DURABLE mark (wire.REPLAY_DURABLE_KEY) — the
        client prunes retained frames at or below it."""
        cl = str(meta[wire.REPLAY_CLIENT_KEY])
        seq = int(meta.get(wire.REPLAY_SEQ_KEY, -1))
        with self._stamp_lock:
            chan = self._replay_seq.get(cl)
            if chan is not None and chan.seen(seq):
                self._stat_dup_frames += 1
                _flight.record(_flight.EV_FAILOVER_REPLAY,
                               note=f"dup seq={seq}")
                dup: Dict = {wire.REPLAY_DUP_KEY: True,
                             wire.REPLAY_DURABLE_KEY:
                                 self._durable_floor.get(cl, -1)}
                # the original apply had per-sub-op failures: the dup
                # ack must repeat them, or a replay whose first ack was
                # lost would resolve the failed sub-ops as successes
                dup.update(chan.failed.get(seq, ()))
                return dup, []
            rmeta, rarrays = self._handle(msg_type, meta, arrays)
            # commit AFTER a successful apply: an apply that raised
            # must stay replayable (at-least-once on failure; the
            # client sees the error either way). A batch with per-
            # sub-op failures still consumes the frame — those are
            # REPORTED per op in the reply (and memoized for dup
            # acks), never silently retried.
            if chan is None:
                chan = self._replay_seq[cl] = _SeqChannel()
            chan.commit(seq)
            if rmeta.get("failed"):
                chan.note_failed(seq, rmeta)
            rmeta = dict(rmeta)
            rmeta[wire.REPLAY_DURABLE_KEY] = self._durable_floor.get(
                cl, -1)
        return rmeta, rarrays

    def mark_durable(self, floors: Dict[str, int]) -> None:
        """Advance the durable (checkpointed) channel floors — called
        by the ShardCheckpointer after a COMMITTED save whose snapshot
        carried exactly these channels. From here on stamped replies
        tell clients that sequences at or below their floor survive a
        crash, so their retention buffers may prune them."""
        with self._stamp_lock:
            self._durable_floor = dict(floors)

    # ------------------------------------------------------------------ #
    # failover checkpoint surface (checkpoint.save_shard_state):
    # one atomic (meta, arrays) snapshot of everything a restarted
    # incarnation needs — data rows, updater state, replay marks,
    # mutation version
    # ------------------------------------------------------------------ #
    def _native_mutex(self):
        """Context manager holding the native shard mutex when this
        shard is natively registered (C++ serving threads mutate the
        buffer under THAT mutex, not ``_lock`` — a checkpoint snapshot
        racing them would tear rows); no-op otherwise."""
        import contextlib
        if self._native_ref is None:
            return contextlib.nullcontext()
        from multiverso_tpu.ps import native as ps_native

        @contextlib.contextmanager
        def held(pin=self._native_ref):
            ps_native.shard_pin_lock(pin)
            try:
                yield
            finally:
                ps_native.shard_pin_unlock(pin)

        return held()

    def checkpoint_state(self) -> Tuple[Dict, List[np.ndarray]]:
        """Consistent shard snapshot for the per-shard failover
        checkpoint. Taken under ``_stamp_lock`` + the shard lock (plus
        the native shard mutex when C++ serves this shard) so the
        replay marks and the data agree exactly (see _handle_stamped);
        every array is an OWNED host copy — a donating apply right
        after release must not invalidate the bytes being written.
        Lock ORDER matters: the native mutex comes FIRST, matching the
        punt path (locked_handler holds it around handle(), which then
        takes _stamp_lock) — the reverse order deadlocks a stamped
        punted frame against a concurrent checkpoint."""
        with self._native_mutex(), self._stamp_lock:
            # grouped shards additionally hold the PLANE lock across the
            # (version, bytes) read: a cross-shard SPMD apply bumps the
            # version under the plane lock WITHOUT this shard's lock, so
            # the shard lock alone no longer makes the pair atomic
            with self._lock, self._plane_lock():
                chans = {k: v.to_dict()
                         for k, v in self._replay_seq.items()}
                version = self._version
                if self._np_mode:
                    data = self._data[: self.n].copy()
                else:
                    data = np.asarray(self._data)[: self.n].copy()
                leaves = [np.asarray(l).copy()
                          for l in jax.tree.leaves(self._ustate)]
        meta = {"kind": "row", "lo": self.lo, "rows": self.n,
                "cols": self.num_col, "dtype": str(self.dtype),
                "version": int(version), "replay": chans,
                "n_leaves": len(leaves)}
        return meta, [data] + leaves

    def restore_checkpoint(self, meta: Dict,
                           arrays: Sequence[np.ndarray]) -> None:
        """Adopt a :meth:`checkpoint_state` snapshot — the restore half
        of shard failover. Dirty bits reset to all-True (sparse workers
        re-pull everything; safe, never wrong), and the restored replay
        marks become BOTH the applied and the durable high-water marks:
        the restored state is by definition exactly what the checkpoint
        made durable."""
        if meta.get("kind") != "row":
            raise svc.PSError(f"{self.name}: checkpoint kind "
                              f"{meta.get('kind')!r} is not a row shard")
        if (int(meta["lo"]) != self.lo or int(meta["rows"]) != self.n
                or int(meta["cols"]) != self.num_col):
            raise svc.PSError(
                f"{self.name}: checkpoint shard [{meta['lo']}, "
                f"{int(meta['lo']) + int(meta['rows'])})x{meta['cols']} "
                f"!= live [{self.lo}, {self.hi})x{self.num_col} — "
                "partition changed since the save")
        data, leaves = arrays[0], list(arrays[1:])
        # a grouped shard restores into CLASSIC storage (the restore
        # rebinds the buffer wholesale — exactly the mutation shape the
        # stacked plane evicts on)
        self._plane_evict()
        # native mutex FIRST (same order rule as checkpoint_state)
        with self._native_mutex(), self._stamp_lock:
            with self._lock:
                flat, treedef = jax.tree.flatten(self._ustate)
                if len(leaves) != len(flat):
                    raise svc.PSError(
                        f"{self.name}: checkpoint has {len(leaves)} "
                        f"updater-state leaves, shard expects "
                        f"{len(flat)}")
                for got, want in zip(leaves, flat):
                    if tuple(np.shape(got)) != tuple(np.shape(want)):
                        raise svc.PSError(
                            f"{self.name}: updater-state leaf shape "
                            f"{np.shape(got)} != {np.shape(want)}")
                if self._np_mode:
                    # in place: a natively-registered shard's C++ side
                    # holds the raw pointer, so the buffer never swaps
                    self._data[: self.n] = np.asarray(data, self.dtype)
                else:
                    host = np.zeros(self._padded, self.dtype)
                    host[: self.n] = np.asarray(data, self.dtype)
                    self._data = self._place_rows(host)
                new = [jnp.asarray(np.asarray(a, np.asarray(w).dtype))
                       for a, w in zip(leaves, flat)]
                self._ustate = jax.tree.unflatten(treedef, new)
                if self._local_sharding is not None:
                    self._ustate = jax.tree.map(self._place_state_local,
                                                self._ustate)
                self._adopt_replay_channels(meta)
                self._version = int(meta.get("version", 0))
                if self._dirty is not None:
                    self._dirty[:] = True
        _flight.record(_flight.EV_FAILOVER_RESTORE,
                       note=f"{self.name} v{meta.get('version', 0)}")

    def _adopt_replay_channels(self, meta: Dict) -> None:
        """Rebuild the replay channels from a checkpoint's ``replay``
        block (caller holds ``_stamp_lock``). The restored channels are
        BOTH the applied and the durable marks: the restored state is
        by definition exactly what the checkpoint made durable."""
        self._replay_seq = {str(k): _SeqChannel.from_dict(v)
                            for k, v in (meta.get("replay")
                                         or {}).items()}
        self._durable_floor = {k: c.floor
                               for k, c in self._replay_seq.items()}

    # exotic mutations evict a grouped shard back to classic storage
    # first (always-safe; the stacked fast path is for row add/get
    # traffic — docs/HOSTPLANE.md "Mesh-sharded data plane")
    _EVICT_TYPES = frozenset()   # filled below, after svc constants

    def _handle(self, msg_type: int, meta: Dict,
                arrays: Sequence[np.ndarray]
                ) -> Tuple[Dict, List[np.ndarray]]:
        if self._plane is not None and msg_type in self._EVICT_TYPES:
            self._plane_evict()
        if msg_type == svc.MSG_ADD_ROWS:
            local, vals, opt = self._prep_add(meta, arrays)
            tr = (meta.get(wire.TRACE_META_KEY)
                  if _trace.enabled() else None)
            t0 = time.time() if tr is not None else 0.0
            self._add_rows(local, vals, opt)
            if tr is not None:
                # the plain-frame analogue of the batch path's
                # shard.wave_apply span (a 1-op window ships as a plain
                # MSG_ADD_ROWS frame, not a MSG_BATCH)
                _trace.add_span("shard.apply", t0, time.time(), trace=tr,
                                args={"table": self.name, "traces": [tr]})
            return {}, []
        if msg_type == svc.MSG_BATCH:
            # a client send window: N logical adds in one frame, one ack
            return self._handle_batch(meta, arrays)
        if msg_type == svc.MSG_GET_ROWS and meta.get("sparse"):
            # stale-only reply for meta["worker_id"] (ref matrix.cpp
            # :475-483 GetOption.worker_id + :540-572 stale filter)
            wid = int(meta.get("worker_id", 0))
            local = self._localize_raw(arrays[0])
            self._note_rows(local)
            with self._lock:
                if self._dirty is None:
                    raise svc.PSError(
                        f"{self.name} was not created with num_workers; "
                        "sparse gets need dirty-bit tracking")
                # mask snapshot + clear ATOMIC with the epoch pin: an add
                # applying after this lock releases re-SETS bits on rows
                # we serve from the pinned (older) epoch, so the next get
                # re-pulls them — nothing lost. Pinning outside this hold
                # (or clearing after it) would open a set-then-lose
                # window: an apply between clear and gather could mutate
                # rows whose cleared bits claim THIS reply carries them.
                mask = self._dirty[wid, local].copy()
                self._dirty[wid, local] = False
                pin = self._pin_data_locked()
            _flight.record(_flight.EV_GET_SERVE,
                           nbytes=int(mask.sum()) * self.num_col
                           * self.dtype.itemsize)
            try:
                stale = local[mask]
                if stale.size:
                    rows = self._gather_rows(stale, data=pin.data)
                else:
                    rows = np.zeros((0, self.num_col), self.dtype)
            finally:
                self._release_data(pin)
            self._stat_gets += 1
            # sparse replies ship [mask, stale rows] uncompressed: that
            # pair IS the wire payload
            self._stat_get_bytes += mask.nbytes + rows.nbytes
            self._tenants.note(meta.get(wire.TENANT_META_KEY),
                               get_bytes=mask.nbytes + rows.nbytes)
            return {}, [mask, rows]
        if msg_type == svc.MSG_GET_ROWS:
            return self._serve_get_rows(meta, arrays)
        if msg_type == svc.MSG_SET_ROWS:
            ids, k = self._localize(arrays[0])
            vals = np.asarray(arrays[1], self.dtype)[:k]
            with self._lock:
                if self._np_mode:
                    self._writable_data()[ids[:k]] = vals
                else:
                    # eager .at[].set: non-donating — pinned epochs stay
                    # valid; the rebind retires their pin count
                    self._data = self._data.at[ids[:k]].set(
                        jnp.asarray(vals))
                if self._dirty is not None:
                    self._dirty[:, ids[:k]] = True
                self._version += 1
            return {}, []
        if msg_type == svc.MSG_ADD_FULL:
            opt = AddOption(**meta.get("opt", {}))
            delta = wire.decode_payload(arrays, meta.get("wire", "none"),
                                        (self.n, self.num_col), self.dtype)
            with self._lock:
                if self._np_mode:
                    data = self._writable_data()
                    sign = _LINEAR_SIGN[type(self.updater)]
                    if sign > 0:
                        data[: self.n] += delta
                    else:
                        data[: self.n] -= delta
                else:
                    padded = np.zeros(self._padded, self.dtype)
                    padded[: self.n] = delta
                    donate = not self._data_pinned()
                    if not donate:
                        self._stat_cow += 1
                    self._data, self._ustate = self._full_update_fn(
                        donate)(self._data, self._ustate,
                                jnp.asarray(padded), opt)
                if self._dirty is not None:
                    self._dirty[:] = True
                self._version += 1
            return {}, []
        if msg_type == svc.MSG_GET_FULL:
            return self._serve_get_full(meta)
        if msg_type == svc.MSG_SNAPSHOT:
            # replica subscription pull (serving plane)
            return self.export_snapshot(meta)
        if msg_type == svc.MSG_GET_STATE:
            # updater-state leaves, full precision (checkpoint plumbing:
            # the sync table persists ustate, table.py store(); async
            # shards must too or a restore silently resets accumulators)
            with self._lock:
                leaves = [np.asarray(l)
                          for l in jax.tree.leaves(self._ustate)]
            return {"n_leaves": len(leaves)}, leaves
        if msg_type == svc.MSG_SET_STATE:
            with self._lock:
                flat, treedef = jax.tree.flatten(self._ustate)
                if len(arrays) != len(flat):
                    raise svc.PSError(
                        f"{self.name}: checkpoint has {len(arrays)} updater-"
                        f"state leaves, shard expects {len(flat)} (was the "
                        "table created with a different updater?)")
                for got, want in zip(arrays, flat):
                    if tuple(got.shape) != tuple(np.shape(want)):
                        raise svc.PSError(
                            f"{self.name}: updater-state leaf shape "
                            f"{got.shape} != {np.shape(want)} (partition "
                            "changed since the checkpoint?)")
                leaves = [jnp.asarray(np.asarray(a, dtype=np.asarray(w).dtype))
                          for a, w in zip(arrays, flat)]
                self._ustate = jax.tree.unflatten(treedef, leaves)
                if self._local_sharding is not None:
                    self._ustate = jax.tree.map(self._place_state_local,
                                                self._ustate)
                self._version += 1
            return {}, []
        raise svc.PSError(f"unknown message type {msg_type}")


RowShard._EVICT_TYPES = frozenset(
    (svc.MSG_SET_ROWS, svc.MSG_ADD_FULL, svc.MSG_SET_STATE))


class HashShard(RowShard):
    """Sparse-key shard: arbitrary non-negative int64 keys map to device
    row slots allocated on first touch — the owner-side storage of the
    reference's app-defined sparse tables (ref Applications/
    LogisticRegression/src/util/sparse_table.h:1-306 hash-stored
    SparseServerTable; util/ftrl_sparse_table.h:1-90 FTRL z/n payloads,
    which arrive here as updater state on the row axis). The slot buffer
    doubles on demand; a plain Get of a never-added key returns the
    initial row (zeros — exactly FTRL's w for empty z/n) WITHOUT
    allocating, so dense sweeps over a huge key space cost no server
    memory. Adds, set_rows, and sparse (dirty-bit) gets allocate — those
    are keys the workload actually touches."""

    def __init__(self, num_col: int, dtype, updater: Updater, name: str,
                 capacity: int = 1024, num_workers: int = 0):
        super().__init__(0, capacity, num_col, dtype, updater, name,
                         num_workers=num_workers)
        self._slot_of: Dict[int, int] = {}
        self._nw = num_workers

    @property
    def keys(self) -> List[int]:
        with self._lock:
            return list(self._slot_of)

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        out["kind"] = "hash"
        with self._lock:
            out["keys"] = len(self._slot_of)
        return out

    def export_snapshot(self, meta: Dict) -> Tuple[Dict, Any]:
        """Hash shards have no stable global row space to replicate —
        slot order is allocation order and changes across restores, so
        a positional snapshot would silently serve the wrong keys.
        Replica support for keyed tables means shipping (keys, rows)
        pairs and a keyed replica read path; refuse loudly until that
        exists rather than serve garbage."""
        raise svc.PSError(
            f"{self.name}: read replicas support row-partitioned "
            "shards only (hash-sharded tables have no stable "
            "positional row space)")

    def _note_rows(self, local: np.ndarray) -> None:
        """No-op: the inherited serve paths reach here with SLOT ids.
        Hash-shard traffic records through :meth:`_note_keys` at the
        key-validation sites instead — the sketch must rank the
        workload's KEYS (DLRM user ids etc.), not slot allocation
        order."""

    def _note_keys(self, keys: np.ndarray) -> None:
        if self._hotkeys is not None:
            self._hotkeys.observe(keys)

    def _grow(self, need: int) -> None:
        old_padded = self._padded
        old_rows = old_padded[0]
        new_n = max(self.n, 1)
        while new_n < need:
            new_n *= 2
        if self._local_sharding is not None:
            # keep the device-multiple row padding the GSPMD layout needs
            ndev = self._local_sharding.mesh.devices.size
            rows = _ceil_to(new_n + 1, ndev)
        else:
            rows = new_n + 1

        def grow(leaf):
            arr = np.asarray(leaf)
            nd, pd = arr.ndim, len(old_padded)
            if nd >= pd and arr.shape[nd - pd:] == old_padded:
                axis = nd - pd
                widths = [(0, 0)] * nd
                widths[axis] = (0, rows - old_rows)
                return np.pad(arr, widths)
            return leaf

        data = grow(self._data)
        ustate = jax.tree.map(grow, self._ustate)
        if self._dirty is not None:
            self._dirty = np.pad(
                self._dirty, [(0, 0), (0, new_n - self.n)],
                constant_values=True)
        self.n = self.hi = new_n
        self._padded = (rows, self.num_col)
        # re-place AFTER _padded is updated: the grown buffers must keep
        # the size-gated local-device sharding, not silently collapse to
        # one device exactly when the table gets big enough to matter
        self._data = self._place_rows(data)
        if self._local_sharding is not None:
            self._ustate = jax.tree.map(
                lambda l: (self._place_state_local(l)
                           if isinstance(l, np.ndarray) else l), ustate)
        else:
            self._ustate = jax.tree.map(
                lambda l: jnp.asarray(l) if isinstance(l, np.ndarray) else l,
                ustate)

    def _apply_rows(self, keys: np.ndarray, vals: np.ndarray,
                    opt) -> None:
        """Queued add entries carry KEYS; translate to slots here, under
        the same lock hold as the update itself (allocation, grow, and
        apply stay atomic — a restore rebuilding the slot map can never
        interleave between translation and apply)."""
        super()._apply_rows(self._slots_for(keys), vals, opt)

    def _validate_keys(self, arr) -> np.ndarray:
        """Shared key validation (per-op adds, batched sub-ops, gets)."""
        keys = np.asarray(arr, np.int64)
        if keys.size == 0:
            raise IndexError(f"{self.name}: empty key batch")
        if np.any(keys < 0):
            raise IndexError(f"{self.name}: negative keys")
        return keys

    def _prep_add_entry(self, meta: Dict, arrays: Sequence[np.ndarray]
                        ) -> _PendingAdd:
        """Batched sub-ops carry KEYS (validated here); key -> slot
        translation stays at apply time inside :meth:`_apply_rows`,
        atomic with the update (same rule as the coalescing queue)."""
        keys = self._validate_keys(arrays[0])
        self._note_keys(keys)
        opt = AddOption(**meta.get("opt", {}))
        vals = np.asarray(arrays[1], self.dtype)[: keys.size]
        # encoded request blobs, per request — same rule as _prep_add
        self._stat_add_bytes += sum(int(getattr(a, "nbytes", 0))
                                    for a in arrays[1:])
        return _PendingAdd(keys, vals, opt,
                           trace=meta.get(wire.TRACE_META_KEY))

    def _slots_for(self, keys: np.ndarray) -> np.ndarray:
        """key -> slot, allocating unseen keys (under the caller's lock)."""
        out = np.empty(keys.size, np.int64)
        fresh = [i for i, k in enumerate(keys.tolist())
                 if k not in self._slot_of]
        if len(self._slot_of) + len(fresh) > self.n:
            self._grow(len(self._slot_of) + len(fresh))
        for i, k in enumerate(keys.tolist()):
            slot = self._slot_of.get(k)
            if slot is None:
                slot = self._slot_of[k] = len(self._slot_of)
            out[i] = slot
        return out

    def checkpoint_state(self) -> Tuple[Dict, List[np.ndarray]]:
        """Hash-shard failover snapshot: the (keys, rows, state-leaf)
        dump plus replay marks/version, same atomicity as RowShard's."""
        with self._stamp_lock:
            with self._lock:
                chans = {k: v.to_dict()
                         for k, v in self._replay_seq.items()}
                version = self._version
                _, arrs = self._dump()
        meta = {"kind": "hash", "cols": self.num_col,
                "dtype": str(self.dtype), "version": int(version),
                "replay": chans, "n_leaves": max(len(arrs) - 2, 0)}
        return meta, [np.ascontiguousarray(a) for a in arrs]

    def restore_checkpoint(self, meta: Dict,
                           arrays: Sequence[np.ndarray]) -> None:
        if meta.get("kind") != "hash":
            raise svc.PSError(f"{self.name}: checkpoint kind "
                              f"{meta.get('kind')!r} is not a hash shard")
        with self._stamp_lock:
            with self._lock:
                self._restore(arrays)
                self._adopt_replay_channels(meta)
                self._version = int(meta.get("version", 0))
        _flight.record(_flight.EV_FAILOVER_RESTORE,
                       note=f"{self.name} v{meta.get('version', 0)}")

    def _handle(self, msg_type: int, meta: Dict,
                arrays: Sequence[np.ndarray]
                ) -> Tuple[Dict, List[np.ndarray]]:
        if msg_type in (svc.MSG_ADD_FULL, svc.MSG_GET_FULL):
            raise svc.PSError(
                f"{self.name}: hash-sharded table has no dense whole-table "
                "plane; use row/key ops")
        if msg_type == svc.MSG_ADD_ROWS:
            # adds ride the coalescing queue OUTSIDE the lock (a waiter
            # holding the RLock would deadlock the applier); entries carry
            # KEYS and _apply_rows translates key->slot at APPLY time,
            # atomic with the update — slots resolved at enqueue time
            # could go stale if a checkpoint restore rebuilds the slot map
            # in between
            entry = self._prep_add_entry(meta, arrays)
            t0 = (time.time()
                  if _trace.enabled() and entry.trace is not None else 0.0)
            self._add_rows(entry.local, entry.vals, entry.opt)
            if t0:
                _trace.add_span("shard.apply", t0, time.time(),
                                trace=entry.trace,
                                args={"table": self.name,
                                      "traces": [entry.trace]})
            return {}, []
        if msg_type == svc.MSG_GET_ROWS and not meta.get("sparse"):
            # allocation-free read: unknown keys gather the scratch row,
            # which is invariantly zeros (padded adds apply zero deltas
            # to it). Key->slot translation is atomic with the epoch pin
            # (one lock hold); the gather + encode run off-lock like the
            # range-sharded shard's.
            keys = self._validate_keys(arrays[0])
            self._note_keys(keys)
            tr = (meta.get(wire.TRACE_META_KEY) if _trace.enabled()
                  else None)
            t0 = time.time() if tr is not None else 0.0
            with self._lock:
                slots = np.array(
                    [self._slot_of.get(k, self.n)
                     for k in keys.tolist()], np.int64)
                pin = self._pin_data_locked()
            if tr is not None:
                _trace.add_span("shard.get_pin", t0, time.time(),
                                trace=tr, args={"table": self.name,
                                                "rows": int(keys.size)})
            return self._serve_rows_from_pin(pin, slots, meta, tr)
        keys = None
        if msg_type in (svc.MSG_GET_ROWS, svc.MSG_SET_ROWS):
            # validate + sketch-record OFF the shard lock, like every
            # other serve path: up to ~0.5 ms of sampled sketch work on
            # a big sparse key batch must not stall applies behind
            # telemetry (the reads-block-applies coupling PR 5 removed)
            keys = self._validate_keys(arrays[0])
            if msg_type == svc.MSG_GET_ROWS:   # sparse keyed get
                self._note_keys(keys)
        with self._lock:   # reentrant: key->slot stays atomic w/ the update
            if msg_type == svc.MSG_GET_STATE and meta.get("dump"):
                return self._dump()
            if msg_type == svc.MSG_SET_STATE and meta.get("dump"):
                return self._restore(arrays)
            if keys is not None:
                slots = self._slots_for(keys)
                arrays = [slots] + list(arrays[1:])
            # _handle, not handle: the replay gate already ran at this
            # request's entry point (HashShard.handle inherits it) —
            # re-entering it here would dup-check the frame twice
            return super()._handle(msg_type, meta, arrays)

    # ------------------------------------------------------------------ #
    # checkpoint: (keys, rows, per-key updater state) — the reference left
    # KV/sparse Store/Load stubbed (kv_table.h:101-119); here it is real
    # ------------------------------------------------------------------ #
    def _dump(self) -> Tuple[Dict, List[np.ndarray]]:
        keys = np.array(sorted(self._slot_of), np.int64)
        slots = np.array([self._slot_of[k] for k in keys.tolist()], np.int64)
        if keys.size:
            rows = self._gather_rows(slots)
        else:
            rows = np.zeros((0, self.num_col), self.dtype)
        leaves = []
        for leaf in jax.tree.leaves(self._ustate):
            axis = self._state_row_axis(leaf)
            arr = np.asarray(leaf)
            if axis >= 0:
                leaves.append(np.take(arr, slots, axis=axis))
            else:
                leaves.append(arr)
        return ({}, [keys, rows] + leaves)

    def _restore(self, arrays: Sequence[np.ndarray]
                 ) -> Tuple[Dict, List[np.ndarray]]:
        keys, rows = np.asarray(arrays[0], np.int64), arrays[1]
        leaves_in = list(arrays[2:])
        self._slot_of = {}
        self.n = self.hi = 0
        self._padded = (1, self.num_col)
        self._data = self._place_rows(np.zeros(self._padded, self.dtype))
        self._ustate = self.updater.init_state(self._padded, self.dtype)
        if self._dirty is not None:
            self._dirty = np.ones((self._nw, 0), bool)
        if keys.size == 0:
            return {}, []
        slots = self._slots_for(keys)
        data = np.array(self._data)   # writable copy
        data[slots] = np.asarray(rows, self.dtype)
        self._data = self._place_rows(data)
        flat, treedef = jax.tree.flatten(self._ustate)
        if len(leaves_in) != len(flat):
            raise svc.PSError(
                f"{self.name}: checkpoint has {len(leaves_in)} updater-state "
                f"leaves, expected {len(flat)}")
        out = []
        for got, want in zip(leaves_in, flat):
            arr = np.asarray(want).copy()
            axis = self._state_row_axis(want)
            if axis >= 0:
                idx = (slice(None),) * axis + (slots,)
                arr[idx] = np.asarray(got, arr.dtype)
            else:
                arr = np.asarray(got, arr.dtype)
            out.append(self._place_state_local(arr)
                       if self._local_sharding is not None
                       else jnp.asarray(arr))
        self._ustate = jax.tree.unflatten(treedef, out)
        if self._dirty is not None:
            self._dirty = np.ones((self._nw, self.n), bool)
        return {}, []


class KVShard:
    """Hash-sharded key-value shard (ref include/multiverso/table/
    kv_table.h:44-54 — ``key % num_servers`` routing; the server-side map
    holds the global aggregate for its keys). Host-side dict: scalar KV
    traffic has no business on the MXU."""

    def __init__(self, name: str):
        self.name = name
        self._store: Dict[int, float] = {}
        self._lock = threading.Lock()

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"kind": "kv", "keys": len(self._store)}

    def handle(self, msg_type: int, meta: Dict,
               arrays: Sequence[np.ndarray]
               ) -> Tuple[Dict, List[np.ndarray]]:
        if msg_type == svc.MSG_KV_ADD:
            keys, vals = arrays
            with self._lock:
                for k, v in zip(keys.tolist(), vals.tolist()):
                    self._store[int(k)] = self._store.get(int(k), 0) + v
            return {}, []
        if msg_type == svc.MSG_KV_GET:
            with self._lock:
                if meta.get("all"):
                    items = sorted(self._store.items())
                    keys = np.array([k for k, _ in items], np.int64)
                    vals = np.array([v for _, v in items], np.float64)
                else:
                    keys = np.asarray(arrays[0], np.int64)
                    vals = np.array(
                        [self._store.get(int(k), 0) for k in keys],
                        np.float64)
            return {}, [keys, vals]
        raise svc.PSError(f"unknown message type {msg_type}")
