"""Zoo: system orchestrator — topology, lifecycle, table registry.

TPU-native re-design of the reference Zoo/Controller bootstrap
(ref: include/multiverso/zoo.h:19, src/zoo.cpp:41-177, src/controller.cpp).
The reference spins up an actor system per MPI/ZMQ process and runs a rank-0
Controller that assigns worker/server ids and implements barriers. On TPU all
of that is subsumed by the JAX runtime:

* node membership / rank assignment  -> ``jax.process_index()/process_count()``
  (multi-controller runtime discovers the pod; no Control_Register handshake)
* worker/server roles                -> every process is a worker, every
  *device* holds a server shard (the reference's ``ps_role=default`` collapse).
  ``num_workers`` = processes, ``num_servers`` = devices in the mesh.
* Controller barrier round-trip      -> a global device sync over ICI
* Communicator/net actors            -> XLA collectives inside jitted table ops

The Zoo owns the global ``jax.sharding.Mesh`` that tables shard over, and the
table registry (table_id -> table) used by checkpointing and the C ABI.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from multiverso_tpu.utils import config, log
from multiverso_tpu.utils.dashboard import Dashboard
from multiverso_tpu.utils.platform import enable_compile_cache


class Zoo:
    """Singleton orchestrator (ref zoo.h Zoo). Use module helpers or Zoo.get()."""

    _instance: Optional["Zoo"] = None
    _lock = threading.Lock()

    def __init__(self) -> None:
        self._started = False
        self._mesh: Optional[jax.sharding.Mesh] = None
        self._tables: Dict[int, Any] = {}
        self._next_table_id = 0
        self._barrier_count = 0
        self._dirty: set = set()   # table_ids with ops since last barrier

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #
    @classmethod
    def get(cls) -> "Zoo":
        with cls._lock:
            if cls._instance is None:
                cls._instance = Zoo()
            return cls._instance

    def start(self, argv: Optional[List[str]] = None,
              mesh: Optional[jax.sharding.Mesh] = None) -> None:
        """ref Zoo::Start (src/zoo.cpp:41): parse flags, init net, start actors.

        Here: parse flags, configure logging, adopt/build the device mesh.
        Idempotent; re-entrant start is a no-op (matching MV_Init usage).
        """
        if self._started:
            return
        config.parse_cmd_flags(argv)
        log.configure_from_flags()
        enable_compile_cache()   # before the mesh: bound at first compile
        self._mesh = mesh if mesh is not None else self._default_mesh()
        # telemetry plane: adopt the trace_ids flag and start the
        # flag-gated metrics exporter (both no-ops unless configured; a
        # PSService starting later upgrades the exporter's payload with
        # its shard registry)
        from multiverso_tpu.telemetry import devstats as _devstats
        from multiverso_tpu.telemetry import exporter as _exporter
        from multiverso_tpu.telemetry import flightrec as _flightrec
        from multiverso_tpu.telemetry import trace as _trace
        _trace.configure(self.rank())
        # device plane: adopt the devstats flag and key compiles with
        # no explicit scope to THIS mesh's shape (the default label a
        # recompile is attributed to when nothing narrower is active)
        _devstats.configure(self.rank())
        _devstats.set_default_mesh(self._mesh)
        _exporter.ensure_started(self.rank())
        # flight-recorder plane: pin the rank, give the structured log
        # sink the same rank, and dump the black box if a fault signal
        # lands (a later handler — e.g. bench.py's SIGTERM salvage —
        # replaces this one and dumps on its own)
        _flightrec.configure(self.rank())
        log.set_rank(self.rank())
        _flightrec.install_signal_handlers()
        self._started = True
        log.info(
            "multiverso_tpu started: process %d/%d, %d devices in mesh %s, "
            "platform=%s",
            self.rank(), self.size(), self._mesh.size,
            dict(zip(self._mesh.axis_names, self._mesh.devices.shape)),
            jax.devices()[0].platform,
        )
        self.barrier()

    def _default_mesh(self) -> jax.sharding.Mesh:
        axis = config.get_flag("mesh_axis")
        devices = np.asarray(jax.devices())
        return jax.sharding.Mesh(devices, (axis,))

    def stop(self, finalize: bool = True) -> None:
        """ref Zoo::Stop (src/zoo.cpp:103): drain, display dashboard, stop
        (including the async-PS service, ref StopPS stopping the actors)."""
        if not self._started:
            return
        self.barrier()
        if config.get_flag("dashboard"):
            # natively-served async ops never cross the Python monitor
            # (that's the point of them), so surface the C++ counters in
            # the shutdown report alongside the monitored paths — BEFORE
            # the final exporter snapshot, so the last metrics record
            # carries them too
            for table in list(self._tables.values()):
                shard = getattr(table, "_shard", None)
                if shard is None or getattr(shard, "_native_ref",
                                            None) is None:
                    continue
                adds, applies = shard._native_stats()
                if adds:
                    Dashboard.note(
                        f"ps[{table.name}].native_served",
                        f"adds = {adds}, applies = {applies}")
        # final telemetry flush while the monitors still hold this run's
        # numbers (the exporter's stop() writes a last snapshot; buffered
        # trace spans drain to metrics_dir)
        from multiverso_tpu.telemetry import aggregator as _aggregator
        from multiverso_tpu.telemetry import exporter as _exporter
        from multiverso_tpu.telemetry import flightrec as _flightrec
        from multiverso_tpu.telemetry import trace as _trace
        # cluster aggregator first (final poll needs the PS service,
        # which reset_default_context below tears down), then the
        # per-rank exporter; the failover checkpointer writes one final
        # committed save while the shards are still intact
        _aggregator.stop_global()
        from multiverso_tpu.ps import failover as _failover
        _failover.stop_global(final=True)
        _exporter.stop_global()
        # final black-box dump (no-op unless a dump directory resolves):
        # a run that hung AFTER stop began still leaves its last tape.
        # routine=True: if a FAULT dump (watchdog trip, peer death,
        # fatal) was already written this process, keep it — the healthy
        # shutdown tape must never overwrite the fault evidence
        _flightrec.dump_global("Zoo.stop", routine=True)
        d = config.get_flag("metrics_dir")
        if d:
            try:
                _trace.dump_to(d)
            except OSError as e:
                log.error("trace dump at shutdown failed: %s", e)
        if config.get_flag("dashboard"):
            Dashboard.display(log.info)
            # a second init/stop cycle must not reprint this run's
            # counters as its own
            Dashboard.reset()
        try:
            from multiverso_tpu.ps import service as _ps_service
            _ps_service.reset_default_context()
        except ImportError:  # pragma: no cover
            pass
        self._tables.clear()
        self._next_table_id = 0
        self._mesh = None
        self._started = False

    @property
    def started(self) -> bool:
        return self._started

    # ------------------------------------------------------------------ #
    # topology (ref zoo.h rank/size/worker_rank/server_rank accessors)
    # ------------------------------------------------------------------ #
    def rank(self) -> int:
        return jax.process_index()

    def size(self) -> int:
        return jax.process_count()

    def mesh(self) -> jax.sharding.Mesh:
        if self._mesh is None:
            raise RuntimeError("multiverso_tpu not initialized; call mv.init()")
        return self._mesh

    def shard_axis(self) -> str:
        """Mesh axis tables shard over (the last axis of the mesh)."""
        return self.mesh().axis_names[-1]

    def num_workers(self) -> int:
        n = config.get_flag("num_workers")
        return n if n > 0 else self.size()

    def num_servers(self) -> int:
        n = config.get_flag("num_servers")
        return n if n > 0 else self.mesh().size

    def worker_id(self) -> int:
        return self.rank()

    def server_id(self) -> int:
        return self.rank()

    def worker_id_to_rank(self, worker_id: int) -> int:
        return worker_id

    def server_id_to_rank(self, server_id: int) -> int:
        return server_id

    # ------------------------------------------------------------------ #
    # barrier (ref Zoo::Barrier, src/zoo.cpp:165-177 — controller round trip)
    # ------------------------------------------------------------------ #
    def mark_dirty(self, table_id: int) -> None:
        """Table ops call this; the next single-process barrier fences only
        tables with activity since the last one (a battery that barriers
        per block with many tables would otherwise pay O(tables) blocking
        syncs per barrier)."""
        self._dirty.add(table_id)

    def barrier(self) -> None:
        self._barrier_count += 1
        # black-box edges: a rank that dies INSIDE the barrier leaves
        # "enter without exit" as the last record of its tape
        from multiverso_tpu.telemetry import flightrec as _flightrec
        _flightrec.record(_flightrec.EV_BARRIER_ENTER,
                          msg_id=self._barrier_count, note="zoo.barrier")
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(
                f"multiverso_tpu_barrier_{self._barrier_count}")
        else:
            # Single controller: block on the live arrays of every table
            # TOUCHED since the last barrier, giving the reference's "all
            # prior Adds are visible" fence without fencing idle tables.
            dirty, self._dirty = self._dirty, set()
            for table_id in dirty:
                table = self._tables.get(table_id)
                raw = getattr(table, "raw", None)
                if callable(raw):
                    value = raw()
                    jax.tree.map(
                        lambda a: a.block_until_ready()
                        if isinstance(a, jax.Array) else a, value)
        _flightrec.record(_flightrec.EV_BARRIER_EXIT,
                          msg_id=self._barrier_count, note="zoo.barrier")

    # ------------------------------------------------------------------ #
    # table registry (ref zoo.h RegisterTable / table_factory ownership)
    # ------------------------------------------------------------------ #
    def register_table(self, table: Any) -> int:
        with self._lock:
            table_id = self._next_table_id
            self._next_table_id += 1
            self._tables[table_id] = table
            return table_id

    def table(self, table_id: int) -> Any:
        return self._tables[table_id]

    def tables(self) -> Dict[int, Any]:
        return dict(self._tables)
