"""PSService: the per-process async parameter-server runtime.

TPU-native re-design of the reference's actor/net runtime for the *async*
plane (ref: src/communicator.cpp — recv thread bridging net and actors;
src/server.cpp:36-58 — Server actor applying Adds/answering Gets as they
arrive; src/zoo.cpp:117-146 — Controller rendezvous assigning ranks).

One PSService per process:

* a listener thread accepts peer connections; each connection gets a
  handler thread that reads requests, dispatches to the owning table
  shard, and writes the reply (the reference's THREAD_MULTIPLE mode,
  communicator.cpp:39-48 — one recv loop per peer instead of one global);
* a client side (:class:`_Peer`) keeps one persistent connection per
  remote rank with a receiver thread completing per-``msg_id`` futures —
  the reference's msg_id -> Waiter bookkeeping (src/table.cpp:27-97) as
  ``concurrent.futures``;
* rendezvous: ranks find each other through a shared directory (flag
  ``ps_rendezvous``) or the JAX distributed coordinator's KV store when
  ``jax.distributed`` is live — the Controller's Register handshake with
  the coordinator already provided by the TPU runtime.

Local shards short-circuit the socket (ref LocalForward,
src/communicator.cpp:69-75) but still run on the service executor so
``add_async`` keeps fire-and-forget semantics.

Failure semantics: requests to a dead/unreachable rank raise
:class:`PSPeerError` (after ``ps_connect_timeout``/``ps_timeout``); the
service itself keeps serving live peers — no collective, so nobody hangs.
"""

from __future__ import annotations

import concurrent.futures as cf
import itertools
import os
import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from multiverso_tpu.ps import wire
# module-level like the exporter (no cycle: the aggregator and the
# failover plane import this module only lazily, inside functions), so
# their stats_poll_interval_s / failover_* flags are registered before
# any Zoo.start/argv parse reads them
from multiverso_tpu.ps import failover as _failover
# fault-injection wire plane (ISSUE 14): module-level so faults_spec /
# faults_seed register before argv parse AND so the plane is compiled
# into every build — the acceptance criterion is zero measurable
# hot-path cost with it present but disarmed (hook sites guard on
# one `_faults.PLANE.armed` attribute read; faults.py never imports
# this module at module scope, so no cycle)
from multiverso_tpu.ps import faults as _faults
# mesh data plane (ISSUE 15): process-colocation registry + stacked
# shard groups. Module-level so the ps_fanout/ps_spmd_stack flags
# register before argv parse and the plane is compiled into every
# build, disarmed by default (the fault-plane discipline); spmd.py
# never imports this module at module scope, so no cycle.
from multiverso_tpu.ps import spmd as _spmd
# serving plane (read replicas + admission): module-level for the same
# reason — its serving_* flags must exist before an argv parse, and its
# replica registry feeds the MSG_STATS "serving" block below. The
# serving package never imports ps at module scope (no cycle).
from multiverso_tpu.serving import replica as _serving_replica
from multiverso_tpu.telemetry import aggregator as _aggregator
from multiverso_tpu.telemetry import devstats as _devstats
from multiverso_tpu.telemetry import exporter as _exporter
from multiverso_tpu.telemetry import flightrec as _flight
from multiverso_tpu.telemetry import memstats as _memstats
from multiverso_tpu.telemetry import slo as _slo
from multiverso_tpu.telemetry import tenants as _tenants
from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.telemetry import watchdog as _watchdog
from multiverso_tpu.utils import config, log, retry as _retry
from multiverso_tpu.utils.dashboard import monitor

# message types (request side; replies reuse the id space below 0x100)
MSG_REPLY_OK = 1
MSG_REPLY_ERR = 2
# one sub-frame of a chunk-streamed get reply (wire.ChunkedReply): N of
# these precede the stream's closing MSG_REPLY_OK, all under the
# request's msg_id (per-conn FIFO orders them). The client decodes and
# scatters each as it lands — reply decode overlaps the network receive
# instead of buffering one mega-frame. Sent only when the REQUEST asked
# (meta "chunk"), so a client never sees one it can't handle; the native
# C++ server punts chunk-requesting gets to Python (its meta whitelist
# rejects the "chunk" key), exactly like MSG_BATCH.
MSG_REPLY_CHUNK = 3
MSG_PING = 0x10
MSG_ADD_ROWS = 0x11
MSG_GET_ROWS = 0x12
MSG_SET_ROWS = 0x13
MSG_ADD_FULL = 0x14
MSG_GET_FULL = 0x15
MSG_KV_ADD = 0x16
MSG_KV_GET = 0x17
MSG_GET_STATE = 0x18
MSG_SET_STATE = 0x19
# multi-op frame: N logical sub-ops (each a complete inner frame with its
# own meta + codec wire, wire.pack_batch) delivered, dispatched, and acked
# as ONE request — the client send window's unit (ps/tables._SendWindow).
# Unknown to the native C++ server by design: it punts to the Python
# handler, which already holds the native shard mutex there.
MSG_BATCH = 0x1A
# remote-dashboard RPC: any worker pulls a rank's full telemetry
# snapshot — Dashboard monitor histograms, free-form notes, and the
# first-class per-shard server stats (queue depth, pending bytes, wave
# distribution, version) — as the REPLY META (pure JSON, no blobs).
# Surfaced as table.server_stats(rank) / PSService.stats(rank); the
# native C++ server punts it to Python like any unknown type.
MSG_STATS = 0x1B
# compact liveness verdict (flight-recorder plane, PR 4): serve-loop
# heartbeat age, shard queue depth, oldest in-flight op age, last
# watchdog verdict — as the REPLY META (pure JSON, no blobs). Cheap by
# construction (counter reads only, never a shard lock): it must answer
# even when the data plane is wedged, which is exactly when it is
# asked. Surfaced as table.server_health(rank) / PSService.health(rank);
# the native server punts it like MSG_STATS.
MSG_HEALTH = 0x1C
# replica subscription pull (serving plane, docs/SERVING.md): one
# committed full-shard row snapshot + the shard's mutation version as
# the reply. Request meta: {"table", "since": last seen version,
# "chunk": rows per sub-frame}. A shard whose version still equals
# "since" replies a tiny {"unchanged": true} frame — the epoch cadence
# costs an idle table almost nothing — and big snapshots stream as
# PR-5 chunked replies. Served off-lock under an epoch pin
# (shard.export_snapshot); the native C++ server punts it to Python
# like MSG_STATS (and its meta whitelist rejects "since" regardless).
MSG_SNAPSHOT = 0x1D
# multi-owner super-frame (mesh data plane, ps/spmd.py; flag
# ps_fanout): N complete inner frames — each a full wire.encode output
# whose meta names its OWNING rank under "ow" (wire.OWNER_META_KEY) —
# delivered, dispatched across ALL the colocated shards of the
# destination process, and acked as ONE request. The reply is the
# inner REPLY frames packed the same way (one per sub-op, OK or ERR,
# in order). This is the reference's worker-side Partition fan-out
# collapsed to one round trip per destination process instead of one
# per shard; colocated plain row adds/gathers additionally collapse
# server-side into ONE SPMD dispatch over the mesh-stacked shard
# group (_handle_multi). Unknown to the native C++ server by design:
# it punts, like MSG_BATCH.
MSG_MULTI = 0x1E

config.define_string("ps_rendezvous", "",
                     "directory for async-PS rank rendezvous (empty = use "
                     "the jax.distributed KV store when available)")
config.define_int("ps_rank", -1,
                  "async-PS rank override (-1 = jax.process_index); lets "
                  "the async plane run without a JAX coordinator, like the "
                  "reference PS needed only its own transport")
config.define_int("ps_world", 0,
                  "async-PS world-size override (0 = jax.process_count)")
config.define_int("ps_port", 0, "async-PS listen port (0 = ephemeral)")
config.define_string("ps_host", "127.0.0.1",
                     "async-PS bind host. Single-host runs keep the "
                     "loopback default; multi-host runs set 0.0.0.0 (the "
                     "published address is then the auto-detected routable "
                     "IP) or this machine's explicit routable IP")
config.define_float("ps_local_shard_min_mb", 1.0,
                    "shard an owned row range over the process's local "
                    "devices only when it is at least this big (tiny "
                    "shards would pay GSPMD partitioning overhead for "
                    "nothing); 0 = always shard")
config.define_float("ps_timeout", 300.0,
                    "async-PS request timeout seconds (generous default: "
                    "a shard's FIRST add/get of each bucket size jit-"
                    "compiles on the owner, which can take tens of seconds "
                    "per program on a cold TPU)")
config.define_float("ps_connect_timeout", 30.0,
                    "async-PS peer connect timeout seconds")
config.define_float("ps_reconnect_backoff", 5.0,
                    "seconds to fail fast against a rank that just died "
                    "before trying a fresh rendezvous lookup + reconnect "
                    "(lets a RESTARTED rank rejoin without every request "
                    "to a still-dead one stalling a connect timeout)")
config.define_bool("ps_coalesce", True,
                   "server-side request coalescing: Adds queued for the "
                   "same shard while an update is in flight are merged "
                   "(deltas summed) into ONE batched jitted update instead "
                   "of one serialized update per message — aggregate "
                   "throughput then rises with worker count instead of "
                   "collapsing on the shard lock (the reference server "
                   "applied strictly per-message, src/server.cpp:36-58). "
                   "Merged adds apply as if their deltas arrived in a "
                   "single message: exact for default/sgd updaters, within "
                   "the ASGD contract for the stateful ones")
config.define_bool("ps_native", True,
                   "serve and speak the async-PS wire through the native "
                   "C++ transport (native/mv_ps.cpp) when libmv_ps.so is "
                   "available: accepted connections are adopted by C++ "
                   "threads that serve hot row ops on host-backed linear "
                   "shards with zero Python in the loop (the reference's "
                   "C++ server hot path, src/server.cpp:36-58), and "
                   "clients send framed adds/gets straight from C. "
                   "Anything the native side cannot serve punts to the "
                   "Python handlers unchanged. Off = pure-Python plane")
config.define_float("ps_health_timeout", 5.0,
                    "MSG_HEALTH probe reply timeout seconds. Deliberately "
                    "watchdog-scale, NOT ps_timeout (300 s): a SIGSTOPPED "
                    "rank's kernel still completes the TCP handshake from "
                    "the listen backlog, and the probe must classify "
                    "'alive but wedged' in seconds — blocking a "
                    "supervisor's poll loop for 5 minutes against the "
                    "exact rank it is triaging would defeat the probe")
config.define_int("ps_probe_attempts", 1,
                  "one-shot probe (MSG_HEALTH/MSG_STATS) attempts per "
                  "pull, all within ONE ps_health_timeout budget "
                  "(deadline propagation, utils/retry.py): > 1 rides "
                  "out a transient connect refusal against a "
                  "restarting rank instead of classifying it down on "
                  "the first RST. Default 1 keeps the supervisor's "
                  "'unreachable IS the answer' fail-fast semantics")
config.define_float("ps_shutdown_grace", 60.0,
                    "seconds a rank keeps its shards served at shutdown "
                    "while waiting for peers to ALSO reach shutdown (the "
                    "reference's MV_ShutDown barrier, src/zoo.cpp:103 — "
                    "without it a fast rank's teardown kills peers still "
                    "pulling from its shard); observed-dead ranks are "
                    "skipped, timeout proceeds with a warning")


class PSError(RuntimeError):
    pass


class PSPeerError(PSError):
    """A specific peer is unreachable/dead; traffic to others is unaffected."""


def _sub_err(e: BaseException) -> Dict:
    """A super-frame sub-op's error as reply meta: the message plus a
    ``"peer"`` marker for peer-death errors, so the client-side fan-out
    can rethrow the TYPED PSPeerError (callers branch on it — a dead
    owner must not collapse into a generic request error just because
    the op rode a super-frame)."""
    out = {"error": f"{type(e).__name__}: {e}"}
    if isinstance(e, PSPeerError):
        out["peer"] = True
    return out


def await_reply(fut: cf.Future, timeout: float, what: str):
    """``fut.result`` with waiter timeouts surfaced as PSPeerError — a
    request that never got a reply is a peer-health event, not a generic
    concurrent.futures condition."""
    try:
        return fut.result(timeout=timeout)
    except cf.TimeoutError as e:
        raise PSPeerError(f"{what}: no reply within {timeout}s") from e


# ---------------------------------------------------------------------- #
# rendezvous backends
# ---------------------------------------------------------------------- #
class FileRendezvous:
    """Shared-directory rendezvous (the test/multi-process-on-one-host path;
    the reference's machine_file, include/multiverso/net/zmq_net.h:20-61)."""

    def __init__(self, directory: str):
        self._dir = directory
        os.makedirs(directory, exist_ok=True)

    def publish(self, rank: int, addr: str) -> None:
        tmp = os.path.join(self._dir, f".{rank}.addr.tmp")
        with open(tmp, "w") as f:
            f.write(addr)
        os.replace(tmp, os.path.join(self._dir, f"{rank}.addr"))

    def lookup(self, rank: int, timeout: float) -> str:
        path = os.path.join(self._dir, f"{rank}.addr")
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                with open(path) as f:
                    addr = f.read().strip()
                if addr:
                    return addr
            except FileNotFoundError:
                pass
            time.sleep(0.02)
        raise PSPeerError(f"rank {rank} never published an address "
                          f"({path} missing after {timeout}s)")

    def mark(self, rank: int, tag: str, value: str = "1") -> None:
        """Publish a marker (shutdown quiesce handshake). ``value`` stamps
        the marker with this incarnation's identity (the published addr),
        so a REUSED rendezvous directory's stale markers from a previous
        run never satisfy the current run's barrier."""
        tmp = os.path.join(self._dir, f".{tag}.{rank}.tmp")
        with open(tmp, "w") as f:
            f.write(value)
        os.replace(tmp, os.path.join(self._dir, f"{tag}.{rank}"))

    def wait_mark(self, rank: int, tag: str, timeout: float,
                  expect: Optional[str] = None) -> bool:
        path = os.path.join(self._dir, f"{tag}.{rank}")
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                with open(path) as f:
                    got = f.read()
                if expect is None or got == expect:
                    return True
            except OSError:
                pass
            time.sleep(0.02)
        return False


class JaxRendezvous:
    """Rendezvous over the jax.distributed coordinator's KV store — the
    multi-host path; topology discovery the reference needed a Controller
    for (src/controller.cpp:38-80) comes from the TPU runtime."""

    def __init__(self, namespace: str = "mv_ps"):
        from jax._src import distributed  # jax's coordinator KV client
        client = distributed.global_state.client
        if client is None:
            raise PSError("jax.distributed is not initialized")
        self._client = client
        self._ns = namespace

    def publish(self, rank: int, addr: str) -> None:
        self._client.key_value_set(f"{self._ns}/{rank}", addr)

    def lookup(self, rank: int, timeout: float) -> str:
        try:
            return self._client.blocking_key_value_get(
                f"{self._ns}/{rank}", int(timeout * 1000))
        except Exception as e:
            raise PSPeerError(f"rank {rank} not in coordinator KV store: "
                              f"{e}") from e

    def mark(self, rank: int, tag: str, value: str = "1") -> None:
        self._client.key_value_set(f"{self._ns}/{tag}/{rank}", value)

    def wait_mark(self, rank: int, tag: str, timeout: float,
                  expect: Optional[str] = None) -> bool:
        # the coordinator KV store dies with the job, so stale cross-run
        # markers cannot exist here; ``expect`` is accepted for interface
        # parity but a present key is sufficient
        try:
            self._client.blocking_key_value_get(
                f"{self._ns}/{tag}/{rank}", int(max(timeout, 0.001) * 1000))
            return True
        except Exception:
            return False


# ---------------------------------------------------------------------- #
# client side: one persistent connection per remote rank
# ---------------------------------------------------------------------- #
_peer_gen = itertools.count()   # per-incarnation msg-id bases (below)


class _Peer:
    def __init__(self, rank: int, addr: str, connect_timeout: float,
                 io_timeout: float,
                 on_death: Optional[Callable[["_Peer", Exception],
                                             None]] = None,
                 src: int = -1):
        self.rank = rank
        self.src = src     # the LOCAL rank (fault-plane src identity;
        #                    -1 = unknown, plane falls back to its own)
        self.addr = addr   # the resolved incarnation address (native
                           # client conns to the same rank reuse it)
        self._on_death = on_death
        host, port = addr.rsplit(":", 1)
        # connect retries ride the shared capped-exponential policy
        # (utils/retry.py) with the connect timeout as the DEADLINE —
        # the flat 50 ms loop this replaces synchronized every client's
        # reconnect storm against a respawning rank
        deadline = _retry.deadline_in(connect_timeout)
        backoff = _retry.Backoff(base_s=0.05, cap_s=1.0)
        attempt = 0
        while True:
            try:
                self._sock = socket.create_connection(
                    (host, int(port)), timeout=connect_timeout)
                break
            except OSError as e:
                if not backoff.sleep(attempt, deadline):
                    raise PSPeerError(
                        f"cannot connect to rank {rank} at {addr}: {e}"
                    ) from e
                attempt += 1
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock.settimeout(io_timeout)
        self._send_lock = threading.Lock()
        # reorder-injection holdback (chaos plane, ps/faults.py): held
        # encoded frames, released after a LATER frame ships. Only ever
        # touched under _send_lock, and only when the plane is armed.
        self._held: List[bytes] = []
        self._pending: Dict[int, cf.Future] = {}
        self._pending_lock = threading.Lock()
        # msg ids start at a per-INCARNATION base (generation << 32):
        # the flight recorder keys in-flight ops by (rank, msg_id), and
        # a reconnected incarnation restarting at 0 would collide with
        # the dying one's unswept ids — its death sweep could then erase
        # the fresh incarnation's live entries (correlation is the outer
        # frame's job either way; the server just echoes the id)
        self._next_id = next(_peer_gen) << 32
        self._dead: Optional[Exception] = None
        self._recv_thread = threading.Thread(
            target=self._recv_loop, name=f"ps-peer-{rank}", daemon=True)
        self._recv_thread.start()

    def _recv_loop(self) -> None:
        try:
            while True:
                try:
                    msg_type, msg_id, meta, arrays = wire.recv(self._sock)
                except TimeoutError:
                    # idle socket, nothing in flight is harmed: the io
                    # timeout bounds BLOCKED REPLIES via each waiter's
                    # fut.result(timeout), not connection lifetime — a
                    # healthy-but-quiet peer must not be declared dead
                    continue
                if msg_type == MSG_REPLY_CHUNK:
                    # one sub-frame of a streamed reply: feed the
                    # requester's sink NOW (decode + scatter overlap the
                    # receive) — the entry stays pending until the
                    # stream's closing MSG_REPLY_OK. A sink failure is
                    # remembered and surfaces on the final frame (the
                    # caller must never consume a half-scattered buffer
                    # as complete).
                    with self._pending_lock:
                        fut = self._pending.get(msg_id)
                    if fut is not None:
                        sink = getattr(fut, "_mv_chunk_sink", None)
                        try:
                            if sink is None:
                                # chunks only arrive when the REQUEST
                                # asked for them, and every asking
                                # caller registers a sink — a sink-less
                                # chunk is a caller bug that must fail
                                # the op, not resolve it with a silently
                                # discarded payload
                                raise PSError(
                                    "chunked reply frame without a "
                                    "registered chunk sink")
                            sink(meta, arrays)
                        except Exception as e:  # noqa: BLE001
                            fut._mv_chunk_err = e
                    continue
                with self._pending_lock:
                    fut = self._pending.pop(msg_id, None)
                if fut is None:
                    continue
                _flight.end_op(self.rank, msg_id,
                               ok=msg_type != MSG_REPLY_ERR)
                if msg_type == MSG_REPLY_ERR:
                    fut.set_exception(PSError(
                        f"rank {self.rank}: {meta.get('error', '?')}"))
                else:
                    cerr = getattr(fut, "_mv_chunk_err", None)
                    if cerr is not None:
                        fut.set_exception(PSError(
                            f"rank {self.rank}: chunk sink failed: "
                            f"{type(cerr).__name__}: {cerr}"))
                    else:
                        fut.set_result((meta, arrays))
        except Exception as e:  # socket death: fail everything in flight
            err = PSPeerError(f"rank {self.rank} connection lost: {e}")
            self._dead = err
            with self._pending_lock:
                pending, self._pending = self._pending, {}
            # black box FIRST, while THIS incarnation's unacked ops are
            # still in the recorder's in-flight table: the dump is the
            # artifact that names this dead rank's oldest unacked msg
            # for postmortem. Only a death with unacked traffic is a
            # diagnostic event — a quiet conn dying at shutdown must not
            # write dumps. The sweep is scoped to OUR msg ids: a
            # reconnected fresh incarnation may already have live ops
            # under the same rank during the dump window.
            _flight.record(_flight.EV_PEER_DEAD, peer=self.rank,
                           note=str(e)[:120])
            if pending:
                _flight.dump_global(
                    f"peer rank {self.rank} connection lost with "
                    "requests in flight")
            _flight.RECORDER.fail_peer(self.rank, msg_ids=list(pending))
            for fut in pending.values():
                if not fut.done():
                    fut.set_exception(err)
            if self._on_death is not None:
                self._on_death(self, err)

    def request(self, msg_type: int, meta: Dict,
                arrays: Sequence[np.ndarray],
                chunk_sink: Optional[Callable] = None) -> cf.Future:
        fut: cf.Future = cf.Future()
        if chunk_sink is not None:
            # attached BEFORE the pending insert: the recv loop may see
            # the first chunk the instant the request hits the wire
            fut._mv_chunk_sink = chunk_sink
        if self._dead is not None:
            fut.set_exception(self._dead)
            return fut
        with self._send_lock:
            msg_id = self._next_id
            self._next_id += 1
            with self._pending_lock:
                self._pending[msg_id] = fut
            # probes are tracked in flight (stuck probes should age) but
            # keep their send/ack edges out of the ring — supervisor
            # polling must not wrap the tape (server-side rule mirrored)
            _flight.begin_op(self.rank, msg_id, msg_type,
                             sum(getattr(a, "nbytes", 0) for a in arrays),
                             record=msg_type not in (MSG_PING, MSG_STATS))
            try:
                if _faults.PLANE.armed:   # chaos plane (off: one load)
                    self._send_faulted(msg_type, msg_id, meta, arrays)
                else:
                    wire.send(self._sock, msg_type, msg_id, meta, arrays)
            except OSError as e:
                err = PSPeerError(f"rank {self.rank} send failed: {e}")
                self._dead = err
                _flight.end_op(self.rank, msg_id, ok=False)
                with self._pending_lock:
                    self._pending.pop(msg_id, None)
                fut.set_exception(err)
                if self._on_death is not None:
                    self._on_death(self, err)
                return fut
            except BaseException:
                # encode/packing failure (bad meta, exotic array): not a
                # peer-death signal — unwind THIS op's bookkeeping and
                # re-raise. Leaving the recorder entry would age into a
                # permanent spurious "stuck" verdict (fail_peer never
                # sweeps a live peer), and leaving the pending future
                # would hold its waiter to the full ps_timeout.
                _flight.end_op(self.rank, msg_id, ok=False)
                with self._pending_lock:
                    self._pending.pop(msg_id, None)
                raise
        # the recv loop may have died BETWEEN the entry _dead check and the
        # _pending insert (it fails only futures it saw in _pending when it
        # swept) — re-check so this future fails fast instead of dangling
        # until the 300s waiter timeout
        if self._dead is not None:
            with self._pending_lock:
                still = self._pending.pop(msg_id, None)
            # close the recorder's entry UNCONDITIONALLY (end_op is
            # idempotent): the recv loop's fail_peer sweep may have run
            # BEFORE begin_op registered this op — in that interleaving
            # the sweep also already took _pending[msg_id], so gating on
            # `still` would skip the close and the orphaned entry would
            # age forever: a permanent spurious "stuck" verdict
            _flight.end_op(self.rank, msg_id, ok=False)
            if still is not None and not fut.done():
                fut.set_exception(self._dead)
        return fut

    # chaos plane (ps/faults.py; reached ONLY when a scenario is armed
    # — the hot path's single `PLANE.armed` load guards it)
    _HELD_CAP = 8   # safety ceiling on the rule's reorder depth

    def _send_faulted(self, msg_type: int, msg_id: int, meta,
                      arrays) -> None:
        """One outbound frame through the armed fault plane. Runs under
        ``_send_lock`` (the caller holds it), so the holdback list and
        the socket are single-writer here. Injected partitions/resets
        raise :class:`faults.InjectedFault` (a ConnectionResetError) —
        the caller's OSError handling then takes the organic peer-death
        path, which is the point."""
        plan = _faults.PLANE.plan_send(self.rank, msg_type, msg_id,
                                       src=self.src)
        if plan is None:
            wire.send(self._sock, msg_type, msg_id, meta, arrays)
            self._release_held()
            return
        if plan.delay_s:
            # a slow wire backpressures senders to this peer exactly
            # like a real one: the sleep holds the send lock
            time.sleep(plan.delay_s)
        if plan.reset:
            # injected partition/reset: kill the conn FIRST so the recv
            # loop observes the death (fails in-flight futures, replay
            # re-arms), then fail this send like the kernel would
            try:
                self._sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            raise _faults.InjectedFault(
                f"injected {'/'.join(plan.kinds)} to rank {self.rank}")
        if plan.drop:
            return   # silently eaten; the caller's timeout is the signal
        buf = wire.encode(msg_type, msg_id, meta, arrays)
        if plan.reorder and len(self._held) < min(plan.depth,
                                                 self._HELD_CAP):
            self._held.append(buf)   # ships AFTER the next frame...
            timer = threading.Timer(plan.hold_s, self._release_held,
                                    kwargs={"locked": False})
            timer.daemon = True      # ...or after hold_s: a blocking
            timer.start()            # caller awaiting THIS frame's ack
            return                   # is its own only traffic source
        self._sock.sendall(buf)
        # a reorder-claimed frame never duplicates — even when the
        # holdback was full and it shipped immediately — so the
        # plane's injected counts/log match what hit the wire
        if plan.duplicate and not plan.reorder:
            self._sock.sendall(buf)
        self._release_held()

    def _release_held(self, locked: bool = True) -> None:
        """Flush reorder-held frames (oldest first) now that a later
        frame has shipped (``locked=True``: caller holds the send
        lock) or the hold timer fired (``locked=False``). Socket
        errors are swallowed on BOTH paths — a held frame dying with
        the conn is just an injected drop, the recv loop owns the
        death signal, and the CURRENT frame's future (its own send
        already succeeded) must not be failed by a sibling's
        corpse."""
        if not self._held:
            return
        if not locked:
            with self._send_lock:
                self._release_held()
            return
        held, self._held = self._held, []
        try:
            for buf in held:
                self._sock.sendall(buf)
        except OSError:
            pass

    def close(self) -> None:
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._sock.close()


# ---------------------------------------------------------------------- #
# the service
# ---------------------------------------------------------------------- #
def _routable_ip() -> str:
    """Best-effort routable address of this host (the reference's
    GetLocalIPAddress, src/util/net_util.cpp — which was Windows-only;
    this one works everywhere): the UDP-connect trick picks the egress
    interface without sending a packet."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("10.255.255.255", 1))
        return s.getsockname()[0]
    except OSError:
        try:
            return socket.gethostbyname(socket.gethostname())
        except OSError:
            return "127.0.0.1"
    finally:
        s.close()


def oneshot_probe(addr: str, msg_type: int, timeout: float,
                  connect_timeout: Optional[float] = None) -> Dict:
    """One telemetry pull (MSG_HEALTH / MSG_STATS / MSG_PING) over a
    fresh one-shot connection to ``addr``; returns the reply meta. The
    shared socket body of :meth:`PSService.health`/``stats_oneshot`` and
    the address-only consumers (``tools/mvtop.py`` probes straight from
    a rendezvous directory, no PSService constructed). The connect is
    budgeted like the reply: a partitioned host (SYN dropped, no RST)
    must not hold a triage loop for the data plane's 30 s connect
    timeout. Raises the raw socket/wire errors (callers wrap them in
    their own peer-health types); an ERR reply raises PSError with the
    server's message."""
    host, port = addr.rsplit(":", 1)
    ct = timeout if connect_timeout is None else min(timeout,
                                                     connect_timeout)
    with socket.create_connection((host, int(port)), timeout=ct) as s:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(timeout)
        wire.send(s, msg_type, 0, {})
        reply_type, _mid, meta, _ = wire.recv(s)
    if reply_type == MSG_REPLY_ERR:
        raise PSError(f"probe to {addr}: {meta.get('error', '?')}")
    return meta


class PSService:
    """Listener + shard registry + peer pool for one process."""

    def __init__(self, rank: int, world: int, rendezvous=None,
                 host: Optional[str] = None, port: Optional[int] = None,
                 defer_publish: bool = False):
        """``defer_publish=True`` holds the rendezvous publish until
        :meth:`publish_addr` — a RESTARTED shard must restore from its
        checkpoint before any survivor can discover the new address,
        or a replayed frame landing on the still-empty shard would
        commit its sequence, ack, and then be wiped by the restore
        (an acked op silently lost). See failover.rejoin."""
        self.rank, self.world = rank, world
        if host is None:
            host = config.get_flag("ps_host") or "127.0.0.1"
        self._rendezvous = rendezvous
        # process-colocation identity (ps/spmd.py): services sharing a
        # process AND a rendezvous may route to each other in-process
        # when ps_fanout is armed. The routing registry entry appears
        # with the rendezvous publish (deferred-publish services stay
        # invisible until their restore, same rule as the address).
        self._proc_key = _spmd.proc_key(rendezvous)
        self._routed_seen: set = set()
        self._routed_dead: set = set()
        self._handlers: Dict[str, Callable] = {}
        # table -> shard object for MSG_STATS (handlers alone are opaque
        # closures; the stats RPC needs the shard's stats() surface)
        self._shards: Dict[str, Any] = {}
        self._handlers_cv = threading.Condition()
        # telemetry: adopt the trace_ids flag under this service's rank
        # (the exporter starts at the END of __init__, once addr exists);
        # the always-on flight recorder pins the same rank and the
        # watchdog thread starts (flag-gated) to age its in-flight table
        _trace.configure(rank)
        _flight.configure(rank)
        _devstats.configure(rank)
        # fault plane: adopt the rank; arms from faults_spec /
        # $MV_FAULTS_SPEC when set (chaos bench workers), else stays
        # the null object — zero injection codepaths reachable
        _faults.configure(rank)
        log.set_rank(rank)
        _watchdog.ensure_started()
        # memory sampler (flag memstats_interval_s; the byte LEDGER is
        # always on and pull-only — this only starts the RSS/device-
        # census cadence feeding the windowed leak verdicts)
        _memstats.ensure_started()
        self._peers: Dict[int, _Peer] = {}
        self._peers_lock = threading.Lock()
        self._peer_locks: Dict[int, threading.Lock] = {}
        # rank -> last observed death (monotonic ts); feeds the reconnect
        # backoff and the death hooks (elastic integration)
        self._dead_ranks: Dict[int, float] = {}
        self._death_hooks: List[Callable[[int], None]] = []
        self._conns: List[socket.socket] = []
        self._conns_lock = threading.Lock()
        self._closed = False
        # fire-and-forget local dispatch (ref: ops on the local shard still
        # hop through the Server actor thread, zoo.cpp SendTo)
        self._local_exec = cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ps-local")
        # native transport (flag ps_native + libmv_ps.so): accepted
        # connections are adopted by C++ serving threads; _native_cb must
        # stay referenced or ctypes frees the callback trampoline under
        # the C++ threads still holding it
        self._native = None        # cleared (under _native_lock) at close
        self._native_raw = None    # NEVER cleared: punt callbacks on C++
        #                            conn threads may run right up to the
        #                            join inside server_free, which close()
        #                            calls only after those threads exit
        self._native_cb = None
        self._native_lock = threading.Lock()
        self._nconns: Dict[int, Any] = {}
        # shard incarnation generation (flag ps_generation): 0 for a
        # first boot; the failover supervisor spawns each replacement
        # at gen+1 and MSG_HEALTH echoes it, so a restarted shard is
        # visible at a glance (mvtop's gen column). Assigned BEFORE
        # the listener exists: a health probe can land the instant the
        # accept loop starts, and health_payload reads this
        self.generation = int(config.get_flag("ps_generation"))
        if config.get_flag("ps_native"):
            from multiverso_tpu.ps import native as ps_native
            if ps_native.available():
                self._native, self._native_cb = ps_native.server_new(
                    self._punt, rank)
                self._native_raw = self._native
        self._listener = socket.create_server(
            (host, port if port is not None else config.get_flag("ps_port")))
        # published address must be ROUTABLE: a wildcard bind advertises the
        # machine's egress IP, not 0.0.0.0 (peers could never connect to it)
        publish_host = (_routable_ip() if host in ("", "0.0.0.0", "::")
                        else host)
        self.addr = "%s:%d" % (publish_host,
                               self._listener.getsockname()[1])
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="ps-accept", daemon=True)
        self._accept_thread.start()
        self._published = False
        if rendezvous is not None and not defer_publish:
            self.publish_addr()
        # flag-gated metrics exporter with the rich (shard-aware)
        # payload; no-op unless metrics_dir is set
        _exporter.ensure_started(rank, self.stats_payload)
        # controller-side cluster observability (flag
        # stats_poll_interval_s): rank 0 polls every rank's MSG_STATS +
        # MSG_HEALTH over the one-shot probe path and keeps the merged
        # cluster time series
        if rank == 0:
            _aggregator.ensure_started(self)
        # flag-gated per-shard failover checkpointer (failover_dir +
        # failover_ckpt_interval_s): the durable half of exactly-once
        # replay — shards registered later are picked up per cycle
        _failover.ensure_checkpointer(self)
        log.debug("PSService rank %d/%d listening on %s", rank, world,
                  self.addr)

    # ----------------------------- server side ----------------------- #
    def publish_addr(self) -> None:
        """Publish (or re-publish) this incarnation's address through
        the rendezvous — the moment peers may discover it. Deferred-
        publish services (restarted shards) call this AFTER their
        checkpoint restore; idempotent. The in-process routing registry
        entry (ps/spmd.py) appears at the same moment and for the same
        reason: a survivor routing a replay onto the still-empty shard
        would commit, ack, and then be wiped by the restore."""
        if self._rendezvous is not None:
            self._rendezvous.publish(self.rank, self.addr)
            self._published = True
        _spmd.register_service(self)

    def register_handler(self, table: str, handler: Callable,
                         shard=None) -> None:
        """``handler(msg_type, meta, arrays) -> (meta, arrays)``, called on
        a connection thread; the shard serializes internally. When
        ``shard`` is a host-backed linear RowShard and the native server
        is live, the shard's buffer registers with C++ for zero-Python
        serving of the hot ops — and the Python handler (which then only
        sees punted messages: compressed wires, checkpoint state, sparse
        protocol) wraps itself in the native shard mutex so its buffer
        mutations serialize with C++ applies."""
        if self._native is not None and shard is not None:
            wrapped = self._try_register_native(table, handler, shard)
            if wrapped is not None:
                handler = wrapped
        with self._handlers_cv:
            self._handlers[table] = handler
            if shard is not None:
                self._shards[table] = shard
            self._handlers_cv.notify_all()
        if shard is not None:
            # mesh-stacked grouping (flag ps_spmd_stack, ps/spmd.py):
            # colocated same-table device shards pool into ONE
            # mesh-sharded stacked array with single-dispatch SPMD
            # apply/gather. No-op unless armed and the shard qualifies.
            _spmd.try_join(self, table, shard)

    def _try_register_native(self, table: str, handler: Callable,
                             shard) -> Optional[Callable]:
        from multiverso_tpu.ps import native as ps_native
        from multiverso_tpu.ps.shard import RowShard
        from multiverso_tpu.updaters import STATELESS_LINEAR
        # EXACT RowShard only: HashShard grows/remaps its buffer, which
        # would leave C++ writing through a stale pointer
        if type(shard) is not RowShard or not shard._np_mode:
            return None
        if config.get_flag("ps_fanout"):
            # process-coalesced routing (ps/spmd.py): a fanout world's
            # traffic arrives in-process, where a native registration
            # only costs — every routed op would cross the FFI to take
            # the C++ shard mutex around its whole python handler, and
            # the sampled 2-worker profile showed exactly that mutex
            # eating 60%+ of the wall. The C++ fast path exists for
            # SOCKET clients, which a fanout-armed world does not use.
            return None
        sign = STATELESS_LINEAR.get(type(shard.updater))
        if sign is None:
            return None
        nworkers = 0 if shard._dirty is None else shard._dirty.shape[0]
        with self._native_lock:
            if self._native is None:   # raced close(): python plane only
                return None
            pin = ps_native.register_shard(
                self._native, table, shard.lo, shard.n, shard.num_col,
                shard._data, sign, shard._dirty, nworkers)
        if pin is None:
            return None
        # the pin addresses THIS shard object — stable across same-name
        # re-registration and server close (review finding: a name lookup
        # at unlock time could unlock a DIFFERENT shard's mutex)
        shard.bind_native(pin)

        def locked_handler(msg_type, meta, arrays,
                           _inner=handler, _pin=pin):
            ps_native.shard_pin_lock(_pin)
            try:
                return _inner(msg_type, meta, arrays)
            finally:
                ps_native.shard_pin_unlock(_pin)

        return locked_handler

    def _punt(self, conn_id: int, frame: bytes) -> None:
        """Frames the native server can't serve, delivered synchronously
        on the C++ connection thread (per-connection FIFO preserved).
        Mirrors _serve_conn's dispatch; the reply goes back through the
        native conn's write lock."""
        from multiverso_tpu.ps import native as ps_native
        try:
            msg_type, msg_id, meta, arrays = wire.parse_frame(frame)
        except wire.WireError as e:
            # Header was sane (C++ validated magic/bounds) but the body
            # failed to parse. The python plane fails fast by killing the
            # connection; silently dropping here would instead park the
            # peer for the full ps_timeout. The header's msg_id is still
            # trustworthy, so send an ERR reply the peer can raise on.
            log.debug("ps native punt: malformed frame (%s)", e)
            try:
                reply = wire.encode(MSG_REPLY_ERR, wire.peek_msg_id(frame),
                                    {"error": f"WireError: {e}"})
                ps_native.send_raw(self._native_raw, conn_id, reply)
            except Exception:
                log.debug("ps native punt: ERR reply for malformed frame "
                          "failed; dropping")
            return
        # the serve beat AND the ring edges mark DATA-PLANE liveness:
        # health/stats/ping probes refresh neither — a wedged server
        # polled at 2 Hz must report a growing serve_age_s, and probe
        # noise must not wrap the ring past the pre-wedge evidence
        # before the operator reads the (refreshed-in-place) fault dump
        probe = msg_type in (MSG_PING, MSG_STATS, MSG_HEALTH)
        if not probe:
            _flight.beat("serve")
            _flight.record(_flight.EV_RECV, msg_type=msg_type,
                           msg_id=msg_id)
        try:
            if msg_type == MSG_PING:       # native serves PING; belt only
                reply = wire.encode(MSG_REPLY_OK, msg_id,
                                    {"rank": self.rank})
            elif msg_type == MSG_STATS:    # remote dashboard pull
                reply = wire.encode(MSG_REPLY_OK, msg_id,
                                    self.stats_payload())
            elif msg_type == MSG_HEALTH:   # liveness verdict pull
                reply = wire.encode(MSG_REPLY_OK, msg_id,
                                    self.health_payload())
            else:
                tr = (meta.get(wire.TRACE_META_KEY)
                      if _trace.enabled() else None)
                t0 = time.time() if tr is not None else 0.0
                if msg_type == MSG_MULTI:
                    # multi-owner super-frame punted by the native
                    # server (unknown type, like MSG_BATCH): dispatch
                    # across this process's colocated shards
                    with monitor("ps[multi].serve"):
                        rmeta, rarrays = self._handle_multi(meta, arrays)
                else:
                    handler = self._wait_handler(meta["table"])
                    with monitor(f"ps[{meta['table']}].serve"):
                        rmeta, rarrays = handler(msg_type, meta, arrays)
                if tr is not None:
                    _trace.add_span("ps.serve", t0, time.time(), trace=tr,
                                    args={"table": meta.get("table",
                                                            "multi"),
                                          "type": msg_type})
                if isinstance(rarrays, wire.ChunkedReply):
                    # streamed reply over the native conn: each chunk
                    # goes through send_raw (the conn's C++ write lock
                    # orders them); the closing OK is the `reply` below
                    for cmeta, carrays in rarrays.chunks:
                        ps_native.send_raw(
                            self._native_raw, conn_id,
                            wire.encode(MSG_REPLY_CHUNK, msg_id, cmeta,
                                        carrays))
                        _flight.record(_flight.EV_GET_CHUNK,
                                       msg_type=msg_type, msg_id=msg_id)
                    rmeta, rarrays = rarrays.meta, ()
                reply = wire.encode(MSG_REPLY_OK, msg_id, rmeta, rarrays)
        except Exception as e:
            log.debug("ps handler error: %s", e)
            if isinstance(e, MemoryError):
                # OOM forensics (same rule as the python serve loop)
                _memstats.oom_dump("MemoryError serving a punted request")
            reply = wire.encode(MSG_REPLY_ERR, msg_id,
                                {"error": f"{type(e).__name__}: {e}"})
        # _native_raw, not _native: close() clears the latter while punts
        # may still be in flight; the raw handle stays valid until
        # server_free (which runs after this conn thread is joined)
        if not probe:
            _flight.record(_flight.EV_REPLY, msg_type=msg_type,
                           msg_id=msg_id, nbytes=len(reply))
        ps_native.send_raw(self._native_raw, conn_id, reply)

    # ----------------------------- telemetry -------------------------- #
    def stats_payload(self) -> Dict:
        """This rank's full telemetry snapshot (the MSG_STATS reply meta
        and the exporter record share this one shape): Dashboard monitor
        histograms, free-form notes, and per-shard server stats. Pure
        JSON-safe data — consumers on other ranks can never mutate live
        state through it."""
        shards = {}
        with self._handlers_cv:
            items = list(self._shards.items())
        for table, shard in items:
            try:
                stats = shard.stats()
            except Exception as e:  # noqa: BLE001 — one bad shard must
                stats = {"error": f"{type(e).__name__}: {e}"}  # not hide
            shards[table] = stats                              # the rest
        # ONE record shape: the monitors/notes assembly is the
        # exporter's (default_stats_fn), overlaid with this service's
        # identity and shard registry — MSG_STATS replies and exporter
        # records must never diverge
        payload = _exporter.default_stats_fn()
        payload.update(rank=self.rank, world=self.world, addr=self.addr,
                       shards=shards)
        # serving plane: this process's read replicas (lag, versions,
        # cache hit rate, shed counters) — the block mvtop's serving
        # panel and the cluster aggregator merge. Process-global like
        # the monitors (same (host, pid) dedupe rule applies there).
        try:
            serving = _serving_replica.stats_snapshot()
            if serving:
                payload["serving"] = serving
        except Exception:   # noqa: BLE001 — telemetry never breaks stats
            pass
        # the steps' block (trace.step_report over the step spans this
        # process recorded): per-process stall fraction / recompile
        # summary — mvtop's stall%/recompiles columns and the aggregator
        # pass it through like serving. Process-global (same (host, pid)
        # collapse as the monitors).
        try:
            profile = _trace.step_summary()
            if profile:
                payload["profile"] = profile
        except Exception:   # noqa: BLE001
            pass
        # memory plane (telemetry/memstats.py): the byte ledger + RSS +
        # recent leak verdicts. Process-global like the monitors (same
        # (host, pid) dedupe in the aggregator); always present — the
        # ledger is always on, like the flight recorder.
        try:
            payload["memory"] = _memstats.stats_snapshot()
        except Exception:   # noqa: BLE001 — telemetry never breaks stats
            pass
        # device plane (telemetry/devstats.py): transfer/collective/
        # compile counters + the per-device live-buffer rollup. OMITTED
        # when nothing ran on the device plane (and by older peers in a
        # mixed-version cluster) — every consumer renders its absence
        # as "-", never a KeyError.
        try:
            devices = _devstats.stats_snapshot()
            if devices:
                payload["devices"] = devices
        except Exception:   # noqa: BLE001
            pass
        # tenant attribution plane (telemetry/tenants.py): per-tenant
        # serve ledger + budgets + the noisy-neighbor verdict sweep
        # (the pull drives one sweep interval). Process-global like
        # serving ((host, pid) dedupe in the aggregator); OMITTED when
        # no tenant traffic was ever accounted — consumers render its
        # absence as "-", never a KeyError.
        try:
            tenants = _tenants.stats_snapshot()
            if tenants:
                payload["tenants"] = tenants
        except Exception:   # noqa: BLE001
            pass
        # SLO sentinel (telemetry/slo.py): per-objective burn rates,
        # firing state, episode counts, and the named straggler.
        # Process-global (rank 0's sentinel judges the cluster);
        # OMITTED while disarmed — the payload stays additive.
        try:
            slo_block = _slo.stats_snapshot()
            if slo_block:
                payload["slo"] = slo_block
        except Exception:   # noqa: BLE001
            pass
        return payload

    def stats(self, rank: int, timeout: Optional[float] = None) -> Dict:
        """Pull ``rank``'s telemetry snapshot over MSG_STATS (the remote
        dashboard; local rank short-circuits). Raises PSPeerError for a
        dead/unreachable rank like any other request."""
        if rank == self.rank:
            return self.stats_payload()
        fut = self._peer(rank).request(MSG_STATS, {}, ())
        meta, _ = await_reply(
            fut, timeout or config.get_flag("ps_timeout"),
            f"stats from rank {rank}")
        return meta

    def health_payload(self) -> Dict:
        """This rank's compact liveness verdict (the MSG_HEALTH reply
        meta): serve-loop heartbeat age, summed shard apply-queue depth,
        oldest in-flight op age, and the last watchdog verdict. Counter
        reads ONLY — no shard lock, no native crossing: a health probe
        must answer even when the data plane is wedged."""
        with self._handlers_cv:
            shards = list(self._shards.values())
        queue_depth = 0
        for s in shards:
            depth = getattr(s, "queue_depth", None)   # RowShard's lock-
            if callable(depth):                       # free accessor;
                queue_depth += depth()                # KV shards: none
        # ONE in-flight snapshot serves both fields (oldest + count):
        # this path contends the hot-path ring lock and is polled, so it
        # must not copy the table twice per probe
        snap = _flight.RECORDER.inflight_snapshot()
        oldest = (max(snap, key=lambda e: e[2]) if snap else None)
        wd = _watchdog.last_verdict()
        serve_age = _flight.RECORDER.beat_age("serve")
        apply_age = _flight.RECORDER.beat_age("apply")
        return {
            "rank": self.rank, "addr": self.addr,
            # incarnation generation: a respawned shard reports its
            # predecessor's + 1, so operators (mvtop) and the cluster
            # aggregator can tell a restarted rank from a healthy one
            # even after its beacon/tombstone state settles
            "gen": self.generation,
            "ts": round(time.time(), 3),
            # beat ages: PYTHON-plane liveness only. None = that loop
            # never ran (no python-plane traffic yet), a growing number
            # = how long it has been quiet. Probe traffic (PING/STATS/
            # HEALTH) does not refresh them, and neither do natively-
            # served ops (zero-Python path, same rule as tracing) — the
            # "native" flag below tells consumers to discount quiet
            # beats on a native-serving rank rather than read them as a
            # wedge (the in-flight/watchdog fields are plane-agnostic).
            "native": self._native_raw is not None,
            "serve_age_s": (None if serve_age is None
                            else round(serve_age, 3)),
            "apply_age_s": (None if apply_age is None
                            else round(apply_age, 3)),
            "queue_depth": queue_depth,
            "inflight": len(snap),
            "oldest_inflight_s": (round(oldest[2], 3) if oldest else 0.0),
            "oldest_inflight": ({"peer": oldest[0], "msg_id": oldest[1],
                                 "type": oldest[3]} if oldest else None),
            "watchdog": wd,
            # headline verdict: the watchdog's view when it has run, else
            # "ok" (an unwatched plane that answered this RPC is serving)
            "status": wd["status"] if wd.get("checked") else "ok",
        }

    def health(self, rank: int, timeout: Optional[float] = None) -> Dict:
        """Pull ``rank``'s liveness verdict over MSG_HEALTH (local rank
        short-circuits). The probe rides its OWN one-shot connection,
        never the shared data conn: per-conn FIFO would queue it behind
        the very data op that is wedged (and behind this caller's own
        outstanding traffic), turning "alive but stuck" into a 300 s
        timeout — the opposite of a liveness probe. A fresh conn gets a
        fresh handler thread on the Python server (and a fresh C++
        serving thread on the native one), so the answer only requires
        the accept loop to be alive — and the reply wait defaults to
        ps_health_timeout (seconds), not ps_timeout: a fully frozen
        rank accepts the handshake in-kernel and then never answers,
        and the probe must return in triage time, not 5 minutes. Raises
        PSPeerError for a dead/unresponsive rank — which IS the 'not
        serving' answer, typed."""
        return self._oneshot_pull(rank, MSG_HEALTH, timeout)

    def stats_oneshot(self, rank: int,
                      timeout: Optional[float] = None) -> Dict:
        """MSG_STATS over the probe path (own one-shot connection,
        triage-scale timeout) — the cluster aggregator's poll primitive.
        :meth:`stats` rides the shared data conn and is the right call
        for a worker consulting a healthy peer; a periodic cluster poll
        must instead survive exactly the degraded states it exists to
        observe, so it gets the same isolation as MSG_HEALTH: a wedged
        data plane (or this rank's own outstanding traffic) can never
        stall it, and an unanswering rank costs ps_health_timeout, not
        ps_timeout."""
        return self._oneshot_pull(rank, MSG_STATS, timeout)

    def _probe_addr(self, rank: int, timeout: float) -> str:
        """Resolve ``rank``'s address for a one-shot probe, WITHOUT the
        data-plane peer registry's liveness gate: _peer() fails fast
        inside the reconnect-backoff window, which would report a rank
        "dead" during exactly the transient the probe exists to
        classify — and a probe-only caller must not construct a full
        persistent peer (socket + recv thread) just to learn an
        address. A healthy cached peer donates its addr; otherwise the
        rendezvous re-resolves (so a restarted incarnation's fresh
        address is honored)."""
        with self._peers_lock:
            peer = self._peers.get(rank)
        if peer is not None and peer._dead is None:
            return peer.addr
        if self._rendezvous is not None:
            try:
                return self._rendezvous.lookup(
                    rank, min(config.get_flag("ps_connect_timeout"),
                              timeout))
            except PSError:
                if peer is None:
                    raise
                return peer.addr   # dead peer's last known address
        if peer is not None:
            return peer.addr
        raise PSError("no rendezvous configured for remote ranks")

    def _oneshot_pull(self, rank: int, msg_type: int,
                      timeout: Optional[float] = None) -> Dict:
        if rank == self.rank:
            return (self.health_payload() if msg_type == MSG_HEALTH
                    else self.stats_payload())
        timeout = timeout or config.get_flag("ps_health_timeout")
        addr = self._probe_addr(rank, timeout)
        # probe retries ride the shared policy (utils/retry.py) inside
        # ONE overall timeout — deadline propagation: each attempt's
        # socket budget is the REMAINING triage time, so attempts > 1
        # rides out a restarting rank's transient RST without ever
        # holding a supervisor poll past ps_health_timeout
        deadline = _retry.deadline_in(timeout)
        try:
            return _retry.call_with_retries(
                lambda: oneshot_probe(
                    addr, msg_type,
                    max(_retry.remaining_s(deadline, timeout), 0.05),
                    config.get_flag("ps_connect_timeout")),
                attempts=config.get_flag("ps_probe_attempts"),
                deadline=deadline,
                retry_on=(OSError, wire.WireError, TimeoutError),
                backoff=_retry.Backoff(base_s=0.05, cap_s=0.5))
        except (OSError, wire.WireError, TimeoutError) as e:
            raise PSPeerError(
                f"probe (type 0x{msg_type:X}) to rank {rank} at {addr} "
                f"failed: {e}") from e

    # ------------------------- multi-owner super-frames --------------- #
    def _owner_service(self, owner: int) -> "PSService":
        """Resolve a super-frame sub-op's owning service: this rank, or
        a colocated sibling through the process registry (ps/spmd.py).
        A previously-routed owner observed gone raises the typed peer
        error AND fires the death hooks — a super-framed sub-op must
        signal a dead shard exactly like a dying socket would (the
        send-window replay plane re-arms off that hook). An owner that
        was NEVER colocated is a routing error."""
        if owner == self.rank:
            return self
        svc = _spmd.colocated_service(self._proc_key, owner)
        if svc is not None:
            self._routed_seen.add(owner)
            if owner in self._routed_dead:
                # fresh incarnation registered (respawn): clear the
                # tombstone — same rule as _route, or a SECOND death of
                # this rank would never re-fire the hooks
                self._routed_dead.discard(owner)
                with self._peers_lock:
                    self._dead_ranks.pop(owner, None)
            return svc
        if owner in self._routed_seen:
            if owner not in self._routed_dead:
                self._routed_dead.add(owner)
                self._note_death(owner)
            raise PSPeerError(
                f"rank {owner} (in-process route) is down")
        raise PSError(
            f"super-frame sub-op for rank {owner}, which is not "
            f"colocated with rank {self.rank}")

    def multi_local(self, subs: Sequence[Tuple[int, Dict, Sequence]]
                    ) -> List[cf.Future]:
        """In-process super-frame dispatch from PYTHON objects: one
        task on this client's serial executor runs every sub-op across
        the colocated shards (grouped SPMD/np fast paths included) and
        resolves one future per sub — the routed fan-out's hot path,
        with ZERO wire encode/parse on either side (the socket-framed
        MSG_MULTI pays that only when a super-frame actually crosses a
        wire). Ordering: same executor queue as every other routed op,
        so per-(client, owner) FIFO holds."""
        futs: List[cf.Future] = [cf.Future() for _ in subs]
        # INLINE on the caller thread (like every routed dispatch when
        # the fan-out plane is armed): program order IS per-owner FIFO,
        # and an executor hop would cost two thread wakeups per op — on
        # an oversubscribed host the scheduler latency of that
        # ping-pong dominated the op itself (measured: 2 workers at 2
        # shards ran 2x SLOWER than one until dispatch went inline)
        try:
            results = self._handle_multi_obj(subs)
        except Exception as e:   # noqa: BLE001 — transport-level
            for f in futs:
                f.set_exception(e)
            return futs
        for f, (ok, rm, ra) in zip(futs, results):
            if ok:
                f.set_result((rm, ra))
            elif rm.get("peer"):
                # rethrow TYPED: callers branch on PSPeerError (dead
                # owner → retry/failover) vs PSError (fail fast), and a
                # sub-op riding a super-frame must not lose that
                f.set_exception(PSPeerError(rm.get("error", "?")))
            else:
                f.set_exception(PSError(rm.get("error", "?")))
        return futs

    def _handle_multi(self, meta: Dict, arrays: Sequence[np.ndarray]
                      ) -> Tuple[Dict, List[np.ndarray]]:
        """Wire entry for a MSG_MULTI super-frame (socket / native
        punt): unpack the inner frames, run the shared sub-op engine,
        and pack the inner replies (OK or ERR per sub, in order) the
        same way."""
        subs = wire.unpack_batch(arrays)
        results = self._handle_multi_obj(subs)
        blobs = [wire.encode(MSG_REPLY_OK if ok else MSG_REPLY_ERR,
                             i, rm, ra)
                 for i, (ok, rm, ra) in enumerate(results)]
        return {"n": len(subs)}, wire.pack_batch(blobs)

    def _handle_multi_obj(self, subs: Sequence[Tuple[int, Dict,
                                                     Sequence]]
                          ) -> List[Tuple[bool, Dict, Any]]:
        """The super-frame sub-op engine: dispatch every ``(msg_type,
        meta, arrays)`` sub-op to its owning colocated shard and return
        ``(ok, reply_meta, reply_arrays)`` per sub. Plain (unstamped)
        row adds and gets whose target shards share an ACTIVE
        mesh-stacked plane collapse into ONE SPMD dispatch per kind
        (MeshStack.apply_grouped / gather_grouped); host-numpy shards
        python alone serves take a direct lock+apply/gather fast path
        (no coalescing-queue event round trip — this executor thread is
        the one server for the client's routed ops); everything else —
        batch frames, replay-stamped frames, state ops, natively-
        registered shards — dispatches through the shard's ordinary
        handler in frame order. Per-sub failures come back as per-sub
        errors (per-owner independence: sub K failing must not fail sub
        K+1); grouping requires each owner to appear at most once, else
        the whole frame falls back to in-order per-sub dispatch."""
        n = len(subs)
        results: List[Optional[Tuple[bool, Dict, Any]]] = [None] * n
        owners = [int(m.get(wire.OWNER_META_KEY, self.rank))
                  for _mt, m, _a in subs]
        groupable = len(set(owners)) == n
        add_group: List[Tuple[int, Any, Dict, Sequence]] = []
        get_group: List[Tuple[int, Any, Dict, Sequence]] = []
        direct: List[int] = []
        from multiverso_tpu.ps.shard import RowShard as _RowShard
        for i, (mt, m, arrs) in enumerate(subs):
            shard = None
            if groupable and mt in (MSG_ADD_ROWS, MSG_GET_ROWS):
                try:
                    shard = self._owner_service(
                        owners[i])._shards.get(m.get("table"))
                except PSError as e:
                    results[i] = (False, _sub_err(e), [])
                    continue
            plane = getattr(shard, "_plane", None)
            # the np fast path mirrors the plane grouping for
            # host-numpy shards python alone serves: direct lock+apply
            # and pinned gather, skipping the per-request machinery a
            # socket frame needs. Natively-registered shards keep the
            # ordinary handler (its wrapper holds the C++ shard mutex).
            np_fast = (type(shard) is _RowShard and shard._np_mode
                       and shard._native_ref is None)
            if (mt == MSG_ADD_ROWS
                    and wire.REPLAY_CLIENT_KEY not in m
                    and ((plane is not None and plane.active)
                         or np_fast)):
                add_group.append((i, shard, m, arrs))
            elif (mt == MSG_GET_ROWS and not m.get("sparse")
                    and not m.get("chunk")
                    and ((plane is not None and plane.active)
                         or np_fast)):
                get_group.append((i, shard, m, arrs))
            else:
                direct.append(i)
        # one SPMD dispatch for all grouped adds (per-sub validation
        # errors stay per-sub; a dispatch-level failure fails exactly
        # the subs that were in it)
        if add_group:
            entries = []
            for i, shard, m, arrs in add_group:
                try:
                    local, vals, opt = shard._prep_add(m, arrs)
                    entries.append((i, shard, local, vals, opt))
                except Exception as e:  # noqa: BLE001 — per sub
                    results[i] = (False,
                                  _sub_err(e),
                                  [])
            planes: Dict[int, List] = {}
            np_done: List[Tuple[Any, float, int]] = []
            from multiverso_tpu.updaters import \
                STATELESS_LINEAR as _LINEAR
            for ent in entries:
                p = ent[1]._plane
                if p is not None and p.active:
                    planes.setdefault(id(p), []).append(ent)
                    continue
                # np fast path: apply under the shard lock directly —
                # the coalescing queue exists to merge CONCURRENT
                # senders' adds, and the routed plane's callers apply
                # in program order. The lock hold is the MUTATION
                # alone: the telemetry sinks (shared apply monitor,
                # global flightrec ring) have their own locks, and
                # nesting them inside n shard locks per super-frame
                # chained lock convoys across every concurrent worker.
                i, s, l, v, o = ent
                try:
                    sign = _LINEAR[type(s.updater)]
                    t0 = time.perf_counter()
                    with s._lock:
                        data = s._writable_data()
                        if sign > 0:
                            data[l] += v
                        else:
                            data[l] -= v
                        if s._dirty is not None:
                            s._dirty[:, l] = True
                        s._version += 1
                        s._record_wave(1)
                        s._stat_adds += 1
                        s._stat_applies += 1
                    np_done.append((s, (time.perf_counter() - t0) * 1e3,
                                    int(v.nbytes)))
                    results[i] = (True, {}, [])
                except Exception as e:  # noqa: BLE001 — per sub
                    results[i] = (False,
                                  _sub_err(e),
                                  [])
            if np_done:
                # off-lock telemetry: per-shard apply histogram samples
                # plus ONE flight edge + beat for the frame's np waves
                for s, ms, _nb in np_done:
                    s._mon_apply.observe_ms(ms)
                _flight.beat("apply")
                _flight.record(_flight.EV_APPLY,
                               nbytes=sum(nb for _s, _m, nb in np_done),
                               note=f"multi np ops={len(np_done)}")
            for group in planes.values():
                plane = group[0][1]._plane
                try:
                    plane.apply_grouped(
                        [(s, l, v, o) for _i, s, l, v, o in group])
                    for _i, s, _l, _v, _o in group:
                        with s._lock:
                            s._record_wave(1)
                            s._stat_adds += 1
                            s._stat_applies += 1
                    for i, *_rest in group:
                        results[i] = (True, {}, [])
                except Exception as e:  # noqa: BLE001
                    err = _sub_err(e)
                    for i, *_rest in group:
                        results[i] = (False, dict(err), [])
        # grouped gets: ONE SPMD dispatch per stacked plane; np shards
        # serve off the shared pinned-epoch body directly
        if get_group:
            pairs = []
            np_srv_bytes = 0
            np_srv = 0
            for i, shard, m, arrs in get_group:
                p = shard._plane
                if p is None or not p.active:
                    try:
                        if (shard._host_serve
                                and m.get("wire", "none") == "none"):
                            # np fast path: ONE lock hold around the
                            # small gather — the pin/release round trip
                            # costs TWO contended lock handoffs per sub
                            # (sampled: a quarter of the 2-worker wall
                            # sat in _pin_data), and a fan-out part's
                            # gather is tiny
                            s = shard
                            local = s._localize_raw(arrs[0])
                            s._note_rows(local)
                            with s._lock:
                                rows = np.asarray(s._data)[local]
                            s._stat_gets += 1
                            s._stat_get_bytes += int(rows.nbytes)
                            np_srv += 1
                            np_srv_bytes += int(rows.nbytes)
                            results[i] = (True, {}, [rows])
                        else:
                            results[i] = (
                                True, *shard._serve_get_rows(m, arrs))
                    except Exception as e:  # noqa: BLE001 — per sub
                        results[i] = (
                            False,
                            _sub_err(e), [])
                    continue
                try:
                    local = shard._localize_raw(arrs[0])
                    shard._note_rows(local)
                    pairs.append((i, shard, m, local))
                except Exception as e:  # noqa: BLE001 — per sub
                    results[i] = (False,
                                  _sub_err(e),
                                  [])
            if np_srv:
                # ONE flight edge for the frame's np-served gathers
                _flight.record(_flight.EV_GET_SERVE,
                               nbytes=np_srv_bytes,
                               note=f"multi np ops={np_srv}")
            if pairs:
                planes = {}
                for ent in pairs:
                    planes.setdefault(id(ent[1]._plane), []).append(ent)
                for group in planes.values():
                    plane = group[0][1]._plane
                    try:
                        blocks = plane.gather_grouped(
                            [(s, l) for _i, s, _m, l in group])
                        for (i, s, m, l), rows in zip(group, blocks):
                            w = m.get("wire", "none")
                            payload = wire.encode_payload(rows, w)
                            s._stat_gets += 1
                            s._stat_get_bytes += sum(
                                int(a.nbytes) for a in payload)
                            _flight.record(
                                _flight.EV_GET_SERVE,
                                nbytes=l.size * s.num_col
                                * s.dtype.itemsize)
                            results[i] = (True, {}, payload)
                    except Exception as e:  # noqa: BLE001
                        err = _sub_err(e)
                        for i, *_rest in group:
                            results[i] = (False, dict(err), [])
        # everything else: in-order per-sub dispatch through the owning
        # shard's ordinary handler (stamp gates, batch waves, native
        # mutex wrappers all apply exactly as for a direct frame)
        for i in direct:
            mt, m, arrs = subs[i]
            try:
                svc2 = self._owner_service(owners[i])
                handler = svc2._wait_handler(m["table"])
                with monitor(f"ps[{m['table']}].serve"):
                    rmeta, rarrays = handler(mt, m, arrs)
                if isinstance(rarrays, wire.ChunkedReply):
                    raise PSError(
                        "chunk-streamed replies cannot ride a "
                        "super-frame")
                results[i] = (True, rmeta, rarrays)
            except Exception as e:  # noqa: BLE001 — per sub
                results[i] = (False,
                              _sub_err(e), [])
        return results

    def _wait_handler(self, table: str, timeout: float = 20.0) -> Callable:
        # a worker can race ahead of a peer still constructing its tables
        # (the reference serialized this through MV_CreateTable's barrier;
        # the async plane just waits at the server)
        with self._handlers_cv:
            deadline = time.monotonic() + timeout
            while table not in self._handlers:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._handlers_cv.wait(remaining):
                    raise PSError(f"no such table {table!r} on rank "
                                  f"{self.rank} (after {timeout}s)")
            return self._handlers[table]

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # single read: close()'s leak branch clears self._native while
            # this thread may be between the check and the call — a second
            # read here would hand serve_fd a null server
            native = self._native
            if native is not None:
                from multiverso_tpu.ps import native as ps_native
                # hand the fd to a C++ serving thread (detach: the C++
                # side owns it now; close() reaches it via the native
                # server, not self._conns)
                ps_native.serve_fd(native, conn.detach())
                continue
            with self._conns_lock:
                self._conns.append(conn)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             name="ps-conn", daemon=True).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        send_lock = threading.Lock()
        try:
            while not self._closed:
                msg_type, msg_id, meta, arrays = wire.recv(conn)
                # serve-loop heartbeat + request edge for the black box
                # (natively-served ops bypass Python and stay unrecorded,
                # same rule as tracing). Probes neither beat nor hit the
                # ring: see _punt.
                if msg_type not in (MSG_PING, MSG_STATS, MSG_HEALTH):
                    _flight.beat("serve")
                    _flight.record(_flight.EV_RECV, msg_type=msg_type,
                                   msg_id=msg_id)
                if msg_type == MSG_PING:
                    with send_lock:
                        wire.send(conn, MSG_REPLY_OK, msg_id,
                                  {"rank": self.rank})
                    continue
                if msg_type in (MSG_STATS, MSG_HEALTH):  # telemetry pulls
                    try:
                        payload = (self.stats_payload()
                                   if msg_type == MSG_STATS
                                   else self.health_payload())
                    except Exception as e:  # noqa: BLE001
                        with send_lock:
                            wire.send(conn, MSG_REPLY_ERR, msg_id,
                                      {"error": f"{type(e).__name__}: {e}"})
                        continue
                    with send_lock:
                        wire.send(conn, MSG_REPLY_OK, msg_id, payload)
                    continue
                try:
                    # chaos plane (ps/faults.py): slow-serve sleeps
                    # before the handler (a slow RANK, not a slow
                    # wire); drop_reply serves the request but never
                    # answers — an ack lost after the apply, which the
                    # client's replay plane must dedupe on retry
                    drop_reply = False
                    if _faults.PLANE.armed:
                        _slow_s, drop_reply = _faults.PLANE.plan_serve(
                            msg_type, msg_id, rank=self.rank)
                        if _slow_s:
                            time.sleep(_slow_s)
                    tr = (meta.get(wire.TRACE_META_KEY)
                          if _trace.enabled() else None)
                    t0 = time.time() if tr is not None else 0.0
                    # server-side Dashboard visibility (ref MONITOR_BEGIN
                    # around Server::ProcessAdd/Get, src/server.cpp:37-45)
                    if msg_type == MSG_MULTI:
                        # multi-owner super-frame over a real socket:
                        # dispatch across this process's colocated
                        # shards (sub-ops carry their owning rank)
                        with monitor("ps[multi].serve"):
                            rmeta, rarrays = self._handle_multi(
                                meta, arrays)
                    else:
                        handler = self._wait_handler(meta["table"])
                        with monitor(f"ps[{meta['table']}].serve"):
                            rmeta, rarrays = handler(msg_type, meta,
                                                     arrays)
                    if tr is not None:
                        _trace.add_span("ps.serve", t0, time.time(),
                                        trace=tr,
                                        args={"table": meta.get(
                                            "table", "multi"),
                                              "type": msg_type})
                    if isinstance(rarrays, wire.ChunkedReply):
                        # streamed get reply: one MSG_REPLY_CHUNK per
                        # sub-frame as the generator yields (encode of
                        # chunk k+1 overlaps chunk k draining into the
                        # socket), closed by the ordinary OK
                        for cmeta, carrays in rarrays.chunks:
                            if drop_reply:
                                continue   # drain the generator, send
                            with send_lock:  # nothing (injected loss)
                                wire.send(conn, MSG_REPLY_CHUNK, msg_id,
                                          cmeta, carrays)
                            _flight.record(_flight.EV_GET_CHUNK,
                                           msg_type=msg_type,
                                           msg_id=msg_id)
                        rmeta, rarrays = rarrays.meta, ()
                    if not drop_reply:
                        with send_lock:
                            wire.send(conn, MSG_REPLY_OK, msg_id, rmeta,
                                      rarrays)
                        _flight.record(_flight.EV_REPLY,
                                       msg_type=msg_type, msg_id=msg_id)
                except Exception as e:  # reply errors, don't kill the conn
                    log.debug("ps handler error: %s", e)
                    if isinstance(e, MemoryError):
                        # OOM forensics: dump the ledger + device census
                        # through the flight recorder's fault path WHILE
                        # the hoards are still reachable — the one
                        # moment the byte ledger answers "what ate it"
                        _memstats.oom_dump(
                            "MemoryError serving a request")
                    with send_lock:
                        wire.send(conn, MSG_REPLY_ERR, msg_id,
                                  {"error": f"{type(e).__name__}: {e}"})
                    # the ERR reply is a reply edge too (the punt path
                    # records both): without it a handler error reads as
                    # "received, never answered" — a wedged-server
                    # signature — in postmortem timelines
                    _flight.record(_flight.EV_REPLY, msg_type=msg_type,
                                   msg_id=msg_id, note="err")
        except (wire.WireError, OSError):
            pass  # client went away; its shard traffic simply stops
        finally:
            conn.close()
            # drop the registry entry too: one-shot health probes open a
            # conn per poll, and an append-only list would leak a dead
            # socket object per probe for process lifetime (close() only
            # clears the list at teardown)
            with self._conns_lock:
                try:
                    self._conns.remove(conn)
                except ValueError:
                    pass   # already cleared by close()

    # ----------------------------- client side ----------------------- #
    def add_death_hook(self, fn: Callable[[int], None]) -> None:
        """``fn(rank)`` runs when a peer connection is observed dead —
        the PS plane's failure signal, consumable by elastic heartbeats
        (elastic.bind_ps) or any supervisor."""
        self._death_hooks.append(fn)

    def dead_ranks(self) -> List[int]:
        """Ranks whose connection died and has not been re-established."""
        with self._peers_lock:
            return sorted(self._dead_ranks)

    def _note_death(self, rank: int, hooks: bool = True,
                    peer: Optional[_Peer] = None) -> None:
        """``hooks=False`` records the failure for reconnect backoff only:
        a rendezvous-lookup/connect timeout may just mean the rank has not
        STARTED yet — only an established socket dying is a death signal
        worth tombstoning (a supervisor keying restarts off elastic.failed
        must not kill a rank that was never up). ``peer`` identifies the
        reporting incarnation: a LATE callback from a superseded peer
        (e.g. its recv loop dying only when the reconnect path closes the
        stale socket) must not re-tombstone a rank whose fresh connection
        is already healthy — that would make dead_ranks()/quiesce skip a
        live rank forever."""
        with self._peers_lock:
            cur = self._peers.get(rank)
            if (peer is not None and cur is not None and cur is not peer
                    and cur._dead is None):
                return   # stale incarnation reporting after replacement
            self._dead_ranks[rank] = time.monotonic()
        if not hooks:
            return
        for fn in self._death_hooks:
            try:
                fn(rank)
            except Exception as e:   # a hook must never break the plane
                log.error("ps death hook failed for rank %d: %s", rank, e)

    def _peer(self, rank: int) -> _Peer:
        # two-phase: the global lock only guards the dict; the (slow)
        # rendezvous lookup + connect runs under a PER-RANK lock, so a dead
        # rank's connect_timeout cannot stall requests to healthy ranks
        with self._peers_lock:
            peer = self._peers.get(rank)
            if peer is not None and peer._dead is None:
                # belt to the incarnation check in _note_death: a healthy
                # peer proves the rank is alive, so any lingering
                # tombstone is stale
                self._dead_ranks.pop(rank, None)
                return peer
            # known-dead rank (cached dead peer OR a recent failed
            # lookup/connect with nothing cached): fail fast inside the
            # backoff window, else re-resolve below — a RESTARTED rank
            # republished its address, so a fresh rendezvous lookup finds
            # the new incarnation (recovery path)
            last = self._dead_ranks.get(rank)
            if (last is not None and time.monotonic() - last
                    < config.get_flag("ps_reconnect_backoff")):
                raise (peer._dead if peer is not None else PSPeerError(
                    f"rank {rank} unreachable (in reconnect backoff)"))
            if peer is not None:
                del self._peers[rank]
                peer.close()   # release the dead socket fd now, not at GC
            lock = self._peer_locks.setdefault(rank, threading.Lock())
        with lock:
            with self._peers_lock:
                peer = self._peers.get(rank)
                if peer is not None and peer._dead is None:
                    return peer
            if self._rendezvous is None:
                raise PSError("no rendezvous configured for remote ranks")
            try:
                addr = self._rendezvous.lookup(
                    rank, config.get_flag("ps_connect_timeout"))
                peer = _Peer(rank, addr,
                             config.get_flag("ps_connect_timeout"),
                             config.get_flag("ps_timeout"),
                             on_death=lambda p, e, r=rank:
                                 self._note_death(r, peer=p),
                             src=self.rank)
            except PSError:
                # lookup/connect failure: backoff yes, death hooks no —
                # the rank may simply not be up yet
                self._note_death(rank, hooks=False)
                raise
            with self._peers_lock:
                stale = self._peers.get(rank)
                self._peers[rank] = peer
                self._dead_ranks.pop(rank, None)   # fresh incarnation
            if stale is not None:
                stale.close()
            return peer

    # ------------------------- native client side --------------------- #
    def native_enabled(self) -> bool:
        """True when this process can open native client connections (the
        remote end may still be pure-Python — the wire is identical)."""
        if not config.get_flag("ps_native"):
            return False
        from multiverso_tpu.ps import native as ps_native
        return ps_native.available()

    def native_conn(self, rank: int):
        """Native client connection to ``rank`` (NativeConn), creating it
        lazily. Liveness, rendezvous, and reconnect-backoff bookkeeping
        stay with the python :meth:`_peer` (which this piggybacks for the
        address); a native conn observed dead is simply dropped — the next
        op re-resolves through _peer, so a restarted rank's fresh address
        is honored. Raises PSPeerError like _peer."""
        from multiverso_tpu.ps import native as ps_native
        with self._peers_lock:
            c = self._nconns.get(rank)
        if c is not None and not c.dead():
            return c
        addr = self.addr if rank == self.rank else self._peer(rank).addr
        try:
            c2 = ps_native.NativeConn(addr,
                                      config.get_flag("ps_connect_timeout"),
                                      config.get_flag("ps_timeout"))
        except ps_native.NativeConnError as e:
            raise PSPeerError(f"rank {rank}: {e}") from e
        with self._peers_lock:
            old = self._nconns.get(rank)
            if old is not None and not old.dead():
                # lost the race to another thread: use theirs
                c2.close()
                return old
            self._nconns[rank] = c2
        if old is not None:
            old.close()
        return c2

    def native_conn_or_none(self, rank: int):
        """:meth:`native_conn` with unreachable ranks mapped to None (the
        fanout paths turn those into failed futures per owner)."""
        try:
            return self.native_conn(rank)
        except PSError:
            return None

    def drop_native_conn(self, rank: int, conn) -> None:
        """Forget a native conn observed dead (kept: death bookkeeping —
        tombstones, hooks — belongs to the python peer plane, which will
        observe the same failure on its own socket)."""
        with self._peers_lock:
            if self._nconns.get(rank) is conn:
                del self._nconns[rank]
        conn.close()

    def native_conns(self):
        with self._peers_lock:
            return list(self._nconns.values())

    def request(self, rank: int, msg_type: int, meta: Dict,
                arrays: Sequence[np.ndarray] = (),
                meta_b: Optional[bytes] = None,
                chunk_sink: Optional[Callable] = None) -> cf.Future:
        """Uncoordinated request to ``rank``; local rank short-circuits the
        socket but keeps async dispatch order via the local executor.
        ``meta_b`` (wire.pack_meta) lets a fan-out op serialize its meta
        once instead of once per remote peer; the local path always uses
        the dict. ``chunk_sink(meta, arrays)`` consumes the sub-frames of
        a chunk-streamed reply as they land on the peer's recv thread
        (the final OK then carries no payload). NEVER raises: a
        dead/unreachable rank yields a future carrying PSPeerError, so
        fire-and-forget callers stay fire-and-forget and multi-owner ops
        keep their live-shard futures."""
        if rank == self.rank:
            return self._dispatch_inproc(self, msg_type, meta, arrays,
                                         chunk_sink)
        # process-coalesced routing (ps/spmd.py; flag ps_fanout): a
        # COLOCATED rank's request skips the localhost socket and
        # dispatches on this client's serial local executor straight
        # into the owning service's handler — per-(client, owner) FIFO
        # (and with it read-your-writes and every window fence) holds
        # because all of one client's routed ops ride ONE queue. A
        # routed rank observed gone (service closed / not yet
        # respawned) fails fast like a dead peer AND fires the death
        # hooks, so the send-window replay plane re-arms exactly as it
        # would off a dying socket.
        rsvc, rerr = self._route(rank)
        if rerr is not None:
            fut: cf.Future = cf.Future()
            fut.set_exception(rerr)
            return fut
        if rsvc is not None:
            return self._dispatch_inproc(rsvc, msg_type, meta, arrays,
                                         chunk_sink)
        try:
            return self._peer(rank).request(
                msg_type, meta if meta_b is None else meta_b, arrays,
                chunk_sink=chunk_sink)
        except PSError as e:
            fut = cf.Future()
            fut.set_exception(e if isinstance(e, PSPeerError)
                              else PSPeerError(str(e)))
            return fut

    def _route(self, rank: int):
        """Resolve ``rank`` to a live colocated service (or a typed
        fast-fail once a previously-routed rank is observed gone).
        ``(None, None)`` = not routed, use the socket path."""
        if self._proc_key is None or not config.get_flag("ps_fanout"):
            return None, None
        svc = _spmd.colocated_service(self._proc_key, rank)
        if svc is not None:
            self._routed_seen.add(rank)
            if rank in self._routed_dead:
                # fresh incarnation registered (respawn): clear the
                # tombstone so backoff-free routing resumes
                self._routed_dead.discard(rank)
                with self._peers_lock:
                    self._dead_ranks.pop(rank, None)
            return svc, None
        if rank in self._routed_seen:
            err = PSPeerError(
                f"rank {rank} (in-process route) is down")
            if rank not in self._routed_dead:
                self._routed_dead.add(rank)
                self._note_death(rank)
            return None, err
        return None, None

    def _dispatch_inproc(self, svc: "PSService", msg_type: int,
                         meta: Dict, arrays,
                         chunk_sink: Optional[Callable]) -> cf.Future:
        """The local short-circuit, generalized to any colocated
        service. With the fan-out plane armed (flag ``ps_fanout``), the
        dispatch runs INLINE on the caller thread: the caller's program
        order IS per-owner FIFO (stronger than the executor queue), and
        skipping the two thread wakeups per op removes the scheduler
        ping-pong that dominated routed round trips on oversubscribed
        hosts. With the plane off (the classic local-rank path), the
        serial executor keeps the established fire-and-forget timing.
        Multi-owner super-frames dispatch through the target's
        :meth:`_handle_multi`."""
        fut: cf.Future = cf.Future()

        def _run():
            try:
                if msg_type == MSG_MULTI:
                    rmeta, rarrays = svc._handle_multi(meta, arrays)
                else:
                    handler = svc._wait_handler(meta["table"])
                    rmeta, rarrays = handler(msg_type, meta, arrays)
                if isinstance(rarrays, wire.ChunkedReply):
                    # in-process dispatch: drive the sink inline (no
                    # socket to overlap, but the caller's scatter
                    # contract holds); clients normally skip the
                    # chunk request for in-process ranks entirely
                    if chunk_sink is None:
                        raise PSError(
                            "chunked reply without a chunk sink on "
                            "the local path")
                    for cmeta, carrays in rarrays.chunks:
                        chunk_sink(cmeta, carrays)
                    rmeta, rarrays = rarrays.meta, []
                fut.set_result((rmeta, rarrays))
            except Exception as e:
                fut.set_exception(e)

        if config.get_flag("ps_fanout"):
            _run()
        else:
            self._local_exec.submit(_run)
        return fut

    def ping(self, rank: int, timeout: Optional[float] = None) -> bool:
        if rank == self.rank:
            return True
        try:
            self._peer(rank).request(MSG_PING, {}, ()).result(
                timeout or config.get_flag("ps_timeout"))
            return True
        except (PSError, cf.TimeoutError):
            return False

    def close(self) -> None:
        # the cluster aggregator polls THROUGH this service: stop it
        # (final short-timeout poll included) while the probe path is
        # still alive — afterwards a poll would just record every rank
        # unreachable. The shard checkpointer stops with a FINAL save
        # while the shards are intact: a cleanly-closing rank's tail of
        # applies must stay durable for whoever inherits its rows.
        _aggregator.stop_if_bound(self)
        _failover.stop_if_bound(self)
        self._closed = True
        # mesh data plane (ps/spmd.py): leave the routing registry (so
        # colocated clients observe this rank's death like a dead
        # socket) and evict this service's shards from their stacked
        # groups — they keep working standalone for the failover
        # checkpointer's final save below
        _spmd.release_service(self)
        # shutdown, not just close: close() does NOT wake a thread blocked
        # in accept() on Linux — shutdown() makes accept return EINVAL
        # immediately (close alone left the join below eating its timeout
        # on every service teardown)
        try:
            self._listener.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._listener.close()
        except OSError:
            pass
        # the accept thread must be DONE before the native server is
        # freed: it could otherwise adopt a last-instant connection into
        # freed memory
        if self._accept_thread.is_alive():
            self._accept_thread.join(timeout=10.0)
        if self._accept_thread.is_alive():
            # A wedged accept thread could still call serve_fd into the
            # native server; freeing it now would be a use-after-free.
            # Leak the native server instead (process is tearing down or
            # the test harness will kill it) and log loudly.
            log.error("ps service close: accept thread did not exit in "
                      "10s; leaking native server instead of freeing it")
            with self._native_lock:
                self._native = None
            # NOT clearing _native_cb: the leaked server's C++ threads
            # still hold the ctypes trampoline — freeing it under them
            # (by dropping the last reference) would be the same
            # use-after-free this branch exists to avoid.
        # drop accepted connections too, so an in-process "killed" service
        # actually goes silent (a killed OS process gets this for free)
        with self._conns_lock:
            for conn in self._conns:
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                conn.close()
            self._conns.clear()
        with self._native_lock:
            native, self._native = self._native, None
        if native is not None:
            from multiverso_tpu.ps import native as ps_native
            # joins the C++ serving threads (any in-flight punt callback
            # finishes first — ctypes released the GIL for this call)
            ps_native.server_free(native)
            self._native_cb = None
        with self._peers_lock:
            nconns, self._nconns = list(self._nconns.values()), {}
            for peer in self._peers.values():
                peer.close()
            self._peers.clear()
        for c in nconns:
            c.close()
        self._local_exec.shutdown(wait=True)


# ---------------------------------------------------------------------- #
# default per-process context
# ---------------------------------------------------------------------- #
class PSContext:
    """Bundle of (rank, world, service) used by the async tables. Built
    from the JAX multi-controller topology by default; tests construct
    standalone contexts to simulate N ranks in-process."""

    def __init__(self, rank: int, world: int, service: PSService):
        self.rank, self.world, self.service = rank, world, service

    def quiesce(self) -> None:
        """Shutdown handshake (the reference's MV_ShutDown barrier,
        src/zoo.cpp:103-115): mark this rank done through the rendezvous
        and keep serving until every live peer is done too — a fast rank's
        teardown must not kill peers still pulling from its shard.
        Observed-dead ranks are skipped; timing out proceeds with a
        warning (an unobserved crash must not wedge shutdown forever)."""
        rdv = self.service._rendezvous
        if self.world <= 1 or rdv is None or not hasattr(rdv, "mark"):
            return
        # reserved tag (must not collide with user/harness markers in the
        # same rendezvous dir — utils/filesync.file_barrier writes
        # "<tag>.<rank>" files there too); the marker VALUE is this
        # incarnation's published address, so a reused rendezvous dir's
        # stale markers never satisfy the current run's barrier
        rdv.mark(self.rank, "ps_quiesce", self.service.addr)
        deadline = time.monotonic() + config.get_flag("ps_shutdown_grace")
        for r in range(self.world):
            if r == self.rank or r in self.service.dead_ranks():
                continue
            remaining = deadline - time.monotonic()
            try:
                expect = rdv.lookup(r, min(max(remaining, 0.001), 5.0))
            except PSError:
                continue   # never published: the rank never came up
            if remaining <= 0 or not rdv.wait_mark(
                    r, "ps_quiesce", remaining, expect=expect):
                # keep waiting on the REMAINING ranks — one laggard (or a
                # transient KV error reading its marker) must not collapse
                # the barrier for everyone after it
                log.error("ps shutdown: rank %d did not reach shutdown "
                          "within ps_shutdown_grace; not waiting for it", r)

    def close(self, quiesce: bool = False) -> None:
        if quiesce:
            try:
                self.quiesce()
            except Exception as e:
                # the handshake is best-effort: a vanished rendezvous dir
                # or dead coordinator must not abort shutdown and leak the
                # service's sockets/threads
                log.error("ps shutdown quiesce failed (%s: %s); closing "
                          "anyway", type(e).__name__, e)
        # final telemetry flush BEFORE the service dies: the last metrics
        # record and any buffered trace spans must survive a short run.
        # export_global, NOT stop_global: a process may hold several
        # contexts (test fixtures, bench workers) and one closing must
        # not kill the exporter for the rest — the global exporter stops
        # at Zoo.stop (app teardown) or with the process.
        try:
            _exporter.export_global()
            d = config.get_flag("metrics_dir")
            if d:
                _trace.dump_to(d)
        except Exception as e:  # noqa: BLE001 — telemetry never blocks
            log.error("telemetry flush at close failed: %s", e)  # shutdown
        self.service.close()


_default_ctx: Optional[PSContext] = None
_default_lock = threading.Lock()


def default_context() -> PSContext:
    global _default_ctx
    with _default_lock:
        if _default_ctx is None:
            world = config.get_flag("ps_world")
            rank = config.get_flag("ps_rank")
            if world <= 0:
                import jax
                rank, world = jax.process_index(), jax.process_count()
            elif rank < 0:
                raise PSError("ps_world set but ps_rank is not")
            rdv = None
            if world > 1:
                rdv_dir = config.get_flag("ps_rendezvous")
                rdv = (FileRendezvous(rdv_dir) if rdv_dir
                       else JaxRendezvous())
            _default_ctx = PSContext(
                rank, world, PSService(rank, world, rdv))
        return _default_ctx


def reset_default_context() -> None:
    global _default_ctx
    with _default_lock:
        if _default_ctx is not None:
            # the default (app-flow) context quiesces: every rank got here
            # via mv.shutdown, so the handshake converges quickly; test
            # fixtures closing explicit contexts sequentially skip it
            _default_ctx.close(quiesce=True)
            _default_ctx = None
