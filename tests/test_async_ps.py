"""Uncoordinated async PS: wire, shards, client tables, failure semantics.

Single-process tier: two standalone PSService instances stand in for two
ranks, talking over real localhost sockets (the reference exercised its
Worker/Server actors the same way before mpirun, Test/main.cpp). The
multi-process tier lives in test_multiprocess_async.py.
"""

import threading
import time

import numpy as np
import pytest

from multiverso_tpu.ps import wire
from multiverso_tpu.ps.service import (FileRendezvous, PSContext, PSPeerError,
                                       PSService)
from multiverso_tpu.ps.tables import (AsyncArrayTable, AsyncKVTable,
                                      AsyncMatrixTable,
                                      AsyncSparseMatrixTable)
from multiverso_tpu.updaters import AdaGradUpdater, AddOption


# the shared two_ranks fixture lives in conftest.py (used here and by the
# async-plane LDA test)


class TestWire:
    def test_roundtrip_via_socket(self):
        import socket
        a, b = socket.socketpair()
        meta = {"table": "t", "opt": {"worker_id": 3}}
        arrays = [np.arange(6, dtype=np.float32).reshape(2, 3),
                  np.array(7, dtype=np.int64),
                  np.zeros(0, dtype=np.float64)]
        wire.send(a, 0x11, 42, meta, arrays)
        msg_type, msg_id, meta2, arrays2 = wire.recv(b)
        assert (msg_type, msg_id, meta2) == (0x11, 42, meta)
        for x, y in zip(arrays, arrays2):
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(x, y)
        a.close(), b.close()

    def test_negative_dim_rejected(self):
        """A frame claiming a negative shape dim must raise WireError —
        np.frombuffer would read count=-1 as 'the rest of the buffer' and
        the cursor would walk backwards."""
        import socket
        import struct as st
        a, b = socket.socketpair()
        payload = (b"{}" + st.pack("<B", 3) + b"<i8" + st.pack("<B", 1)
                   + st.pack("<q", -1) + bytes(24))
        a.sendall(wire._HEADER.pack(wire.MAGIC, 0x11, 0, 1, 2, 1,
                                    len(payload)) + payload)
        with pytest.raises(wire.WireError, match="negative dim"):
            wire.recv(b)
        a.close(), b.close()

    def test_corrupt_meta_json_is_wire_error(self):
        """Garbage meta bytes must surface as WireError, not leak
        json.JSONDecodeError — the native plane's punt path keys its
        fail-fast ERR reply on WireError (review finding: corrupt JSON,
        the likeliest malformed body, used to bypass it and park the
        peer for the full ps_timeout)."""
        bad_meta = b"{not json"
        frame = wire._HEADER.pack(wire.MAGIC, 0x11, 0, 7, len(bad_meta),
                                  0, len(bad_meta)) + bad_meta
        with pytest.raises(wire.WireError, match="meta json"):
            wire.parse_frame(frame)
        assert wire.peek_msg_id(frame) == 7  # ERR reply stays bindable

    def test_bad_magic_raises(self):
        import socket
        a, b = socket.socketpair()
        a.sendall(b"XXXX" + bytes(wire._HEADER.size - 4))
        with pytest.raises(wire.WireError):
            wire.recv(b)
        a.close(), b.close()

    def test_service_survives_garbage_connections(self, two_ranks):
        """A network-facing server must shrug off malformed frames: random
        bytes, truncated frames, oversized length fields — the offending
        connection dies, the service keeps serving real clients."""
        import socket

        t0 = AsyncMatrixTable(10, 2, name="g", ctx=two_ranks[0])
        AsyncMatrixTable(10, 2, name="g", ctx=two_ranks[1])
        host, port = two_ranks[1].service.addr.rsplit(":", 1)
        rng = np.random.default_rng(0)
        for payload in (
                rng.integers(0, 256, 64, dtype=np.uint8).tobytes(),
                b"MVPS" + bytes(4),                       # truncated header
                wire.encode(0x11, 1, {"table": "g"})[:10],  # cut mid-frame
                # huge meta length field: must be rejected, not allocated
                wire._HEADER.pack(wire.MAGIC, 0x11, 0, 1,
                                  wire.MAX_META + 1, 0, wire.MAX_META + 1),
                # huge/negative frame length: rejected before allocation
                wire._HEADER.pack(wire.MAGIC, 0x11, 0, 1, 4, 0,
                                  wire.MAX_FRAME + 1),
                wire._HEADER.pack(wire.MAGIC, 0x11, 0, 1, 4, 0, -8),
        ):
            s = socket.create_connection((host, int(port)), timeout=5)
            s.sendall(payload)
            s.close()
        time.sleep(0.2)
        # the real client plane is unaffected
        t0.add_rows([9], np.ones((1, 2), np.float32))
        np.testing.assert_allclose(t0.get_rows([9])[0], 1.0)


class TestAsyncMatrixTable:
    def test_different_row_sets_per_worker(self, two_ranks):
        """THE capability the sync plane lacks (ref worker.cpp:30-76 +
        server.cpp:36-58): each worker pushes its OWN row set, no
        coordination, and the global state converges to the sum."""
        t0 = AsyncMatrixTable(10, 4, name="m", ctx=two_ranks[0])
        t1 = AsyncMatrixTable(10, 4, name="m", ctx=two_ranks[1])
        # rows 0-4 owned by rank 0, rows 5-9 by rank 1
        t0.add_rows([0, 7], np.full((2, 4), 1.0, np.float32))
        t1.add_rows([3, 7, 9], np.full((3, 4), 2.0, np.float32))
        t1.add_rows([7], np.full((1, 4), 0.5, np.float32))
        got = t0.get_rows([0, 3, 7, 9])
        np.testing.assert_allclose(got[0], 1.0)
        np.testing.assert_allclose(got[1], 2.0)
        np.testing.assert_allclose(got[2], 3.5)   # 1 + 2 + 0.5
        np.testing.assert_allclose(got[3], 2.0)
        # the other worker sees the same state (server truth, not caches)
        np.testing.assert_allclose(t1.get_rows([7])[0], 3.5)

    def test_uncoordinated_rates(self, two_ranks):
        """Workers at wildly different rates; nobody waits for anybody
        (no collective): total = sum of all pushes."""
        t0 = AsyncMatrixTable(8, 2, name="r", ctx=two_ranks[0])
        t1 = AsyncMatrixTable(8, 2, name="r", ctx=two_ranks[1])

        def fast():
            for _ in range(50):
                t0.add_rows([1, 6], np.ones((2, 2), np.float32))

        def slow():
            for _ in range(5):
                t1.add_rows([1], np.ones((1, 2), np.float32))
                time.sleep(0.01)

        th = [threading.Thread(target=fast), threading.Thread(target=slow)]
        [x.start() for x in th]
        [x.join() for x in th]
        t0.flush(), t1.flush()
        got = t0.get_rows([1, 6])
        np.testing.assert_allclose(got[0], 55.0)   # 50 + 5
        np.testing.assert_allclose(got[1], 50.0)

    def test_async_msg_ids_and_wait(self, two_ranks):
        t0 = AsyncMatrixTable(6, 3, name="w", ctx=two_ranks[0])
        AsyncMatrixTable(6, 3, name="w", ctx=two_ranks[1])
        mids = [t0.add_rows_async([i % 6], np.ones((1, 3), np.float32))
                for i in range(7)]
        gid = t0.get_rows_async([0, 1, 2, 3, 4, 5])
        for m in mids:
            t0.wait(m)
        rows = t0.wait(gid)
        assert rows.shape == (6, 3)
        # re-waiting a consumed id returns None (ref Waiter semantics)
        assert t0.wait(mids[0]) is None

    def test_duplicates_and_order(self, two_ranks):
        t0 = AsyncMatrixTable(10, 2, name="d", ctx=two_ranks[0])
        AsyncMatrixTable(10, 2, name="d", ctx=two_ranks[1])
        # duplicate ids in one add accumulate (ref per-row accumulation)
        t0.add_rows([8, 2, 8], np.ones((3, 2), np.float32))
        got = t0.get_rows([8, 2, 8, 2])
        np.testing.assert_allclose(got[0], 2.0)
        np.testing.assert_allclose(got[1], 1.0)
        np.testing.assert_allclose(got[2], 2.0)   # original order preserved

    def test_whole_table_and_array(self, two_ranks):
        t0 = AsyncMatrixTable(7, 3, name="f", ctx=two_ranks[0])
        t1 = AsyncMatrixTable(7, 3, name="f", ctx=two_ranks[1])
        t0.add(np.ones((7, 3), np.float32))
        t1.add(2 * np.ones((7, 3), np.float32))
        np.testing.assert_allclose(t1.get(), 3.0)

        a0 = AsyncArrayTable(9, name="arr", ctx=two_ranks[0])
        a1 = AsyncArrayTable(9, name="arr", ctx=two_ranks[1])
        a0.add(np.arange(9, dtype=np.float32))
        a1.add(np.arange(9, dtype=np.float32))
        np.testing.assert_allclose(a0.get(), 2 * np.arange(9))

    def test_per_worker_adagrad_state(self, two_ranks):
        """ref adagrad_updater.h:19 — per-worker historic g² on the server,
        keyed by the AddOption worker_id each worker sends."""
        ts = [AsyncMatrixTable(
                  4, 2, name="ag",
                  updater=AdaGradUpdater(num_workers=2, per_worker=True),
                  ctx=two_ranks[r]) for r in range(2)]
        opt = dict(learning_rate=1.0, rho=1.0)
        ts[0].add_rows([0], np.ones((1, 2), np.float32),
                       AddOption(worker_id=0, **opt))
        before = ts[0].get_rows([0])[0].copy()
        # worker 1's first add must use ITS OWN fresh g² (not worker 0's)
        ts[1].add_rows([0], np.ones((1, 2), np.float32),
                       AddOption(worker_id=1, **opt))
        after = ts[1].get_rows([0])[0]
        # both first-adds step by the same magnitude (fresh g² each):
        # w0: 0 - 1*1/(sqrt(1)+eps) = -1 ; w1: -1 - 1 = -2
        np.testing.assert_allclose(before, -1.0, rtol=1e-5)
        np.testing.assert_allclose(after, -2.0, rtol=1e-5)

    def test_random_init_consistent_across_clients(self, two_ranks):
        t0 = AsyncMatrixTable(10, 4, name="ri", seed=3, init_scale=0.5,
                              ctx=two_ranks[0])
        t1 = AsyncMatrixTable(10, 4, name="ri", seed=3, init_scale=0.5,
                              ctx=two_ranks[1])
        a, b = t0.get(), t1.get()
        np.testing.assert_array_equal(a, b)
        assert np.abs(a).max() <= 0.5 and np.abs(a).std() > 0

    def test_set_rows_and_store_load(self, two_ranks, tmp_path):
        t0 = AsyncMatrixTable(6, 2, name="ck", ctx=two_ranks[0])
        AsyncMatrixTable(6, 2, name="ck", ctx=two_ranks[1])
        t0.set_rows([5, 1], np.array([[5, 5], [1, 1]], np.float32))
        np.testing.assert_allclose(t0.get_row(5), 5.0)
        np.testing.assert_allclose(t0.get_row(1), 1.0)
        with open(tmp_path / "ck.npy", "wb") as f:
            t0.store(f)
        t0.add(np.ones((6, 2), np.float32))
        with open(tmp_path / "ck.npy", "rb") as f:
            t0.load(f)
        np.testing.assert_allclose(t0.get_row(5), 5.0)

    def test_errors_are_typed(self, two_ranks):
        t0 = AsyncMatrixTable(5, 2, name="e", ctx=two_ranks[0])
        with pytest.raises(IndexError):
            t0.add_rows([5], np.ones((1, 2), np.float32))
        with pytest.raises(TypeError):
            t0.get_rows([0.5])
        with pytest.raises(ValueError):
            t0.get_rows([])


class TestCoalescing:
    """Server-side request coalescing (ps_coalesce): concurrent adds to a
    shard merge into batched jitted updates, with per-message results
    identical to sequential application for linear updaters — the server-
    side scaling fix the reference never had (its server applied strictly
    per-message, src/server.cpp:36-58)."""

    def _shard(self, n=32, cols=4, updater=None, num_workers=0):
        from multiverso_tpu.ps.shard import RowShard
        from multiverso_tpu.updaters import Updater
        return RowShard(0, n, cols, np.float32, updater or Updater(),
                        "coal", num_workers=num_workers)

    @staticmethod
    def _block_applier_and_queue(shard, requests):
        """Deterministic merge setup: while holding the shard lock, start a
        zero-delta dummy add (it becomes the applier and blocks on the
        lock), then start ``requests``, which all queue behind it. On lock
        release the dummy applies alone and the rest drain as one batch."""
        import multiverso_tpu.ps.service as svc
        cols = shard.num_col
        zero = np.zeros((1, cols), np.float32)
        threads = []
        with shard._lock:
            dummy = threading.Thread(
                target=shard.handle,
                args=(svc.MSG_ADD_ROWS, {"table": shard.name},
                      [np.array([0]), zero]))
            dummy.start()
            threads.append(dummy)
            deadline = time.monotonic() + 5
            # the dummy is draining (popped its own entry) once the flag is
            # up and the queue is empty again
            while ((not shard._addq_draining or shard._addq)
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            for meta, arrays in requests:
                t = threading.Thread(target=shard.handle,
                                     args=(svc.MSG_ADD_ROWS, meta, arrays))
                t.start()
                threads.append(t)
            while (len(shard._addq) < len(requests)
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert len(shard._addq) == len(requests)
        for t in threads:
            t.join(timeout=10)

    def test_queued_adds_merge_into_one_update(self):
        """Adds queued behind a blocked applier must apply as ONE merged
        update, summing exactly."""
        shard = self._shard()
        ids = np.arange(8)
        one = np.ones((8, 4), np.float32)
        self._block_applier_and_queue(
            shard, [({"table": "coal"}, [ids, one]) for _ in range(6)])
        assert shard.stat_adds == 7             # dummy + 6
        assert shard.stat_applies == 2          # dummy + one merged batch
        got = np.asarray(shard._data)[:8]
        np.testing.assert_allclose(got, 6 * one)    # sum is exact
        assert shard._dirty is None

    def test_cross_worker_adds_merge_for_stateless_updaters(self):
        """The client default opt stamps worker_id=rank; stateless
        updaters ignore opt, so adds from DIFFERENT workers must still
        merge into one update — the cross-worker case coalescing exists
        for."""
        shard = self._shard()
        ids = np.arange(8)
        one = np.ones((8, 4), np.float32)
        self._block_applier_and_queue(
            shard,
            [({"table": "coal", "opt": {"worker_id": w}}, [ids, one])
             for w in range(6)])
        assert shard.stat_applies == 2      # dummy + ONE merged batch
        np.testing.assert_allclose(np.asarray(shard._data)[:8], 6 * one)

    def test_distinct_opts_stay_separate_updates(self):
        """Per-worker AdaGrad state keys on opt.worker_id — merged applies
        must group by opt so each worker's g² accumulates its own deltas."""
        from multiverso_tpu.updaters import AdaGradUpdater
        shard = self._shard(updater=AdaGradUpdater(num_workers=2,
                                                   per_worker=True))
        ids = np.arange(4)
        one = np.ones((4, 4), np.float32)
        self._block_applier_and_queue(
            shard,
            [({"table": "coal", "opt": {"worker_id": wid,
                                        "learning_rate": 1.0}}, [ids, one])
             for wid in (0, 0, 1)])
        g2 = np.asarray(shard._ustate["g_sqr"])
        # worker 0's two adds merged (delta 2 -> g2 += 4), worker 1's one
        # add stayed its own group (g2 += 1): buffers stayed per-worker
        np.testing.assert_allclose(g2[0, :4], 4.0)
        np.testing.assert_allclose(g2[1, :4], 1.0)

    def test_disabled_flag_applies_per_message(self):
        from multiverso_tpu.utils import config
        import multiverso_tpu.ps.service as svc
        config.set_flag("ps_coalesce", False)
        shard = self._shard()
        ids = np.arange(4)
        one = np.ones((4, 4), np.float32)
        for _ in range(3):
            shard.handle(svc.MSG_ADD_ROWS, {"table": "coal"}, [ids, one])
        assert shard.stat_adds == shard.stat_applies == 3
        np.testing.assert_allclose(np.asarray(shard._data)[:4], 3 * one)

    def test_concurrent_hammer_sums_exactly(self, two_ranks):
        """End-to-end over the sockets: many client threads adding random
        disjoint-and-overlapping batches; the grand total must be exact
        (linear updater) — coalescing must never drop or double a delta."""
        t0 = AsyncMatrixTable(64, 8, name="hammer", ctx=two_ranks[0])
        t1 = AsyncMatrixTable(64, 8, name="hammer", ctx=two_ranks[1])
        rng = np.random.default_rng(7)
        batches = [(rng.choice(64, size=16, replace=False),
                    rng.integers(-3, 4, size=(16, 8)).astype(np.float32))
                   for _ in range(24)]
        expect = np.zeros((64, 8), np.float32)
        for ids, vals in batches:
            np.add.at(expect, ids, vals)

        def work(table, chunk):
            for ids, vals in chunk:
                table.add_rows(ids, vals)

        threads = [threading.Thread(target=work,
                                    args=(t, batches[i::4]))
                   for i, t in enumerate([t0, t1, t0, t1])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        np.testing.assert_allclose(t0.get_rows(np.arange(64)), expect,
                                   rtol=1e-5, atol=1e-5)

    def test_hash_shard_coalesces_outside_lock(self, two_ranks):
        """AsyncSparseKVTable adds (HashShard) ride the same queue; key->
        slot translation must not deadlock against a blocked applier."""
        from multiverso_tpu.ps.tables import AsyncSparseKVTable
        t0 = AsyncSparseKVTable(4, name="kvcoal", updater="default",
                                ctx=two_ranks[0])
        AsyncSparseKVTable(4, name="kvcoal", updater="default",
                           ctx=two_ranks[1])
        keys = np.array([5, 1000003, 17, 2**40 + 3])
        one = np.ones((4, 4), np.float32)
        threads = [threading.Thread(target=t0.add_rows, args=(keys, one))
                   for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        np.testing.assert_allclose(t0.get_rows(keys), 8 * one)


class TestWireBf16:
    def test_bf16_wire_roundtrip(self, two_ranks):
        """wire="bf16" halves the TCP payload both directions (the role
        the reference's filters played on its MPI wire); values come back
        in table dtype with bf16 precision."""
        t0 = AsyncMatrixTable(10, 4, name="wb", wire="bf16",
                              ctx=two_ranks[0])
        t1 = AsyncMatrixTable(10, 4, name="wb", wire="bf16",
                              ctx=two_ranks[1])
        rng = np.random.default_rng(0)
        vals = rng.normal(size=(4, 4)).astype(np.float32)
        t0.add_rows([0, 3, 7, 9], vals)       # spans both shards
        got = t1.get_rows([0, 3, 7, 9])
        assert got.dtype == np.float32
        np.testing.assert_allclose(got, vals, rtol=2e-2, atol=2e-2)
        t0.add(np.ones((10, 4), np.float32))  # full-table path too
        np.testing.assert_allclose(t0.get()[1], 1.0, rtol=2e-2)

    def test_unknown_wire_raises(self, two_ranks):
        for mode in ("zstd", "1bit", "topk"):
            with pytest.raises(ValueError):
                AsyncMatrixTable(4, 2, name="wx", wire=mode,
                                 ctx=two_ranks[0])

    def test_store_keeps_full_precision_despite_wire(self, two_ranks,
                                                     tmp_path):
        """Checkpoints are durable state: store() must bypass the bf16
        wire (values below bf16 resolution survive a save round-trip)."""
        t0 = AsyncMatrixTable(6, 2, name="ws", wire="bf16",
                              ctx=two_ranks[0])
        AsyncMatrixTable(6, 2, name="ws", wire="bf16", ctx=two_ranks[1])
        exact = np.full((6, 2), 1.0009765625, np.float32)  # not bf16-exact
        t0.set_rows(np.arange(6), exact)                   # exact path in
        with open(tmp_path / "ws.npy", "wb") as f:
            t0.store(f)
        saved = np.load(tmp_path / "ws.npy")
        np.testing.assert_array_equal(saved, exact)        # bit-exact
        assert t0._wire == "bf16"                          # mode restored


class TestLocalDeviceSharding:
    def test_shard_spans_local_devices(self, two_ranks):
        """On a multi-chip host the owned row range itself shards over the
        local devices (device-level partition composing with the
        process-level one) — here the 8-device CPU mesh stands in for an
        8-chip host."""
        import jax

        from multiverso_tpu.utils import config
        config.set_flag("ps_local_shard_min_mb", 0.0)  # force for tiny table
        t0 = AsyncMatrixTable(64, 8, name="lds", ctx=two_ranks[0])
        AsyncMatrixTable(64, 8, name="lds", ctx=two_ranks[1])
        ndev = len(jax.local_devices())
        if ndev == 1:
            pytest.skip("single local device")
        data = t0.raw()
        assert len(data.sharding.device_set) == ndev
        # padded row count divides evenly over the device axis
        assert data.shape[0] % ndev == 0
        # ops stay correct over the sharded storage
        t0.add_rows([0, 40], np.ones((2, 8), np.float32))
        got = t0.get_rows([0, 40, 63])
        np.testing.assert_allclose(got[0], 1.0)
        np.testing.assert_allclose(got[2], 0.0)


class TestAsyncSparse:
    """Stale-row protocol on the uncoordinated plane (ref matrix.cpp
    :432-572: the reference async server's sparse mode)."""

    def test_stale_only_transfer(self, two_ranks):
        ts = [AsyncSparseMatrixTable(10, 4, name="sp", num_workers=2,
                                     ctx=two_ranks[r]) for r in range(2)]
        ids = np.arange(10)
        # first pull: everything is stale -> all 10 rows cross the wire
        rows = ts[0].get_rows_sparse(ids, worker_id=0)
        assert ts[0].last_transfer_rows == 10
        np.testing.assert_allclose(rows, 0.0)
        # nothing changed: second pull transfers NOTHING
        rows = ts[0].get_rows_sparse(ids, worker_id=0)
        assert ts[0].last_transfer_rows == 0
        # worker 1 (via the other client) is tracked independently
        rows1 = ts[1].get_rows_sparse(ids, worker_id=1)
        assert ts[1].last_transfer_rows == 10
        # a remote add dirties exactly its rows for worker 0
        ts[1].add_rows([2, 7], np.ones((2, 4), np.float32))
        rows = ts[0].get_rows_sparse(ids, worker_id=0)
        assert ts[0].last_transfer_rows == 2
        np.testing.assert_allclose(rows[2], 1.0)
        np.testing.assert_allclose(rows[7], 1.0)
        np.testing.assert_allclose(rows[3], 0.0)

    def test_sparse_needs_num_workers(self, two_ranks):
        t = AsyncMatrixTable(6, 2, name="nosp", ctx=two_ranks[0])
        AsyncMatrixTable(6, 2, name="nosp", ctx=two_ranks[1])
        from multiverso_tpu.ps import service as svc
        with pytest.raises(svc.PSError, match="num_workers"):
            # plain table has no dirty bits; typed error end-to-end
            t.ctx.service.request(
                0, svc.MSG_GET_ROWS, {"table": "nosp", "sparse": True,
                                      "worker_id": 0},
                [np.array([0], np.int64)]).result(timeout=10)


class TestCreateTableParity:
    def test_options_via_create_table(self):
        """Async tables ride the same MV_CreateTable option surface as the
        collective tables (single-process default context)."""
        import multiverso_tpu as mv
        mv.init()
        try:
            from multiverso_tpu.ps import (AsyncArrayTableOption,
                                           AsyncMatrixTableOption)
            t = mv.create_table(AsyncMatrixTableOption(6, 3), name="opt_m")
            t.add_rows([1], np.ones((1, 3), np.float32))
            np.testing.assert_allclose(t.get_row(1), 1.0)
            a = mv.create_table(AsyncArrayTableOption(8), name="opt_a")
            a.add(np.arange(8, dtype=np.float32))
            np.testing.assert_allclose(a.get(), np.arange(8))
        finally:
            mv.shutdown()


class TestAsyncKV:
    def test_hash_sharded_aggregated_get(self, two_ranks):
        k0 = AsyncKVTable(name="kv", ctx=two_ranks[0])
        k1 = AsyncKVTable(name="kv", ctx=two_ranks[1])
        k0.add([0, 1, 2], [1.0, 1.0, 1.0])
        k1.add([1, 2, 3], [2.0, 2.0, 2.0])
        # uncoordinated aggregated read — no collective, either side
        assert k0.get() == {0: 1.0, 1: 3.0, 2: 3.0, 3: 2.0}
        assert k1.get([1, 9]) == {1: 3.0, 9: 0}
        assert k0[2] == 3.0

    def test_duplicate_request_keys_not_double_counted(self, two_ranks):
        k0 = AsyncKVTable(name="kvd", ctx=two_ranks[0])
        AsyncKVTable(name="kvd", ctx=two_ranks[1])
        k0.add([5], [2.0])
        assert k0.get([5, 5, 5]) == {5: 2.0}


class TestFailureSemantics:
    def test_idle_connection_survives_timeout(self, tmp_path):
        """A healthy-but-quiet peer must not be declared dead: the io
        timeout bounds blocked replies, not connection lifetime."""
        from multiverso_tpu.utils import config
        config.set_flag("ps_timeout", 1.0)
        rdv = FileRendezvous(str(tmp_path / "rdv"))
        ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
        try:
            t0 = AsyncMatrixTable(10, 2, name="idle", ctx=ctxs[0])
            AsyncMatrixTable(10, 2, name="idle", ctx=ctxs[1])
            t0.add_rows([9], np.ones((1, 2), np.float32))  # open the conn
            time.sleep(2.5)                                # > ps_timeout idle
            np.testing.assert_allclose(t0.get_rows([9])[0], 1.0)
        finally:
            for c in ctxs:
                c.close()

    def test_first_contact_dead_peer_yields_failed_future(self, tmp_path):
        """A rank that died before we EVER connected to it: async ops must
        not raise (failed future instead), the wait is typed and bounded,
        and live-shard traffic keeps working (regression: the first-contact
        path used to raise synchronously out of fire-and-forget calls)."""
        from multiverso_tpu.utils import config
        config.set_flag("ps_timeout", 4.0)
        config.set_flag("ps_connect_timeout", 3.0)
        rdv = FileRendezvous(str(tmp_path / "rdv"))
        ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
        try:
            t0 = AsyncMatrixTable(10, 2, name="fc", ctx=ctxs[0])
            AsyncMatrixTable(10, 2, name="fc", ctx=ctxs[1])
            ctxs[1].close()   # dies before rank 0 ever dials it
            time.sleep(0.1)
            start = time.monotonic()
            mid = t0.add_rows_async([1, 9],           # spans live + dead
                                    np.ones((2, 2), np.float32))
            with pytest.raises(PSPeerError):
                t0.wait(mid)
            assert time.monotonic() - start < 12.0
            # the live half landed; later live traffic unaffected
            np.testing.assert_allclose(t0.get_rows([1])[0], 1.0)
        finally:
            for c in ctxs:
                c.close()

    def test_flush_surfaces_swept_failures_deterministically(self, tmp_path):
        """A fire-and-forget push to a dead shard must be reported by the
        NEXT flush even if the sweep already logged-and-dropped it — a
        training loop pushing async and flushing at the end (the WE block
        path) gets a deterministic error, never silent delta loss."""
        from multiverso_tpu.utils import config
        config.set_flag("ps_timeout", 5.0)
        config.set_flag("ps_connect_timeout", 5.0)
        rdv = FileRendezvous(str(tmp_path / "rdv"))
        ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
        try:
            t0 = AsyncMatrixTable(10, 2, name="sf", ctx=ctxs[0])
            AsyncMatrixTable(10, 2, name="sf", ctx=ctxs[1])
            t0.add_rows([9], np.ones((1, 2), np.float32))
            ctxs[1].close()
            time.sleep(0.1)
            t0.add_rows_async([8], np.ones((1, 2), np.float32))  # will fail
            time.sleep(0.3)
            # trigger sweeps so the failed op is popped before the flush
            for _ in range(3):
                t0.add_rows([1], np.ones((1, 2), np.float32))
            with pytest.raises(PSPeerError):
                t0.flush()
            t0.flush()   # failure consumed; table stays usable
            np.testing.assert_allclose(t0.get_rows([1])[0], 3.0)
        finally:
            for c in ctxs:
                c.close()

    def test_failed_fire_and_forget_does_not_poison_table(self, tmp_path):
        """A dead shard's unawaited add is logged, not re-raised: later ops
        on live shards keep working (the elasticity contract)."""
        from multiverso_tpu.utils import config
        config.set_flag("ps_timeout", 5.0)
        config.set_flag("ps_connect_timeout", 5.0)
        rdv = FileRendezvous(str(tmp_path / "rdv"))
        ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
        try:
            t0 = AsyncMatrixTable(10, 2, name="poison", ctx=ctxs[0])
            AsyncMatrixTable(10, 2, name="poison", ctx=ctxs[1])
            t0.add_rows([9], np.ones((1, 2), np.float32))
            ctxs[1].close()                      # rank 1 dies
            time.sleep(0.1)
            t0.add_rows_async([8], np.ones((1, 2), np.float32))  # never waited
            time.sleep(0.3)                      # let the failure land
            for _ in range(3):                   # sweeps must not raise
                t0.add_rows([1], np.ones((1, 2), np.float32))
            np.testing.assert_allclose(t0.get_rows([1])[0], 3.0)
        finally:
            for c in ctxs:
                c.close()

    def test_dead_peer_does_not_hang_live_traffic(self, tmp_path):
        """A killed worker/server must not block peers: ops on live shards
        proceed, ops on the dead shard raise PSPeerError quickly (the
        elastic behavior the reference lacked — its MPI world just hung)."""
        from multiverso_tpu.utils import config
        config.set_flag("ps_timeout", 5.0)
        config.set_flag("ps_connect_timeout", 5.0)
        rdv = FileRendezvous(str(tmp_path / "rdv"))
        ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
        try:
            t0 = AsyncMatrixTable(10, 2, name="dp", ctx=ctxs[0])
            AsyncMatrixTable(10, 2, name="dp", ctx=ctxs[1])
            t0.add_rows([0, 9], np.ones((2, 2), np.float32))
            t0.flush()
            ctxs[1].close()           # rank 1 dies
            time.sleep(0.1)
            # rows 0-4 live on rank 0: still fully functional
            t0.add_rows([1], np.ones((1, 2), np.float32))
            np.testing.assert_allclose(t0.get_rows([1])[0], 1.0)
            # rows 5-9 lived on rank 1: typed error, bounded time
            start = time.monotonic()
            with pytest.raises(PSPeerError):
                t0.get_rows([9])
            assert time.monotonic() - start < 10.0
        finally:
            for c in ctxs:
                c.close()


class TestDeathBookkeeping:
    def test_stale_incarnation_death_is_ignored(self, two_ranks):
        """A late on_death from a superseded peer object must not
        re-tombstone a rank whose fresh connection is healthy (the
        reconnect race: stale.close() fires its recv-loop death AFTER the
        new incarnation already cleared the rank)."""
        import types
        svc0 = two_ranks[0].service
        assert svc0.ping(1)
        cur = svc0._peers[1]
        svc0._note_death(1, peer=types.SimpleNamespace())   # stale object
        assert 1 not in svc0.dead_ranks()
        svc0._note_death(1, peer=cur)   # the live incarnation does count
        assert 1 in svc0.dead_ranks()
        # ...and the healthy fast path clears the stale tombstone
        assert svc0._peer(1) is cur
        assert 1 not in svc0.dead_ranks()


class TestAsyncCheckpoint:
    def test_corrupt_updater_trailer_fails_loudly(self, two_ranks,
                                                  tmp_path):
        """A checkpoint whose updater-state trailer is truncated MID-READ
        must fail the restore — only a CLEAN end-of-stream means 'legacy
        checkpoint without updater state'. Silently accepting a torn
        trailer would leave optimizer accumulators at whatever they were."""
        import io
        t0 = AsyncMatrixTable(6, 2, name="ctrl", updater="adagrad",
                              ctx=two_ranks[0])
        AsyncMatrixTable(6, 2, name="ctrl", updater="adagrad",
                         ctx=two_ranks[1])
        t0.add_rows(np.arange(6), np.ones((6, 2), np.float32))
        buf = io.BytesIO()
        t0.store(buf)
        raw = buf.getvalue()
        # cut inside the trailer HEADER (the second .npy magic): the exact
        # window the old code misread as "legacy stream"
        second_magic = raw.index(b"\x93NUMPY", raw.index(b"\x93NUMPY") + 1)
        with pytest.raises(ValueError):
            t0.load(io.BytesIO(raw[: second_magic + 4]))
        # a clean data-only stream (true legacy) still loads fine
        legacy = io.BytesIO()
        np.save(legacy, t0.get(), allow_pickle=False)
        legacy.seek(0)
        t0.load(legacy)
        np.testing.assert_allclose(t0.get_rows(np.arange(6)).shape, (6, 2))

    def test_checkpoint_walks_async_tables(self, tmp_path):
        """checkpoint.save/restore covers async tables through the same Zoo
        registry walk as the collective tables (store pulls the whole table
        off the shards; load pushes ranges back)."""
        import multiverso_tpu as mv
        from multiverso_tpu import checkpoint
        mv.init()
        try:
            t = mv.AsyncMatrixTable(8, 3, name="ck_async")
            a = mv.AsyncArrayTable(5, name="ck_async_arr")
            t.add_rows([2, 6], np.ones((2, 3), np.float32))
            a.add(np.arange(5, dtype=np.float32))
            checkpoint.save(str(tmp_path), tag="s1")
            t.add(np.full((8, 3), 9.0, np.float32))     # diverge
            a.add(np.ones(5, np.float32))
            n = checkpoint.restore(str(tmp_path), tag="s1")
            assert n >= 2
            np.testing.assert_allclose(t.get_row(2), 1.0)
            np.testing.assert_allclose(t.get_row(0), 0.0)
            np.testing.assert_allclose(a.get(), np.arange(5))
        finally:
            mv.shutdown()

    def test_checkpoint_restores_updater_state(self, tmp_path):
        """Async store/load round-trips the shard's optimizer accumulators
        (adagrad g²) — restoring must NOT silently reset them (sync-table
        parity: table.py store() persists ustate)."""
        import jax
        import multiverso_tpu as mv
        from multiverso_tpu import checkpoint
        mv.init()
        try:
            t = mv.AsyncMatrixTable(8, 3, name="ck_async_ada",
                                    updater="adagrad")
            t.add_rows([1, 2], np.ones((2, 3), np.float32))
            t.flush()
            before = [np.asarray(l) for l in jax.tree.leaves(t._shard._ustate)]
            assert any(np.abs(b).sum() > 0 for b in before)  # g² accumulated
            checkpoint.save(str(tmp_path), tag="u1")
            t.add_rows([1, 2], np.ones((2, 3), np.float32))  # diverge
            t.flush()
            checkpoint.restore(str(tmp_path), tag="u1")
            after = [np.asarray(l) for l in jax.tree.leaves(t._shard._ustate)]
            assert len(after) == len(before)
            for b, a in zip(before, after):
                np.testing.assert_allclose(a, b, rtol=1e-6)
        finally:
            mv.shutdown()


class TestAsyncSparseKVTable:
    """Hash-sharded sparse keys + FTRL payloads on the uncoordinated plane
    (ref sparse_table.h:1-306, ftrl_sparse_table.h:1-90,
    model/ps_model.cpp:24-41 — the reference's flagship sparse-LR tables)."""

    def _pair(self, two_ranks, **kw):
        from multiverso_tpu.ps.tables import AsyncSparseKVTable
        return [AsyncSparseKVTable(3, name="skv", ctx=c, **kw)
                for c in two_ranks]

    def test_hash_partition_and_accumulation(self, two_ranks):
        t0, t1 = self._pair(two_ranks)
        # arbitrary sparse keys, both parities (owner = key % 2)
        keys = np.array([7, 1_000_003, 42, 88])
        t0.add_rows(keys, np.ones((4, 3), np.float32))
        t1.add_rows(keys[:2], 2 * np.ones((2, 3), np.float32))
        got = t0.get_rows(keys)
        np.testing.assert_allclose(got[:2], 3.0)   # 1 + 2
        np.testing.assert_allclose(got[2:], 1.0)
        # a never-touched key reads as zeros (fresh slot)
        np.testing.assert_allclose(t1.get_rows([555])[0], 0.0)
        # duplicate keys in one call pre-accumulate
        t1.add_rows([9, 9], np.ones((2, 3), np.float32))
        np.testing.assert_allclose(t0.get_rows([9])[0], 2.0)

    def test_negative_and_float_keys_rejected(self, two_ranks):
        t0, _ = self._pair(two_ranks)
        with pytest.raises(IndexError):
            t0.add_rows([-1], np.ones((1, 3), np.float32))
        with pytest.raises(TypeError):
            t0.get_rows(np.array([1.5]))

    def test_ftrl_over_the_wire(self, two_ranks):
        """FTRL z/n live as shard state; pushing raw gradients moves the
        stored weight the way the proximal update says (sign-opposite to
        the gradient, zero until |z| clears lambda1)."""
        t0, t1 = self._pair(two_ranks, updater="ftrl")
        g = np.full((1, 3), 0.5, np.float32)
        key = [12345]
        for _ in range(20):
            t0.add_rows(key, g)
        w = t0.get_rows(key)[0]
        assert np.all(w < 0)                     # steady +g pushes w negative
        assert np.all(np.abs(w) < 10)
        # the other rank sees the same uncoordinated state
        np.testing.assert_allclose(t1.get_rows(key)[0], w, rtol=1e-6)

    def test_sparse_get_stale_protocol(self, two_ranks):
        t0, t1 = self._pair(two_ranks, num_workers=2)
        keys = np.array([3, 4, 5, 6])
        first = t0.get_rows_sparse(keys, worker_id=0)
        np.testing.assert_allclose(first, 0.0)
        assert t0.last_transfer_rows == 4        # first pull: everything
        again = t0.get_rows_sparse(keys, worker_id=0)
        assert t0.last_transfer_rows == 0        # all fresh now
        np.testing.assert_allclose(again, 0.0)
        # rank 1 touches ONE key -> exactly one row re-crosses the wire
        t1.add_rows([5], np.ones((1, 3), np.float32))
        got = t0.get_rows_sparse(keys, worker_id=0)
        assert t0.last_transfer_rows == 1
        np.testing.assert_allclose(got[2], 1.0)

    def test_dense_get_and_bound(self, two_ranks):
        t0, _ = self._pair(two_ranks, num_row=10)
        t0.add_rows([2, 9], np.ones((2, 3), np.float32))
        dense = t0.get()
        assert dense.shape == (10, 3)
        np.testing.assert_allclose(dense[[2, 9]], 1.0)
        np.testing.assert_allclose(dense[0], 0.0)
        with pytest.raises(IndexError):
            t0.get_rows([10])

    def test_checkpoint_roundtrip_with_state(self, two_ranks, tmp_path):
        t0, t1 = self._pair(two_ranks, updater="adagrad")
        t0.add_rows([1, 2, 1001], np.ones((3, 3), np.float32))
        t1.flush(), t0.flush()
        saved_rows = t0.get_rows([1, 2, 1001])
        with open(tmp_path / "skv.ck", "wb") as f:
            t0.store(f)
        t0.add_rows([1, 7], np.ones((2, 3), np.float32))  # diverge
        with open(tmp_path / "skv.ck", "rb") as f:
            t0.load(f)
        np.testing.assert_allclose(t0.get_rows([1, 2, 1001]), saved_rows)
        np.testing.assert_allclose(t0.get_rows([7])[0], 0.0)
        # adagrad accumulators restored: the next identical add moves the
        # weight by the same amount it did the first time after the save
        before = t0.get_rows([1])[0].copy()
        t0.add_rows([1], np.ones((1, 3), np.float32))
        step_after_restore = t0.get_rows([1])[0] - before
        assert np.all(np.abs(step_after_restore) > 0)

    def test_slot_growth_past_capacity(self, two_ranks):
        from multiverso_tpu.ps.tables import AsyncSparseKVTable
        t0 = AsyncSparseKVTable(2, name="skv_grow", ctx=two_ranks[0])
        AsyncSparseKVTable(2, name="skv_grow", ctx=two_ranks[1])
        n = 3000   # > initial 1024-slot capacity per shard
        keys = np.arange(n)
        t0.add_rows(keys, np.ones((n, 2), np.float32))
        got = t0.get_rows(keys[::7])
        np.testing.assert_allclose(got, 1.0)


class TestPipelineSparseGets:
    """Prefetch-overlapped sparse pulls (ref matrix.cpp:407-418 is_pipeline
    doubled its per-worker slots for exactly this; here overlapped pulls are
    first-class). Exact rows-transferred assertions."""

    def test_two_pulls_in_flight(self, two_ranks):
        t0 = AsyncSparseMatrixTable(12, 2, num_workers=2, name="pp",
                                    ctx=two_ranks[0])
        t1 = AsyncSparseMatrixTable(12, 2, num_workers=2, name="pp",
                                    ctx=two_ranks[1])
        lo, hi = np.arange(6), np.arange(6, 12)
        # double-buffer: both pulls dispatched before either is consumed
        a = t0.get_rows_sparse_async(lo, worker_id=0)
        b = t0.get_rows_sparse_async(hi, worker_id=0)
        ra = t0.wait(a)
        n_a = t0.last_transfer_rows
        rb = t0.wait(b)
        n_b = t0.last_transfer_rows
        np.testing.assert_allclose(ra, 0.0)
        np.testing.assert_allclose(rb, 0.0)
        assert n_a == 6 and n_b == 6          # first epoch: everything stale
        # steady state: overlapped pulls of fresh rows transfer NOTHING
        a = t0.get_rows_sparse_async(lo, worker_id=0)
        b = t0.get_rows_sparse_async(hi, worker_id=0)
        t0.wait(a); assert t0.last_transfer_rows == 0
        t0.wait(b); assert t0.last_transfer_rows == 0
        # a peer dirties one row per block -> exactly one row per pull
        t1.add_rows([2, 8], np.ones((2, 2), np.float32))
        a = t0.get_rows_sparse_async(lo, worker_id=0)
        b = t0.get_rows_sparse_async(hi, worker_id=0)
        ra = t0.wait(a); assert t0.last_transfer_rows == 1
        rb = t0.wait(b); assert t0.last_transfer_rows == 1
        np.testing.assert_allclose(ra[2], 1.0)
        np.testing.assert_allclose(rb[2], 1.0)   # row 8 -> position 2 in hi

    def test_out_of_order_wait_stays_correct(self, two_ranks):
        """Waiting the second pull before the first, with OVERLAPPING rows:
        worst case the client self-heals with a plain re-pull — values are
        always right."""
        t0 = AsyncSparseMatrixTable(8, 2, num_workers=2, name="oo",
                                    ctx=two_ranks[0])
        t1 = AsyncSparseMatrixTable(8, 2, num_workers=2, name="oo",
                                    ctx=two_ranks[1])
        t1.add_rows(np.arange(8), np.ones((8, 2), np.float32))
        a = t0.get_rows_sparse_async(np.arange(8), worker_id=0)
        b = t0.get_rows_sparse_async(np.arange(4), worker_id=0)
        rb = t0.wait(b)    # consumed before a
        ra = t0.wait(a)
        np.testing.assert_allclose(ra, 1.0)
        np.testing.assert_allclose(rb, 1.0)

    def test_threaded_prefetch_against_training(self, two_ranks):
        """An AsyncBuffer-style prefetch thread pulls while the main thread
        pushes — no corruption, final state exact."""
        t0 = AsyncSparseMatrixTable(16, 2, num_workers=2, name="th",
                                    ctx=two_ranks[0])
        AsyncSparseMatrixTable(16, 2, num_workers=2, name="th",
                               ctx=two_ranks[1])
        stop, errs = threading.Event(), []

        def prefetch():
            try:
                while not stop.is_set():
                    t0.get_rows_sparse(np.arange(16), worker_id=0)
            except Exception as e:   # pragma: no cover
                errs.append(e)

        th = threading.Thread(target=prefetch)
        th.start()
        for _ in range(30):
            t0.add_rows([1, 9], np.ones((2, 2), np.float32))
        t0.flush()
        stop.set()
        th.join(timeout=30)
        assert not errs, errs
        got = t0.get_rows_sparse(np.arange(16), worker_id=0)
        np.testing.assert_allclose(got[1], 30.0)
        np.testing.assert_allclose(got[9], 30.0)
        np.testing.assert_allclose(got[0], 0.0)

    def test_out_of_order_wait_does_not_revert_newer_data(self, two_ranks):
        """An older pull consumed AFTER a newer one must not overwrite the
        newer cached rows (the server bit is clear by then — a revert would
        be served forever)."""
        t0 = AsyncSparseMatrixTable(8, 2, num_workers=2, name="rv",
                                    ctx=two_ranks[0])
        t1 = AsyncSparseMatrixTable(8, 2, num_workers=2, name="rv",
                                    ctx=two_ranks[1])
        t0.get_rows_sparse(np.arange(8), worker_id=0)          # warm
        t1.add_rows([1], np.ones((1, 2), np.float32))          # v = 1
        a = t0.get_rows_sparse_async([1, 2], worker_id=0)
        with t0._lock:   # stage: A fully processed server-side before B
            futs_a = t0._pending[a][0]
        for f in futs_a:
            f.result(timeout=10)
        t1.add_rows([1], np.ones((1, 2), np.float32))          # v = 2
        b = t0.get_rows_sparse_async([1, 2, 3], worker_id=0)
        rb = t0.wait(b)                                        # newer first
        ra = t0.wait(a)                                        # older second
        np.testing.assert_allclose(rb[0], 2.0)
        np.testing.assert_allclose(ra[0], 2.0)   # not reverted to 1.0
        again = t0.get_rows_sparse([1], worker_id=0)
        assert t0.last_transfer_rows == 0        # cache kept the newer row
        np.testing.assert_allclose(again[0], 2.0)


class TestShutdownQuiesce:
    """The MV_ShutDown-barrier analogue (ref src/zoo.cpp:103-115): a rank
    keeps serving until live peers also reach shutdown."""

    def test_both_ranks_converge_quickly(self, two_ranks, tmp_path):
        from multiverso_tpu.utils import config
        config.set_flag("ps_shutdown_grace", 30.0)
        t0 = time.monotonic()
        th = threading.Thread(target=lambda: two_ranks[0].quiesce())
        th.start()
        time.sleep(0.15)            # rank 0 waits on rank 1's mark
        two_ranks[1].quiesce()
        th.join(timeout=10)
        assert not th.is_alive()
        assert time.monotonic() - t0 < 10

    def test_timeout_proceeds_without_peer(self, two_ranks):
        from multiverso_tpu.utils import config
        config.set_flag("ps_shutdown_grace", 0.4)
        t0 = time.monotonic()
        two_ranks[0].quiesce()      # rank 1 never marks
        dt = time.monotonic() - t0
        assert 0.3 < dt < 5.0       # bounded by the grace, no hang

    def test_observed_dead_peer_skipped(self, two_ranks):
        from multiverso_tpu.utils import config
        config.set_flag("ps_shutdown_grace", 30.0)
        t0_ctx, t1_ctx = two_ranks
        t = AsyncMatrixTable(8, 2, name="qd", ctx=t0_ctx)
        AsyncMatrixTable(8, 2, name="qd", ctx=t1_ctx)
        t.add_rows([7], np.ones((1, 2), np.float32))  # rank-1-owned: connect
        t1_ctx.service.close()      # rank 1 "dies"
        config.set_flag("ps_timeout", 3.0)
        with pytest.raises(Exception):
            t.get_rows([7])         # observe the death -> dead_ranks
        assert 1 in t0_ctx.service.dead_ranks()
        t0 = time.monotonic()
        t0_ctx.quiesce()            # dead peer skipped, returns immediately
        assert time.monotonic() - t0 < 5.0

    def test_stale_markers_from_previous_run_ignored(self, tmp_path):
        """A reused rendezvous dir's leftover quiesce markers must not
        satisfy the current run's barrier: markers are stamped with the
        incarnation's published address."""
        rdv = FileRendezvous(str(tmp_path / "r"))
        rdv.mark(1, "ps_quiesce", "127.0.0.1:1111")   # previous run
        rdv.publish(1, "127.0.0.1:2222")              # current incarnation
        assert not rdv.wait_mark(1, "ps_quiesce", 0.2,
                                 expect="127.0.0.1:2222")
        rdv.mark(1, "ps_quiesce", "127.0.0.1:2222")   # current run quiesces
        assert rdv.wait_mark(1, "ps_quiesce", 1.0,
                             expect="127.0.0.1:2222")


class TestMultiHostBind:
    def test_wildcard_bind_publishes_routable_addr(self, tmp_path):
        """-ps_host 0.0.0.0 (the multi-host setting) must publish a
        ROUTABLE address, never the wildcard itself — peers connect to
        what the rendezvous says."""
        from multiverso_tpu.ps.service import _routable_ip
        rdv = FileRendezvous(str(tmp_path / "rdv"))
        s0 = PSService(0, 2, rdv, host="0.0.0.0")
        s1 = PSService(1, 2, rdv, host="0.0.0.0")
        try:
            host0 = s0.addr.rsplit(":", 1)[0]
            assert host0 not in ("0.0.0.0", "", "::")
            assert host0 == _routable_ip()
            assert rdv.lookup(0, 5.0) == s0.addr
            # a real connection works through the published address
            c0 = PSContext(0, 2, s0)
            c1 = PSContext(1, 2, s1)
            t0 = AsyncMatrixTable(8, 2, name="wb", ctx=c0)
            AsyncMatrixTable(8, 2, name="wb", ctx=c1)
            t0.add_rows([6], np.ones((1, 2), np.float32))  # rank-1-owned
            np.testing.assert_allclose(t0.get_rows([6])[0], 1.0)
        finally:
            s0.close()
            s1.close()
