"""Send-window layer (PR 2): MSG_BATCH framing, client-side coalescing,
ordering fences, dashboard counters, and the get_rows(out=) reply
scatter — the tier-1 smoke coverage so framing/window regressions
surface without a full bench run."""

import concurrent.futures as cf

import numpy as np
import pytest

from multiverso_tpu.ps import service as svc
from multiverso_tpu.ps import wire
from multiverso_tpu.ps.tables import AsyncMatrixTable, AsyncSparseKVTable
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils import config
from multiverso_tpu.utils.dashboard import Dashboard


# ---------------------------------------------------------------------- #
# MSG_BATCH framing (pure wire layer, no sockets)
# ---------------------------------------------------------------------- #
class TestBatchFraming:
    def test_pack_unpack_round_trip(self):
        rng = np.random.default_rng(3)
        subs = []
        for i in range(5):
            ids = rng.integers(0, 100, rng.integers(1, 9)).astype(np.int64)
            vals = rng.normal(size=(ids.size, 7)).astype(np.float32)
            meta = {"table": "t", "opt": AddOption()._asdict()}
            subs.append((meta, [ids, vals]))
        blobs = [wire.encode(svc.MSG_ADD_ROWS, i, m, arrs)
                 for i, (m, arrs) in enumerate(subs)]
        out = wire.unpack_batch(wire.pack_batch(blobs))
        assert len(out) == len(subs)
        for (meta, arrs), (mt, m, got) in zip(subs, out):
            assert mt == svc.MSG_ADD_ROWS
            assert m == meta
            assert len(got) == len(arrs)
            for a, b in zip(arrs, got):
                assert a.dtype == b.dtype and a.shape == b.shape
                assert np.array_equal(a, b)

    def test_round_trip_preserves_codec_payloads(self):
        """A sub-op carrying a compressed wire (a bfloat16 blob) must
        come back byte-identical — the shard decodes straight from the
        batch blobs."""
        rng = np.random.default_rng(4)
        vals = rng.normal(size=(4, 32)).astype(np.float32)
        [half] = wire.encode_payload(vals, "bf16")
        ids = np.arange(4, dtype=np.int64)
        blob = wire.encode(svc.MSG_ADD_ROWS, 0,
                           {"table": "t", "wire": "bf16"}, [ids, half])
        [(mt, meta, arrs)] = wire.unpack_batch(wire.pack_batch([blob]))
        assert meta["wire"] == "bf16"
        assert arrs[1].dtype == half.dtype
        assert arrs[1].nbytes == vals.nbytes // 2
        assert arrs[1].tobytes() == half.tobytes()
        assert np.array_equal(
            wire.decode_payload(arrs[1:], "bf16", vals.shape, np.float32),
            half.astype(np.float32))

    def test_empty_and_oversize_batches_rejected(self):
        with pytest.raises(wire.WireError):
            wire.pack_batch([])
        big = [b"x"] * (wire.MAX_BATCH_OPS + 1)
        with pytest.raises(wire.WireError):
            wire.pack_batch(big)
        arrs = [np.zeros(4, np.uint8)] * (wire.MAX_BATCH_OPS + 1)
        with pytest.raises(wire.WireError):
            wire.unpack_batch(arrs)

    def test_corrupt_sub_frame_raises(self):
        with pytest.raises(wire.WireError):
            wire.unpack_batch([np.zeros(64, np.uint8)])


# ---------------------------------------------------------------------- #
# window behavior on a live 2-rank plane
# ---------------------------------------------------------------------- #
def test_window_off_by_default(two_ranks):
    t = AsyncMatrixTable(8, 2, name="nw", ctx=two_ranks[0])
    assert t._window is None


def test_flag_installs_window(two_ranks):
    config.set_flag("batch_window_ms", 1.5)
    t = AsyncMatrixTable(8, 2, name="fw", ctx=two_ranks[0])
    assert t._window is not None
    assert t._window.window_s == pytest.approx(1.5e-3)
    # per-table override beats the flag, including turning it OFF
    t2 = AsyncMatrixTable(8, 2, name="fw2", send_window_ms=0.0,
                          ctx=two_ranks[0])
    assert t2._window is None


def test_windowed_adds_read_your_writes(two_ranks):
    """A get issued right after windowed async adds must observe them —
    the fence ships the queue before the get's own frame (per-conn
    FIFO), with NO explicit flush/wait from the caller."""
    t = AsyncMatrixTable(16, 3, name="ryw", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    AsyncMatrixTable(16, 3, name="ryw", ctx=two_ranks[1])
    ones = np.ones((1, 3), np.float32)
    for row in (1, 9, 9, 15):   # both shards, duplicates included
        t.add_rows_async([row], ones)
    got = t.get_rows(np.arange(16))
    expect = np.zeros((16, 3), np.float32)
    for row in (1, 9, 9, 15):
        expect[row] += 1.0
    assert np.array_equal(got, expect)


def test_window_counters_surface_in_dashboard(two_ranks):
    """The zoo shutdown report prints every registered monitor — the
    window's three counters must exist (and tick)."""
    t = AsyncMatrixTable(8, 2, name="wc", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    AsyncMatrixTable(8, 2, name="wc", ctx=two_ranks[1])
    names = [f"table[wc].add_rows.{k}"
             for k in ("windowed", "flushes", "merged_rows")]
    snap = Dashboard.snapshot()
    assert all(n in snap for n in names)   # registered eagerly
    t.add_rows_async([2], np.ones((1, 2), np.float32))
    t.add_rows_async([3], np.ones((1, 2), np.float32))   # same owner: merges
    t.flush()
    snap = Dashboard.snapshot()
    assert snap["table[wc].add_rows.windowed"].count == 2
    assert snap["table[wc].add_rows.flushes"].count >= 1
    # the two disjoint single-row adds merged into one frame
    assert snap["table[wc].add_rows.merged_rows"].count >= 1


def test_window_op_bound_ships_inline(two_ranks):
    """Hitting batch_window_ops flushes the owner's queue immediately —
    no timer involved (window_ms set huge)."""
    config.set_flag("batch_window_ops", 4)
    t = AsyncMatrixTable(8, 2, name="ob", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    AsyncMatrixTable(8, 2, name="ob", ctx=two_ranks[1])
    flushes = Dashboard.get("table[ob].add_rows.flushes")
    for row in range(4):   # rank 0 owns rows [0, 4)
        t.add_rows_async([row], np.ones((1, 2), np.float32))
    assert flushes.count == 1
    t.flush()


def test_batch_frames_carry_adds_only(two_ranks):
    """A MSG_BATCH with a non-add sub-op is a framing error: the shard
    rejects it with a typed PSError reply."""
    AsyncMatrixTable(8, 2, name="bo", ctx=two_ranks[0])
    AsyncMatrixTable(8, 2, name="bo", ctx=two_ranks[1])
    blob = wire.encode(svc.MSG_GET_ROWS, 0, {"table": "bo"},
                       [np.arange(2, dtype=np.int64)])
    fut = two_ranks[0].service.request(
        1, svc.MSG_BATCH, {"table": "bo"}, wire.pack_batch([blob]))
    with pytest.raises(svc.PSError):
        svc.await_reply(fut, 20.0, "batch")


def test_kv_window_parity(two_ranks):
    """The hash-sharded plane windows too: keyed adds coalesce per owner
    and land bit-for-bit identical to the window-off table."""
    rng = np.random.default_rng(11)
    tw = AsyncSparseKVTable(3, name="kvw", send_window_ms=60_000.0,
                            ctx=two_ranks[0])
    AsyncSparseKVTable(3, name="kvw", ctx=two_ranks[1])
    tr = AsyncSparseKVTable(3, name="kvr", ctx=two_ranks[0])
    AsyncSparseKVTable(3, name="kvr", ctx=two_ranks[1])
    keys = np.unique(rng.integers(0, 5000, 40))
    for i in range(30):
        k = rng.choice(keys, rng.integers(1, 6), replace=False)
        v = rng.normal(size=(k.size, 3)).astype(np.float32)
        tw.add_rows_async(k, v)
        tr.add_rows_async(k, v)
        if i % 9 == 0:
            assert np.array_equal(tw.get_rows(keys), tr.get_rows(keys))
    tw.flush()
    tr.flush()
    assert np.array_equal(tw.get_rows(keys), tr.get_rows(keys))


def test_wait_completes_windowed_add(two_ranks):
    """wait(msg_id) on a still-queued windowed add fences the window and
    blocks until the ack — the placeholder futures are real futures."""
    t = AsyncMatrixTable(8, 2, name="ww", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    AsyncMatrixTable(8, 2, name="ww", ctx=two_ranks[1])
    mid = t.add_rows_async([5], np.ones((1, 2), np.float32))
    t.wait(mid)   # must not hang; add durably applied after
    got = t.get_rows([5])
    assert got[0, 0] == 1.0


def test_batch_partial_failure_reports_per_subop(two_ranks):
    """A sub-op that fails mid-batch fails ONLY its own placeholder
    future (via the reply meta's "failed" indices): deltas that durably
    applied are never reported lost — a blanket error would invite a
    retry that double-applies them."""
    t = AsyncMatrixTable(8, 2, name="pf", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    t1 = AsyncMatrixTable(8, 2, name="pf", ctx=two_ranks[1])
    shard = t1._shard   # rank 1 owns rows [4, 8)
    orig = type(shard)._apply_rows

    def boom(self, local, vals, opt):
        if (5 - self.lo) in np.asarray(local):
            raise RuntimeError("synthetic apply failure")
        return orig(self, local, vals, opt)

    shard._apply_rows = boom.__get__(shard)
    ones = np.ones((1, 2), np.float32)
    # three sub-ops, forced into separate waves by the row-4 conflicts:
    # [4] applies, [4, 5] fails (synthetic), [4] applies
    m_ok1 = t.add_rows_async([4], ones)
    m_bad = t.add_rows_async([4, 5], np.ones((2, 2), np.float32))
    m_ok2 = t.add_rows_async([4], ones)
    t.wait(m_ok1)
    t.wait(m_ok2)
    with pytest.raises(svc.PSError):
        t.wait(m_bad)
    shard._apply_rows = orig.__get__(shard)
    # the two successful adds landed exactly once each; the failed
    # sub-op's rows are untouched
    got = t.get_rows([4, 5])
    assert np.array_equal(
        got, np.array([[2.0, 2.0], [0.0, 0.0]], np.float32)), got


def test_windowed_add_failure_surfaces_at_flush(two_ranks):
    """An unreachable owner fails the windowed add's placeholder future;
    flush() raises it like any other lost delta."""
    t = AsyncMatrixTable(8, 2, name="wf", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    AsyncMatrixTable(8, 2, name="wf", ctx=two_ranks[1])
    config.set_flag("ps_timeout", 4.0)
    config.set_flag("ps_connect_timeout", 4.0)
    two_ranks[1].close()   # rank 1 (rows [4, 8)) goes away
    t.add_rows_async([6], np.ones((1, 2), np.float32))
    with pytest.raises((svc.PSPeerError, cf.TimeoutError)):
        t.flush()


def test_window_ops_knob_clamped_to_wire_bound(two_ranks):
    """batch_window_ops set past wire.MAX_BATCH_OPS must not make
    windows unsendable: the knob clamps, and an over-full window would
    chunk into multiple frames rather than fail every queued delta."""
    config.set_flag("batch_window_ops", wire.MAX_BATCH_OPS * 2)
    t = AsyncMatrixTable(8, 2, name="clamp", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    AsyncMatrixTable(8, 2, name="clamp", ctx=two_ranks[1])
    assert t._window.max_ops == wire.MAX_BATCH_OPS
    # unmergeable sub-ops (same row repeatedly): a burst still applies
    for _ in range(40):
        t.add_rows_async([0], np.ones((1, 2), np.float32))
    t.flush()
    assert t.get_rows([0])[0, 0] == 40.0


def test_windowed_add_owns_values_buffer(two_ranks):
    """A training loop that reuses one gradient scratch buffer between
    windowed adds must not corrupt queued deltas: the window copies
    anything it defers (the single-owner fast path used to queue a
    zero-copy view of the caller's array)."""
    t = AsyncMatrixTable(8, 2, name="alias", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    AsyncMatrixTable(8, 2, name="alias", ctx=two_ranks[1])
    buf = np.ones((1, 2), np.float32)
    t.add_rows_async([1], buf)
    buf[:] = 100.0            # caller reuses the scratch buffer
    t.add_rows_async([2], buf)
    buf[:] = -5.0
    got = t.get_rows([1, 2])
    assert np.array_equal(
        got, np.array([[1.0, 1.0], [100.0, 100.0]], np.float32)), got


def test_flusher_thread_exits_with_table(two_ranks, monkeypatch):
    """The window's daemon flusher holds its table only via weakref: once
    the table is garbage, the thread exits at its next bounded wakeup
    instead of pinning the table (conns, monitors) for process life."""
    import gc
    import time as _time

    from multiverso_tpu.ps import tables as tables_mod
    monkeypatch.setattr(tables_mod._SendWindow, "_IDLE_WAIT_S", 0.05)
    t = AsyncMatrixTable(8, 2, name="thx", send_window_ms=60_000.0,
                         ctx=two_ranks[0])
    AsyncMatrixTable(8, 2, name="thx", ctx=two_ranks[1])
    t.add_rows_async([1], np.ones((1, 2), np.float32))
    t.flush()
    th = t._window._thread
    assert th is not None and th.is_alive()
    del t
    gc.collect()
    deadline = _time.monotonic() + 5.0
    while th.is_alive() and _time.monotonic() < deadline:
        _time.sleep(0.05)
    assert not th.is_alive()


# ---------------------------------------------------------------------- #
# get_rows(out=) reply scatter (PR-2 satellite)
# ---------------------------------------------------------------------- #
class TestGetRowsOut:
    def test_out_buffer_is_filled_and_returned(self, two_ranks):
        t = AsyncMatrixTable(10, 4, name="go", ctx=two_ranks[0])
        AsyncMatrixTable(10, 4, name="go", ctx=two_ranks[1])
        t.add_rows(np.arange(10), np.arange(40, dtype=np.float32)
                   .reshape(10, 4))
        ids = np.array([1, 4, 7, 9])
        buf = np.full((4, 4), -1.0, np.float32)
        got = t.get_rows(ids, out=buf)
        assert got is buf   # replies scattered into the CALLER's buffer
        ref = t.get_rows(ids)
        assert np.array_equal(buf, ref)

    def test_out_with_duplicate_ids(self, two_ranks):
        t = AsyncMatrixTable(10, 4, name="gd", ctx=two_ranks[0])
        AsyncMatrixTable(10, 4, name="gd", ctx=two_ranks[1])
        t.add_rows(np.arange(10), np.arange(40, dtype=np.float32)
                   .reshape(10, 4))
        ids = np.array([3, 8, 3, 1])
        buf = np.empty((4, 4), np.float32)
        got = t.get_rows(ids, out=buf)
        assert got is buf
        assert np.array_equal(buf, t.get_rows(ids))

    def test_mismatched_out_still_correct(self, two_ranks):
        """A non-contiguous / wrong-dtype out cannot take the scatter
        directly; the fallback copy path must still fill it."""
        t = AsyncMatrixTable(10, 4, name="gm", ctx=two_ranks[0])
        AsyncMatrixTable(10, 4, name="gm", ctx=two_ranks[1])
        t.add_rows(np.arange(10), np.arange(40, dtype=np.float32)
                   .reshape(10, 4))
        ids = np.array([0, 5, 9])
        wide = np.empty((3, 8), np.float32)
        buf = wide[:, ::2]   # non-contiguous view
        got = t.get_rows(ids, out=buf)
        assert got is buf
        assert np.array_equal(np.ascontiguousarray(buf), t.get_rows(ids))


# ---------------------------------------------------------------------- #
# multi-owner fan-out (ISSUE 15, ps/spmd.py): windowed adds coalesced
# into one super-frame per destination process, and exactly-once replay
# surviving a routed shard's kill/respawn — across 4 shards, on BOTH
# wire planes, bit-identical to the 1-shard oracle
# ---------------------------------------------------------------------- #
class TestMultiOwnerFanout:
    ROWS, DIM = 64, 4

    def _stream(self):
        rng = np.random.default_rng(11)
        out = []
        for _ in range(10):
            k = int(rng.integers(3, self.ROWS // 2))
            ids = np.sort(rng.choice(self.ROWS, size=k, replace=False))
            out.append((ids,
                        rng.normal(size=(k, self.DIM))
                        .astype(np.float32)))
        return out

    def _oracle(self, tmp_path):
        config.set_flag("ps_fanout", False)
        rdv = svc.FileRendezvous(str(tmp_path / "orc"))
        ctx = svc.PSContext(0, 1, svc.PSService(0, 1, rdv))
        t = AsyncMatrixTable(self.ROWS, self.DIM, name="fw_o",
                             send_window_ms=2.0, ctx=ctx)
        for ids, vals in self._stream():
            t.add_rows_async(ids, vals)
        t.flush()
        want = t.get_rows(np.arange(self.ROWS))
        ctx.close()
        return want

    @pytest.mark.parametrize("plane", ["native", "python"])
    def test_windowed_fanout_parity_four_shards(self, tmp_path, plane):
        want = self._oracle(tmp_path)
        config.set_flag("ps_native", plane == "native")
        config.set_flag("ps_fanout", True)
        rdv = svc.FileRendezvous(str(tmp_path / "w"))
        ctxs = [svc.PSContext(r, 4, svc.PSService(r, 4, rdv))
                for r in range(4)]
        tabs = [AsyncMatrixTable(self.ROWS, self.DIM, name="fw_t",
                                 send_window_ms=2.0, ctx=c)
                for c in ctxs]
        t = tabs[0]
        for ids, vals in self._stream():
            t.add_rows_async(ids, vals)
        t.flush()
        flushes = Dashboard.get("table[fw_t].add_rows.flushes")
        assert flushes.snapshot().count > 0
        got = tabs[2].get_rows(np.arange(self.ROWS))
        np.testing.assert_array_equal(got, want)
        for c in ctxs:
            c.close()

    @pytest.mark.parametrize("plane", ["native", "python"])
    def test_replay_after_kill_four_shards(self, tmp_path, plane):
        """Exactly-once replay over the ROUTED plane: kill one of four
        colocated shards mid-stream, respawn + restore it, and the
        final table must be bit-identical to the 1-shard oracle — no
        acked op lost, no frame double-applied."""
        import time as _time

        from multiverso_tpu.ps import failover

        want = self._oracle(tmp_path)
        config.set_flag("ps_native", plane == "native")
        config.set_flag("ps_fanout", True)
        config.set_flag("ps_replay", True)
        config.set_flag("ps_timeout", 30.0)
        config.set_flag("ps_connect_timeout", 5.0)
        config.set_flag("ps_reconnect_backoff", 0.2)
        config.set_flag("ps_replay_backoff", 0.05)
        rdv = svc.FileRendezvous(str(tmp_path / "k"))
        ckdir = str(tmp_path / "ck")
        ctxs = [svc.PSContext(r, 4, svc.PSService(r, 4, rdv))
                for r in range(4)]
        tabs = [AsyncMatrixTable(self.ROWS, self.DIM, name="fk_t",
                                 send_window_ms=1.0, ctx=c)
                for c in ctxs]
        ctx3b = None
        try:
            t = tabs[0]
            stream = self._stream()
            # checkpoint rank 3's EMPTY shard so the respawn has a
            # restorable base (seq channels start empty; replay covers
            # everything after)
            ck = failover.ShardCheckpointer(ckdir, 3, [tabs[3]],
                                            interval_s=999)
            ck.checkpoint_now()
            for ids, vals in stream[:5]:
                t.add_rows_async(ids, vals)
            t.flush()
            ctxs[3].service.close()   # the "crash" of a routed shard
            # mid-outage traffic: frames to rank 3 arm for replay
            for ids, vals in stream[5:]:
                t.add_rows_async(ids, vals)
            _time.sleep(0.3)
            config.set_flag("ps_generation", 1)
            svc3b = svc.PSService(3, 4, rdv, defer_publish=True)
            ctx3b = svc.PSContext(3, 4, svc3b)
            t3b = AsyncMatrixTable(self.ROWS, self.DIM, name="fk_t",
                                   send_window_ms=1.0, ctx=ctx3b)
            assert failover.rejoin(ckdir, 3, [t3b],
                                   service=svc3b) == 1
            t.flush()
            # every pre-kill acked frame for rank 3 REPLAYS (its
            # checkpoint was empty) and every mid-outage frame lands:
            # final state must be exactly the oracle's
            deadline = _time.monotonic() + 20.0
            got = None
            while _time.monotonic() < deadline:
                got = tabs[1].get_rows(np.arange(self.ROWS))
                if np.array_equal(got, want):
                    break
                _time.sleep(0.2)
            np.testing.assert_array_equal(got, want)
        finally:
            for c in [ctxs[0], ctxs[1], ctxs[2]]:
                c.close()
            if ctx3b is not None:
                ctx3b.close()
