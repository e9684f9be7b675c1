"""Read-path overhaul (ISSUE 5): off-lock snapshot serving (epoch pins,
copy-on-write applies, donate gating), chunk-streamed get replies, the
client get coalescer, the sparse dirty-bit/epoch atomicity fix, and the
get_rows(out=) shape validation — tier-1 coverage so a regression in any
layer surfaces without a full bench run."""

import threading
import time

import numpy as np
import pytest

from multiverso_tpu.ps import service as svc
from multiverso_tpu.ps.shard import RowShard
from multiverso_tpu.ps.tables import AsyncMatrixTable
from multiverso_tpu.updaters import AddOption, get_updater
from multiverso_tpu.utils import config
from multiverso_tpu.utils.dashboard import Dashboard


def _row_shard(n=32, cols=4, updater="sgd", workers=0):
    return RowShard(0, n, cols, np.float32,
                    get_updater(updater, num_workers=max(workers, 1),
                                dtype=np.float32),
                    f"shard_{updater}_{workers}", num_workers=workers)


def _add(shard, ids, vals, opt=None):
    shard.handle(svc.MSG_ADD_ROWS,
                 {"table": shard.name,
                  "opt": (opt or AddOption())._asdict()},
                 [np.asarray(ids, np.int64),
                  np.asarray(vals, np.float32)])


def _get(shard, ids, **meta):
    _, arrays = shard.handle(svc.MSG_GET_ROWS,
                             dict({"table": shard.name}, **meta),
                             [np.asarray(ids, np.int64)])
    return np.asarray(arrays[0])


# ---------------------------------------------------------------------- #
# epoch pins: refcounting, copy-on-write, donate gating (no sockets)
# ---------------------------------------------------------------------- #
class TestEpochPins:
    def test_pin_release_refcount(self):
        s = _row_shard()
        pin = s._pin_data()
        assert s._cur_pins == 1 and s._data_pinned()
        pin2 = s._pin_data()
        assert s._cur_pins == 2
        s._release_data(pin)
        s._release_data(pin2)
        assert s._cur_pins == 0 and not s._data_pinned()

    def test_np_mode_apply_cows_while_pinned(self):
        """An in-place numpy apply racing a pinned read must copy: the
        pinned snapshot keeps its pre-apply bytes, the shard moves on."""
        s = _row_shard(updater="sgd")
        assert s._np_mode
        _add(s, [1], [[1, 1, 1, 1]])
        pin = s._pin_data()
        before = np.asarray(pin.data).copy()
        buf_id = id(s._data)
        _add(s, [1], [[2, 2, 2, 2]])          # must NOT touch the pin
        assert id(s._data) != buf_id           # copy-on-write swapped
        assert s._stat_cow == 1
        assert np.array_equal(np.asarray(pin.data), before)
        assert s._data[1, 0] == -3.0           # sgd: 0 - 1 - 2
        s._release_data(pin)
        # stale release against a swapped buffer is a no-op, and the
        # NEXT apply (no pins) mutates in place again
        buf_id = id(s._data)
        _add(s, [1], [[1, 0, 0, 0]])
        assert id(s._data) == buf_id and s._stat_cow == 1
        # the last release of a CURRENT pin drops the identity anchor
        # too — a retired buffer must free on release, not linger in
        # _pin_buf until the next get (a full extra table of memory)
        pin2 = s._pin_data()
        _add(s, [1], [[1, 0, 0, 0]])     # COW retires pin2's buffer
        s._release_data(pin2)
        assert s._pin_buf is None and s._cur_pins == 0

    def test_jit_apply_skips_donation_while_pinned(self):
        """Device-backed shards (stateful updater -> jitted apply with
        buffer donation) must compile the non-donating variant while a
        reader pins the epoch — the pinned array stays readable."""
        s = _row_shard(updater="adagrad")
        assert not s._np_mode
        _add(s, [2], [[1, 1, 1, 1]])
        pin = s._pin_data()
        before = np.asarray(pin.data).copy()
        _add(s, [2], [[1, 1, 1, 1]])
        assert s._stat_cow == 1
        # the pinned buffer was NOT donated: still materializable
        assert np.array_equal(np.asarray(pin.data), before)
        s._release_data(pin)
        _add(s, [2], [[1, 1, 1, 1]])           # donating path again

    def test_get_serves_pinned_epoch_while_applies_flow(self):
        """The stress shape, deterministically: a get stuck mid-gather
        (injected) must neither block concurrent applies nor see any of
        their effects — it serves the pinned epoch bit-for-bit."""
        for updater in ("sgd", "adagrad"):
            s = _row_shard(n=64, updater=updater)
            _add(s, np.arange(64), np.ones((64, 4)))
            expected = (np.asarray(s._data)[:64].copy())
            in_gather = threading.Event()
            unblock = threading.Event()
            orig = s._gather_rows

            def slow_gather(local, data=None, _orig=orig):
                in_gather.set()
                assert unblock.wait(10)
                return _orig(local, data=data)

            s._gather_rows = slow_gather
            got = {}

            def getter():
                got["rows"] = _get(s, np.arange(64))

            th = threading.Thread(target=getter)
            th.start()
            assert in_gather.wait(10)
            # applies must complete while the get is mid-gather
            appliers = [threading.Thread(
                target=_add, args=(s, np.arange(64), np.full((64, 4), i)))
                for i in range(1, 4)]
            for a in appliers:
                a.start()
            for a in appliers:
                a.join(timeout=10)
            assert not any(a.is_alive() for a in appliers), \
                "applies stalled behind an in-flight get"
            unblock.set()
            th.join(timeout=10)
            assert not th.is_alive()
            # epoch consistency: the reply is the PRE-apply snapshot
            assert np.array_equal(got["rows"], expected), updater
            # ...and the applies all landed
            final = _get(s, np.arange(64))
            if updater == "sgd":
                assert np.array_equal(
                    final, expected - np.full((64, 4), 6.0))

    def test_get_full_and_set_rows_respect_pins(self):
        s = _row_shard(updater="sgd")
        _add(s, [0], [[5, 5, 5, 5]])
        pin = s._pin_data()
        before = np.asarray(pin.data).copy()
        s.handle(svc.MSG_SET_ROWS, {"table": s.name},
                 [np.array([0], np.int64),
                  np.zeros((1, 4), np.float32)])
        assert np.array_equal(np.asarray(pin.data), before)
        s._release_data(pin)
        _, arrays = s.handle(svc.MSG_GET_FULL, {"table": s.name}, [])
        assert arrays[0][0, 0] == 0.0


# ---------------------------------------------------------------------- #
# sparse dirty bits: mask snapshot/clear atomic with the epoch pin
# ---------------------------------------------------------------------- #
class TestSparseDirtyAtomicity:
    def _sparse_get(self, s, ids, wid=0):
        _, (mask, rows) = s.handle(
            svc.MSG_GET_ROWS,
            {"table": s.name, "sparse": True, "worker_id": wid},
            [np.asarray(ids, np.int64)])
        return np.asarray(mask).astype(bool), np.asarray(rows)

    def test_two_thread_no_lost_update(self):
        """Regression for the set-then-lose window: a reader thread
        keeps a mirror from stale-only pulls while a writer thread
        applies adds. Whatever interleaving happened, a final pull must
        leave the mirror EXACTLY equal to the shard — a lost dirty bit
        would leave a stale row forever."""
        n, cols, rounds = 16, 4, 60
        s = _row_shard(n=n, cols=cols, updater="sgd", workers=1)
        mirror = np.zeros((n, cols), np.float32)
        ids = np.arange(n)
        stop = threading.Event()
        errs = []

        def reader():
            try:
                while not stop.is_set():
                    mask, rows = self._sparse_get(s, ids)
                    mirror[ids[mask]] = rows
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def writer():
            try:
                rng = np.random.default_rng(0)
                for i in range(rounds):
                    rid = rng.integers(0, n, 3)
                    _add(s, np.unique(rid),
                         rng.normal(size=(np.unique(rid).size, cols))
                         .astype(np.float32))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        rt = threading.Thread(target=reader)
        wt = threading.Thread(target=writer)
        rt.start()
        wt.start()
        wt.join(timeout=30)
        stop.set()
        rt.join(timeout=30)
        assert not errs, errs
        # one final settle pull, then the mirror must be exact
        mask, rows = self._sparse_get(s, ids)
        mirror[ids[mask]] = rows
        assert np.array_equal(mirror, np.asarray(s._data)[:n])

    def test_bit_set_after_pin_survives(self):
        """An add landing AFTER the mask clear + epoch pin re-dirties
        its rows: the reply carries the older epoch, and the set bit
        makes the next pull fetch the newer one — by construction, not
        by luck (the pin and the clear share one lock hold)."""
        s = _row_shard(n=8, updater="sgd", workers=1)
        _add(s, [3], [[1, 1, 1, 1]])
        mask, rows = self._sparse_get(s, np.arange(8))
        assert mask.all()          # first pull: everything stale
        _add(s, [3], [[1, 1, 1, 1]])
        mask2, rows2 = self._sparse_get(s, np.arange(8))
        assert mask2[3] and not mask2[0]
        assert rows2[0, 0] == -2.0


# ---------------------------------------------------------------------- #
# chunk-streamed replies + coalescer, end to end over real sockets
# ---------------------------------------------------------------------- #
def test_chunked_get_parity(two_ranks):
    """A chunk-streamed get (bf16 wire keeps the serve on the python
    plane under both fixture params) returns bit-identical bytes to the
    one-frame reply, for row gets AND the whole-table pull."""
    rows, cols = 64, 4
    vals = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
    t = AsyncMatrixTable(rows, cols, name="ckp", wire="bf16",
                         ctx=two_ranks[0])
    t2 = AsyncMatrixTable(rows, cols, name="ckp", wire="bf16",
                          ctx=two_ranks[1])
    t.set_rows(np.arange(rows), vals)
    plain = t.get_rows(np.arange(rows))
    full_plain = t.get()
    config.set_flag("get_chunk_rows", 8)
    chunked = t.get_rows(np.arange(rows))
    full_chunked = t.get()
    assert np.array_equal(plain, chunked)
    assert np.array_equal(full_plain, full_chunked)
    assert t2._shard._stat_chunks >= 8   # both pulls streamed


def test_chunked_get_with_out_buffer(two_ranks):
    rows, cols = 48, 4
    vals = np.random.default_rng(0).normal(size=(rows, cols)) \
        .astype(np.float32)
    t = AsyncMatrixTable(rows, cols, name="cko", wire="bf16",
                         ctx=two_ranks[0])
    AsyncMatrixTable(rows, cols, name="cko", wire="bf16",
                     ctx=two_ranks[1])
    t.set_rows(np.arange(rows), vals)
    ref = t.get_rows(np.arange(rows))
    config.set_flag("get_chunk_rows", 8)
    buf = np.empty((rows, cols), np.float32)
    got = t.get_rows(np.arange(rows), out=buf)
    assert got is buf and np.array_equal(buf, ref)


def test_chunked_failure_leaves_out_untouched(two_ranks):
    """A stream dying mid-way must raise with the caller's out= buffer
    UNTOUCHED — the sinks scatter into a private buffer that commits
    only on full success (a torn mix of two epochs in a caller's weight
    buffer would be silently trained on)."""
    rows, cols = 64, 4
    t = AsyncMatrixTable(rows, cols, name="ckf", wire="bf16",
                         ctx=two_ranks[0])
    t2 = AsyncMatrixTable(rows, cols, name="ckf", wire="bf16",
                          ctx=two_ranks[1])
    t.set_rows(np.arange(rows),
               np.ones((rows, cols), np.float32))
    config.set_flag("get_chunk_rows", 8)
    orig = t2._shard._chunked_reply

    def dies_mid_stream(rows_arr, w, chunk, tr):
        meta, reply = orig(rows_arr, w, chunk, tr)
        inner = reply.chunks

        def gen():
            yield next(inner)
            raise RuntimeError("stream died mid-way")

        reply.chunks = gen()
        return meta, reply

    t2._shard._chunked_reply = dies_mid_stream
    buf = np.full((rows, cols), -7.0, np.float32)
    with pytest.raises(svc.PSError):
        t.get_rows(np.arange(rows), out=buf)
    assert np.all(buf == -7.0), "caller's buffer was torn by the stream"
    # recovery: the unbroken path fills it
    t2._shard._chunked_reply = orig
    got = t.get_rows(np.arange(rows), out=buf)
    assert got is buf and np.all(buf[rows // 2:] == 1.0)


@pytest.fixture
def py_ranks(tmp_path):
    """2-rank world pinned to the pure-python plane: these tests inject
    delays into the python serve path, which the native C++ fast path
    would bypass."""
    from multiverso_tpu.ps.service import (FileRendezvous, PSContext,
                                           PSService)
    config.set_flag("ps_native", False)
    rdv = FileRendezvous(str(tmp_path / "rdv"))
    ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
    yield ctxs
    for c in ctxs:
        c.close()


def test_get_window_single_flight(py_ranks):
    """Concurrent gets to one owner collapse into single-flight batches:
    with the serve path slowed, 8 threads' gets reach the shard as far
    fewer serves, and every caller still gets its exact rows."""
    rows, cols = 64, 4
    vals = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
    t = AsyncMatrixTable(rows, cols, name="sf", get_window_ms=50.0,
                         ctx=py_ranks[0])
    t2 = AsyncMatrixTable(rows, cols, name="sf", get_window_ms=50.0,
                          ctx=py_ranks[1])
    t.set_rows(np.arange(rows), vals)
    t.get_rows([40])   # warm the conn
    orig = t2._shard._gather_rows

    def slow(local, data=None):
        time.sleep(0.08)
        return orig(local, data=data)

    t2._shard._gather_rows = slow
    served_before = t2._shard._stat_gets
    results = [None] * 8
    start = threading.Barrier(8)

    def getter(i):
        start.wait()
        results[i] = t.get_rows(np.array([40 + (i % 4)]))

    ths = [threading.Thread(target=getter, args=(i,)) for i in range(8)]
    for th in ths:
        th.start()
    for th in ths:
        th.join(timeout=30)
    assert not any(th.is_alive() for th in ths)
    for i in range(8):
        assert np.array_equal(results[i][0], vals[40 + (i % 4)]), i
    served = t2._shard._stat_gets - served_before
    assert served < 8, f"coalescer shipped {served} frames for 8 gets"
    assert Dashboard.get("table[sf].get_rows.fetches").count < 8


def test_get_window_serial_and_duplicates(py_ranks):
    """Serial gets through the window dispatch immediately and return
    exact values — including unsorted ids and duplicates (the re-expand
    path)."""
    rows, cols = 32, 3
    vals = np.random.default_rng(1).normal(size=(rows, cols)) \
        .astype(np.float32)
    t = AsyncMatrixTable(rows, cols, name="swd", get_window_ms=5.0,
                         ctx=py_ranks[0])
    AsyncMatrixTable(rows, cols, name="swd", get_window_ms=5.0,
                     ctx=py_ranks[1])
    t.set_rows(np.arange(rows), vals)
    ids = np.array([30, 17, 2, 17, 30])   # unsorted + duplicates
    got = t.get_rows(ids)
    assert np.array_equal(got, vals[ids])
    # cross-owner batch, unsorted
    ids2 = np.array([31, 1, 16, 0])
    assert np.array_equal(t.get_rows(ids2), vals[ids2])


def test_get_window_read_your_writes(py_ranks):
    """A windowed add followed by a coalesced get must observe the add
    (both fences compose: send-window flush, then the get joins a batch
    that reaches the conn after it)."""
    t = AsyncMatrixTable(16, 2, name="ryw", send_window_ms=50.0,
                         get_window_ms=50.0, ctx=py_ranks[0])
    AsyncMatrixTable(16, 2, name="ryw", send_window_ms=50.0,
                     get_window_ms=50.0, ctx=py_ranks[1])
    for i in range(4):
        t.add_rows_async([12], np.full((1, 2), 1.0, np.float32))
        got = t.get_rows([12])
        assert got[0, 0] == float(i + 1)


def test_apply_waves_dont_stall_behind_big_get_e2e(py_ranks):
    """End-to-end stress (python serve path): a big get from rank 0 is
    held mid-gather at the owner while ANOTHER client (rank 1's own
    worker plane, the local short-circuit — a different lane than the
    get's conn, whose FIFO necessarily queues same-conn traffic) keeps
    pushing add waves. The adds must complete while the get is stuck —
    the old locked path serialized them behind it — and the final state
    must equal the locked-path oracle bit-for-bit."""
    rows, cols = 256, 8
    t = AsyncMatrixTable(rows, cols, name="stall", ctx=py_ranks[0])
    t2 = AsyncMatrixTable(rows, cols, name="stall", ctx=py_ranks[1])
    rng = np.random.default_rng(2)
    init = rng.normal(size=(rows, cols)).astype(np.float32)
    t.set_rows(np.arange(rows), init)
    t.get_rows(np.arange(rows))   # warm
    in_gather = threading.Event()
    unblock = threading.Event()
    orig = t2._shard._gather_rows

    def slow(local, data=None):
        if local.size > 100:       # only the big get blocks
            in_gather.set()
            assert unblock.wait(20)
        return orig(local, data=data)

    t2._shard._gather_rows = slow
    got = {}

    def getter():
        got["rows"] = t.get_rows(np.arange(rows))

    th = threading.Thread(target=getter)
    th.start()
    assert in_gather.wait(20)
    # oracle: deltas applied with plain numpy in issue order — pushed by
    # rank 1 into its OWN rows [128, 256) while the get is mid-gather
    oracle = init.copy()
    deltas = [rng.normal(size=(rows // 2, cols)).astype(np.float32)
              for _ in range(3)]
    t_waves0 = time.monotonic()
    for d in deltas:
        t2.add_rows(np.arange(rows // 2, rows), d)
        oracle[rows // 2:] += d
    waves_s = time.monotonic() - t_waves0
    assert th.is_alive(), "the big get should still be held"
    assert waves_s < 10, "add waves stalled behind the in-flight get"
    unblock.set()
    th.join(timeout=30)
    assert not th.is_alive()
    # the held get served ONE consistent epoch: pre-wave bytes
    assert np.array_equal(got["rows"], init)
    # bit-parity with the oracle after the waves
    assert np.array_equal(t.get_rows(np.arange(rows)), oracle)


def test_apply_waves_with_big_get_native_parity(two_ranks):
    """Native-plane variant (no delay injection possible in C++): a big
    get racing add waves still returns SOME consistent epoch, and the
    final state matches the oracle bit-for-bit."""
    rows, cols = 512, 8
    t = AsyncMatrixTable(rows, cols, name="npar", ctx=two_ranks[0])
    AsyncMatrixTable(rows, cols, name="npar", ctx=two_ranks[1])
    rng = np.random.default_rng(3)
    init = rng.normal(size=(rows, cols)).astype(np.float32)
    t.set_rows(np.arange(rows), init)
    oracle = init.copy()
    errs = []
    stop = threading.Event()

    def getter():
        try:
            while not stop.is_set():
                t.get_rows(np.arange(rows))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    th = threading.Thread(target=getter)
    th.start()
    for _ in range(10):
        d = rng.normal(size=(rows, cols)).astype(np.float32)
        t.add_rows(np.arange(rows), d)
        oracle += d
    stop.set()
    th.join(timeout=30)
    assert not errs, errs
    assert np.array_equal(t.get_rows(np.arange(rows)), oracle)


# ---------------------------------------------------------------------- #
# get_rows(out=) shape validation (satellite fix)
# ---------------------------------------------------------------------- #
class TestGetRowsOutValidation:
    def test_wrong_shape_raises_even_when_reshapable(self, two_ranks):
        t = AsyncMatrixTable(10, 4, name="ov", ctx=two_ranks[0])
        AsyncMatrixTable(10, 4, name="ov", ctx=two_ranks[1])
        ids = np.array([1, 8])
        with pytest.raises(ValueError, match="shape"):
            t.get_rows(ids, out=np.empty((4, 2), np.float32))  # transposed
        with pytest.raises(ValueError, match="shape"):
            t.get_rows(ids, out=np.empty((3, 4), np.float32))  # wrong rows
        with pytest.raises(ValueError, match="shape"):
            t.get_rows(ids, out=np.empty(7, np.float32))   # wrong flat size
        # strided flat view: reshape would COPY and the fill would be
        # lost — must raise, not silently no-op
        with pytest.raises(ValueError, match="shape"):
            t.get_rows(ids, out=np.empty(16, np.float32)[::2])

    def test_flat_contiguous_out_still_fills(self, two_ranks):
        """The legacy reference-binding surface (handlers.py) passes flat
        buffers; a C-contiguous (n*cols,) out is unambiguous row-major
        and keeps working."""
        t = AsyncMatrixTable(10, 4, name="of", ctx=two_ranks[0])
        AsyncMatrixTable(10, 4, name="of", ctx=two_ranks[1])
        t.add_rows(np.arange(10),
                   np.arange(40, dtype=np.float32).reshape(10, 4))
        ids = np.array([1, 8])
        flat = np.empty(8, np.float32)
        got = t.get_rows(ids, out=flat)
        assert got is flat
        assert np.array_equal(flat.reshape(2, 4), t.get_rows(ids))

    def test_right_shape_wrong_dtype_still_fills(self, two_ranks):
        t = AsyncMatrixTable(10, 4, name="od", ctx=two_ranks[0])
        AsyncMatrixTable(10, 4, name="od", ctx=two_ranks[1])
        t.add_rows(np.arange(10),
                   np.arange(40, dtype=np.float32).reshape(10, 4))
        ids = np.array([2, 7])
        buf = np.empty((2, 4), np.float64)   # dtype fallback, shape OK
        got = t.get_rows(ids, out=buf)
        assert got is buf
        assert np.array_equal(buf, t.get_rows(ids).astype(np.float64))


# ---------------------------------------------------------------------- #
# multi-owner fan-out gets (ISSUE 15): chunk-eligible big gets across 4
# colocated shards — routed parts serve in-process (chunking is a
# network-overlap device, skipped for in-process destinations), and the
# result must stay bit-identical to the 1-shard oracle, out= included
# ---------------------------------------------------------------------- #
class TestFanoutChunkedGets:
    ROWS, DIM = 512, 8

    def _fill(self, t):
        rng = np.random.default_rng(5)
        vals = rng.normal(size=(self.ROWS, self.DIM)).astype(np.float32)
        t.add_rows(np.arange(self.ROWS), vals)
        return vals

    @pytest.mark.parametrize("plane", ["native", "python"])
    def test_chunk_flag_fanout_parity(self, tmp_path, plane):
        from multiverso_tpu.ps.service import (FileRendezvous,
                                               PSContext, PSService)
        config.set_flag("ps_native", plane == "native")
        config.set_flag("ps_fanout", True)
        config.set_flag("get_chunk_rows", 32)   # far below every part
        rdv = FileRendezvous(str(tmp_path / "rdv"))
        ctxs = [PSContext(r, 4, PSService(r, 4, rdv))
                for r in range(4)]
        tabs = [AsyncMatrixTable(self.ROWS, self.DIM, name="fc_t",
                                 ctx=c) for c in ctxs]
        want = self._fill(tabs[0])
        got = tabs[1].get_rows(np.arange(self.ROWS))
        np.testing.assert_array_equal(got, want)
        # out= commits only on full success, exact bytes
        out = np.empty((self.ROWS, self.DIM), np.float32)
        res = tabs[2].get_rows(np.arange(self.ROWS), out=out)
        assert res is out
        np.testing.assert_array_equal(out, want)
        # duplicate caller-order ids re-expand exactly
        ids = np.array([400, 3, 130, 3, 511, 400])
        np.testing.assert_array_equal(tabs[3].get_rows(ids),
                                      want[ids])
        for c in ctxs:
            c.close()

    def test_mixed_routed_and_socket_parts_chunk(self, tmp_path):
        """A world where only SOME owners are colocated: routed parts
        serve in-process, the non-colocated one still chunk-streams
        over its socket — one get, both transports, exact bytes."""
        from multiverso_tpu.ps import spmd
        from multiverso_tpu.ps.service import (FileRendezvous,
                                               PSContext, PSService)
        config.set_flag("ps_native", False)
        config.set_flag("ps_fanout", True)
        config.set_flag("get_chunk_rows", 32)
        rdv = FileRendezvous(str(tmp_path / "rdv"))
        ctxs = [PSContext(r, 4, PSService(r, 4, rdv))
                for r in range(4)]
        # hide rank 3 from the colocation registry BEFORE tables
        # resolve their routes: its traffic keeps the socket path
        spmd.unregister_service(ctxs[3].service)
        tabs = [AsyncMatrixTable(self.ROWS, self.DIM, name="mx_t",
                                 ctx=c) for c in ctxs]
        assert tabs[0]._routed_set == {1, 2}
        want = self._fill(tabs[0])
        got = tabs[0].get_rows(np.arange(self.ROWS))
        np.testing.assert_array_equal(got, want)
        for c in ctxs:
            c.close()
