"""API + ArrayTable/MatrixTable/KVTable behavior on an 8-device mesh.

Mirrors the reference integration harness semantics (SURVEY §4 tier 2:
Test/main.cpp TestKV/TestArray/TestMatrix) — correctness of Add/Get across
shards, sync semantics, updaters, and checkpoint Store/Load.
"""

import io

import jax
import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.tables.array_table import ArrayTableOption
from multiverso_tpu.tables.matrix_table import MatrixTableOption
from multiverso_tpu.updaters import AddOption


@pytest.fixture(autouse=True)
def _init():
    mv.init()
    yield
    mv.shutdown()


class TestTopology:
    def test_basic(self):
        assert mv.rank() == 0
        assert mv.size() == 1
        assert mv.num_servers() == 8  # 8 virtual devices
        assert mv.num_workers() == 1
        assert mv.mesh().size == 8
        mv.barrier()

    def test_create_table_option(self):
        t = mv.create_table(ArrayTableOption(100))
        assert t.size == 100
        m = mv.create_table(MatrixTableOption(10, 4))
        assert (m.num_row, m.num_col) == (10, 4)


class TestArrayTable:
    def test_add_get(self):
        # ref Test/main.cpp TestArray: delta accumulates across adds.
        t = mv.ArrayTable(1000)
        delta = np.arange(1000, dtype=np.float32)
        t.add(delta)
        t.add(delta)
        got = t.get()
        np.testing.assert_allclose(got, 2 * delta, rtol=1e-6)

    def test_sharding_layout(self):
        t = mv.ArrayTable(1000)
        # padded to a multiple of 8 shards, actually sharded over devices
        assert t.padded_shape[0] % 8 == 0
        assert len(t.raw().sharding.device_set) == 8

    def test_async_wait(self):
        t = mv.ArrayTable(64)
        ids = [t.add_async(np.ones(64, np.float32)) for _ in range(5)]
        for i in ids:
            t.wait(i)
        np.testing.assert_allclose(t.get(), 5.0)

    def test_reads_flush_queued_adds_even_under_dispatch_lock(self):
        """An add_async issued while the caller holds the dispatch lock
        (the fused WE path holds it from program_state to adopt) is in
        .state and in get() at once, and does not deadlock: the lock is
        reentrant and the add is dispatched before add_async returns."""
        t = mv.ArrayTable(32, updater="sgd")
        delta = np.ones(32, np.float32)
        with t._dispatch_lock:
            t.add_async(delta)
            host = np.asarray(t.state["data"]).reshape(-1)[:32]
            np.testing.assert_allclose(t.get(), -1.0)
        np.testing.assert_allclose(host, -1.0)
        np.testing.assert_allclose(t.get(), -1.0)

    def test_momentum_adds_do_not_coalesce(self):
        """Stateful updaters keep per-add sequencing (N sequential
        momentum applies != one summed apply)."""
        t = mv.ArrayTable(16, updater="momentum_sgd")
        opt = AddOption(momentum=0.5)
        for _ in range(3):
            t.wait(t.add_async(np.ones(t.shape, np.float32), opt))
        # sequential momentum: smooth=.5,.75,.875 -> data = -2.125
        np.testing.assert_allclose(t.get(), -2.125, rtol=1e-6)

    def test_get_out_buffer(self):
        t = mv.ArrayTable(10, init=np.arange(10, dtype=np.float32))
        out = np.zeros(10, np.float32)
        ret = t.get(out=out)
        assert ret is out
        np.testing.assert_allclose(out, np.arange(10))

    def test_int_table_uses_default_updater(self):
        t = mv.ArrayTable(16, dtype=np.int32, updater="sgd")
        assert t.updater.name == "default"
        t.add(np.ones(16, np.int32))
        np.testing.assert_array_equal(t.get(), 1)

    def test_init_value(self):
        init = np.full(32, 3.0, np.float32)
        t = mv.ArrayTable(32, init=init)
        np.testing.assert_allclose(t.get(), 3.0)

    def test_store_load_roundtrip(self):
        t = mv.ArrayTable(50, updater="adagrad")
        t.add(np.random.default_rng(0).normal(size=50).astype(np.float32),
              AddOption(learning_rate=0.1, rho=0.1))
        buf = io.BytesIO()
        t.store(buf)
        snapshot = t.get().copy()
        t.add(np.ones(50, np.float32))
        buf.seek(0)
        t.load(buf)
        np.testing.assert_allclose(t.get(), snapshot, rtol=1e-6)


class TestUpdaters:
    def test_sgd(self):
        t = mv.ArrayTable(8, updater="sgd",
                          init=np.full(8, 1.0, np.float32))
        t.add(np.full(8, 0.25, np.float32))
        np.testing.assert_allclose(t.get(), 0.75)

    def test_momentum(self):
        t = mv.ArrayTable(4, updater="momentum_sgd")
        opt = AddOption(momentum=0.5)
        t.add(np.ones(4, np.float32), opt)
        # smooth = 0.5*0 + 0.5*1 = 0.5 ; data = -0.5
        np.testing.assert_allclose(t.get(), -0.5)
        t.add(np.ones(4, np.float32), opt)
        # smooth = 0.5*0.5 + 0.5*1 = 0.75 ; data = -1.25
        np.testing.assert_allclose(t.get(), -1.25)

    def test_adagrad(self):
        t = mv.ArrayTable(4, updater="adagrad")
        opt = AddOption(learning_rate=1.0, rho=1.0)
        t.add(np.full(4, 2.0, np.float32), opt)
        # G = 4 ; step = 2/sqrt(4) = 1
        np.testing.assert_allclose(t.get(), -1.0, rtol=1e-5)

    def test_adam_moves_against_gradient(self):
        t = mv.ArrayTable(4, updater="adam")
        for _ in range(3):
            t.add(np.full(4, 1.0, np.float32), AddOption(learning_rate=0.1))
        assert np.all(t.get() < 0)

    def test_custom_updater_registration(self):
        class Doubling(mv.Updater):
            name = "doubling"

            def apply(self, data, state, delta, opt):
                return data + 2 * delta, state

        mv.register_updater("doubling", Doubling)
        t = mv.ArrayTable(4, updater="doubling")
        t.add(np.ones(4, np.float32))
        np.testing.assert_allclose(t.get(), 2.0)


class TestMatrixTable:
    def test_whole_table(self):
        m = mv.MatrixTable(12, 6)
        delta = np.arange(72, dtype=np.float32).reshape(12, 6)
        m.add(delta)
        np.testing.assert_allclose(m.get(), delta)

    def test_row_ops(self):
        # ref Test/main.cpp TestMatrix: row-batch get/add correctness.
        m = mv.MatrixTable(100, 8)
        ids = [3, 50, 99]
        vals = np.ones((3, 8), np.float32) * np.array([[1], [2], [3]],
                                                      np.float32)
        m.add_rows(ids, vals)
        got = m.get_rows(ids)
        np.testing.assert_allclose(got, vals)
        # untouched rows stay zero
        np.testing.assert_allclose(m.get_row(0), 0.0)
        full = m.get()
        np.testing.assert_allclose(full[50], 2.0)

    def test_duplicate_ids_accumulate(self):
        m = mv.MatrixTable(10, 4)
        m.add_rows([2, 2, 5], np.ones((3, 4), np.float32))
        np.testing.assert_allclose(m.get_row(2), 2.0)
        np.testing.assert_allclose(m.get_row(5), 1.0)

    def test_row_update_is_local_for_momentum(self):
        # Updater state of untouched rows must not decay (ref server applies
        # the updater only to received rows).
        m = mv.MatrixTable(10, 4, updater="momentum_sgd")
        opt = AddOption(momentum=0.5)
        m.add_rows([1], np.ones((1, 4), np.float32), opt)
        m.add_rows([2], np.ones((1, 4), np.float32), opt)
        # row 1 got exactly one momentum step: -0.5
        np.testing.assert_allclose(m.get_row(1), -0.5)
        np.testing.assert_allclose(m.get_row(2), -0.5)

    def test_random_init(self):
        m = mv.MatrixTable(20, 10, seed=42, init_scale=0.5)
        vals = m.get()
        assert np.all(np.abs(vals) <= 0.5)
        assert np.std(vals) > 0.05

    def test_out_of_range(self):
        m = mv.MatrixTable(10, 4)
        with pytest.raises(IndexError):
            m.get_rows([10])

    def test_large_row_batch_buckets(self):
        m = mv.MatrixTable(64, 4)
        ids = np.arange(33)
        vals = np.ones((33, 4), np.float32)
        m.add_rows(ids, vals)
        np.testing.assert_allclose(m.get_rows(ids), 1.0)


class TestKVTable:
    def test_add_get(self):
        # ref Test/main.cpp TestKV
        kv = mv.KVTable()
        kv.add([1, 5, 9], [10, 20, 30])
        kv.add([1], [5])
        assert kv[1] == 15
        assert kv.get([5, 9]) == {5: 20, 9: 30}
        assert kv.get()[1] == 15

    def test_store_load(self):
        kv = mv.KVTable()
        kv.add([7, 3], [1.0, 2.0])
        buf = io.BytesIO()
        kv.store(buf)
        kv2 = mv.KVTable()
        buf.seek(0)
        kv2.load(buf)
        assert kv2[7] == 1 and kv2[3] == 2


class TestAggregate:
    def test_single_process_identity(self):
        # ref Test/main.cpp TestAllreduce (-ma mode): with one worker,
        # MV_Aggregate is identity.
        data = np.arange(16, dtype=np.float32)
        out = mv.aggregate(data.copy())
        np.testing.assert_allclose(out, data)


class TestReviewRegressions:
    def test_get_rows_with_many_duplicates(self):
        # regression: duplicate-heavy get batch larger than padded_rows
        init = np.tile(np.arange(10, dtype=np.float32)[:, None], (1, 4))
        m = mv.MatrixTable(10, 4, init=init)
        ids = [3] * 20 + [7] * 5
        rows = m.get_rows(ids)
        assert rows.shape == (25, 4)
        np.testing.assert_allclose(rows[:20], 3.0)
        np.testing.assert_allclose(rows[20:], 7.0)

    def test_aggregate_noncontiguous_inplace(self):
        mat = np.arange(16, dtype=np.float32).reshape(4, 4)
        col = mat[:, 0]  # strided view
        out = mv.aggregate(col)
        np.testing.assert_allclose(mat[:, 0], [0, 4, 8, 12])
        assert out.base is mat or out is col


# ---------------------------------------------------------------------- #
# one Add path, one Get path (PR 46): what the dispatch lock and program
# order give, with no queue, cache or stamp between caller and device
# ---------------------------------------------------------------------- #
def _array(updater, name):
    return mv.ArrayTable(24, updater=updater, name=name)


def _matrix(updater, name):
    return mv.MatrixTable(6, 4, updater=updater, name=name)


@pytest.mark.parametrize("make", [_array, _matrix],
                         ids=["ArrayTable", "MatrixTable"])
def test_get_async_issued_before_a_donating_add_reads_the_values_before_it(
        make):
    t = make("sgd", "snap")
    one = np.ones(t.shape, np.float32)
    t.add(one)
    pending = t.get_async()
    for _ in range(3):              # each donates the buffer get_async read
        t.add_async(one)
    np.testing.assert_array_equal(t.read(pending), -one)
    np.testing.assert_array_equal(t.get(), -4 * one)


def test_a_table_starts_no_thread_and_defines_no_cache_flag():
    import threading

    from multiverso_tpu.table import Table
    from multiverso_tpu.utils import config

    before = set(threading.enumerate())
    t, m = _array("sgd", "quiet_a"), _matrix("sgd", "quiet_m")
    for table in (t, m):
        one = np.ones(table.shape, np.float32)
        ids = [table.add_async(one) for _ in range(4)]
        table.read(table.get_async())
        table.get()
        for i in ids:
            table.wait(i)
    m.get_rows([0, 3])
    started = set(threading.enumerate()) - before
    assert not started, sorted(th.name for th in started)
    assert not any(th.name.startswith("mv-add-")
                   for th in threading.enumerate())
    assert not config.has_flag("table_get_cache")
    assert not hasattr(Table, "version") and not hasattr(t, "version")


@pytest.mark.parametrize("updater", ["sgd", "adagrad"])
def test_store_after_unwaited_add_async_holds_the_add(updater):
    t, twin = _array(updater, "st_a"), _array(updater, "st_b")
    opt = AddOption(learning_rate=0.1, rho=0.1)
    rng = np.random.default_rng(3)
    for _ in range(3):
        t.add_async(rng.normal(size=t.shape).astype(np.float32), opt)
    buf = io.BytesIO()
    t.store(buf)                    # no wait between the adds and the store
    buf.seek(0)
    twin.load(buf)
    assert np.any(twin.get() != 0)
    np.testing.assert_array_equal(twin.get(), t.get())
    for a, b in zip(jax.tree.leaves(twin.state["ustate"]),
                    jax.tree.leaves(t.state["ustate"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
