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

    def test_async_adds_coalesce_into_one_apply(self):
        """Pipelined host adds on a stateless-linear table merge into one
        summed upload (transfers do not overlap on a slow link, so
        fewer transfers is the only pipelining lever): all queued entries
        share one completion token, and the sum is exact."""
        t = mv.ArrayTable(64, updater="sgd")
        base = t._m if hasattr(t, "_m") else t
        delta = np.full(64, 2.0, np.float32)
        # hold the dispatch lock so the applier can't run: all three adds
        # queue, then one drain applies them as one batch
        with base._dispatch_lock:
            mids = [base.add_async(delta.reshape(base.shape))
                    for _ in range(3)]
            assert base._addq_inflight == 3
        toks = [base.wait(m) for m in mids]
        assert toks[0] is toks[1] is toks[2]     # ONE merged apply
        np.testing.assert_allclose(t.get(), -6.0)   # sgd sign, exact sum

    def test_reads_flush_queued_adds_even_under_dispatch_lock(self):
        """Reading .state/get while holding the dispatch lock (the fused
        WE path does exactly this) must drain the queue inline, not
        deadlock against the applier thread."""
        t = mv.ArrayTable(32, updater="sgd")
        base = t._m if hasattr(t, "_m") else t
        delta = np.ones(32, np.float32)
        with base._dispatch_lock:
            base.add_async(delta.reshape(base.shape))
            st = base.state                     # flushes inline
            host = np.asarray(st["data"]).reshape(-1)[:32]
        np.testing.assert_allclose(host, -1.0)
        np.testing.assert_allclose(t.get(), -1.0)

    def test_momentum_adds_do_not_coalesce(self):
        """Stateful updaters must keep per-add sequencing (N sequential
        momentum applies != one summed apply)."""
        t = mv.ArrayTable(16, updater="momentum_sgd")
        base = t._m if hasattr(t, "_m") else t
        opt = AddOption(momentum=0.5)
        for _ in range(3):
            base.wait(base.add_async(np.ones(base.shape, np.float32), opt))
        assert base._addq_inflight == 0 and not base._addq
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


