"""Randomized differential test for the UNCOORDINATED plane: a long
random op sequence through two live PSContexts (riding the native C++
transport where built) must match a plain numpy model exactly — the
async twin of tests/test_table_fuzz.py, catching row-partitioning,
dedupe-in-batch, FIFO-per-owner, and reply-scatter edge cases that the
scripted tests don't reach.

Ordering contract exercised: all ops issue from ONE thread, and every
owner's traffic (including the self shard — a real loopback conn on the
native plane) is per-connection FIFO, so a get issued after an async
add must observe it.
"""

import numpy as np
import pytest

from multiverso_tpu.ps.service import FileRendezvous, PSContext, PSService
from multiverso_tpu.ps.tables import (AsyncArrayTable, AsyncKVTable,
                                      AsyncMatrixTable)


@pytest.fixture
def two_ranks(tmp_path):
    rdv = FileRendezvous(str(tmp_path / "rdv"))
    ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
    yield ctxs
    for c in ctxs:
        c.close()


def test_async_matrix_matches_numpy_model(two_ranks):
    rng = np.random.default_rng(7)
    rows, cols = 37, 5            # awkward split: ceil(37/2)=19 vs 18
    t = AsyncMatrixTable(rows, cols, name="fz_m", ctx=two_ranks[0])
    AsyncMatrixTable(rows, cols, name="fz_m", ctx=two_ranks[1])
    model = np.zeros((rows, cols), np.float32)
    pending = []
    for step in range(120):
        op = rng.choice(["add_rows", "add_rows_async", "get_rows",
                         "add_full", "get_full", "flush"])
        if op in ("add_rows", "add_rows_async"):
            k = int(rng.integers(1, 12))
            ids = rng.integers(0, rows, k)      # duplicates welcome
            vals = rng.normal(size=(k, cols)).astype(np.float32)
            if op == "add_rows":
                t.add_rows(ids, vals)
            else:
                pending.append(t.add_rows_async(ids, vals))
            np.add.at(model, ids, vals)
        elif op == "add_full":
            d = rng.normal(size=(rows, cols)).astype(np.float32)
            t.add(d)
            model += d
        elif op == "get_rows":
            k = int(rng.integers(1, 10))
            ids = np.unique(rng.integers(0, rows, k))
            np.testing.assert_allclose(t.get_rows(ids), model[ids],
                                       rtol=2e-5, atol=2e-4)
        elif op == "get_full":
            np.testing.assert_allclose(t.get(), model, rtol=2e-5,
                                       atol=2e-4)
        else:
            t.flush()
            pending.clear()
    t.flush()
    np.testing.assert_allclose(t.get(), model, rtol=2e-5, atol=2e-4)


def test_async_array_matches_numpy_model(two_ranks):
    rng = np.random.default_rng(11)
    size = 101
    t = AsyncArrayTable(size, name="fz_a", ctx=two_ranks[0])
    AsyncArrayTable(size, name="fz_a", ctx=two_ranks[1])
    model = np.zeros(size, np.float32)
    for step in range(80):
        op = rng.choice(["add", "add_async", "get"])
        if op in ("add", "add_async"):
            d = rng.normal(size=size).astype(np.float32)
            (t.add if op == "add" else t.add_async)(d)
            model += d
        else:
            np.testing.assert_allclose(t.get(), model, rtol=2e-5,
                                       atol=2e-4)
    t.flush()
    np.testing.assert_allclose(t.get(), model, rtol=2e-5, atol=2e-4)


def test_async_sparse_matrix_matches_numpy_model(two_ranks):
    """The stale-row protocol (C++-served dirty-bit GET) is an
    optimization, not a semantics change: get_rows_sparse must always
    equal the model's rows, for EITHER worker's cache, interleaved with
    adds from both ranks' table objects at random."""
    from multiverso_tpu.ps.tables import AsyncSparseMatrixTable
    rng = np.random.default_rng(23)
    rows, cols = 29, 3
    t0 = AsyncSparseMatrixTable(rows, cols, name="fz_s", ctx=two_ranks[0])
    t1 = AsyncSparseMatrixTable(rows, cols, name="fz_s", ctx=two_ranks[1])
    model = np.zeros((rows, cols), np.float32)
    for step in range(100):
        op = rng.choice(["add0", "add1", "sparse0", "sparse1", "plain"])
        if op in ("add0", "add1"):
            k = int(rng.integers(1, 8))
            ids = rng.integers(0, rows, k)
            vals = rng.normal(size=(k, cols)).astype(np.float32)
            (t0 if op == "add0" else t1).add_rows(ids, vals)
            np.add.at(model, ids, vals)
        elif op in ("sparse0", "sparse1"):
            t = t0 if op == "sparse0" else t1
            k = int(rng.integers(1, 10))
            ids = np.unique(rng.integers(0, rows, k))
            got = t.get_rows_sparse(ids)
            np.testing.assert_allclose(got, model[ids], rtol=2e-5,
                                       atol=2e-4)
        else:
            ids = np.unique(rng.integers(0, rows, 6))
            np.testing.assert_allclose(t0.get_rows(ids), model[ids],
                                       rtol=2e-5, atol=2e-4)
    # final full check from both workers' caches
    all_ids = np.arange(rows)
    np.testing.assert_allclose(t0.get_rows_sparse(all_ids), model,
                               rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(t1.get_rows_sparse(all_ids), model,
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("wire", ["none", "bf16"])
def test_send_window_bit_for_bit_parity(two_ranks, wire):
    """PR-2 acceptance: a windowed table fed a random interleaving of
    add_rows / add_rows_async / get_rows / flush / wait must be
    BIT-FOR-BIT identical to a window-off table fed the same sequence —
    across the plain wire AND the bf16 wire (both merge by exact
    disjoint concat)."""
    rng = np.random.default_rng(91 + len(wire))
    rows, cols = 37, 5
    tw = AsyncMatrixTable(rows, cols, name=f"wz_{wire}", wire=wire,
                          updater="default", send_window_ms=30.0,
                          ctx=two_ranks[0])
    AsyncMatrixTable(rows, cols, name=f"wz_{wire}", wire=wire,
                     updater="default", ctx=two_ranks[1])
    tr = AsyncMatrixTable(rows, cols, name=f"wr_{wire}", wire=wire,
                          updater="default", ctx=two_ranks[0])
    AsyncMatrixTable(rows, cols, name=f"wr_{wire}", wire=wire,
                     updater="default", ctx=two_ranks[1])
    assert tw._window is not None and tr._window is None
    pending = []
    for step in range(90):
        op = rng.choice(["add_rows", "add_rows_async", "get_rows",
                         "flush", "wait"])
        if op in ("add_rows", "add_rows_async"):
            k = int(rng.integers(1, 9))
            ids = rng.integers(0, rows, k)      # duplicates welcome
            vals = rng.normal(size=(k, cols)).astype(np.float32)
            if op == "add_rows":
                tw.add_rows(ids, vals)
                tr.add_rows(ids, vals)
            else:
                pending.append((tw.add_rows_async(ids, vals),
                                tr.add_rows_async(ids, vals)))
        elif op == "get_rows":
            k = int(rng.integers(1, 10))
            ids = rng.integers(0, rows, k)
            a, b = tw.get_rows(ids), tr.get_rows(ids)
            assert np.array_equal(a, b), f"step {step}: window diverged"
        elif op == "wait" and pending:
            mw, mr = pending.pop(rng.integers(len(pending)))
            tw.wait(mw)
            tr.wait(mr)
        else:
            tw.flush()
            tr.flush()
            pending.clear()
    tw.flush()
    tr.flush()
    assert np.array_equal(tw.get(), tr.get())


@pytest.mark.parametrize("updater", ["adagrad", "adam"])
def test_send_window_parity_stateful_updater(two_ranks, updater):
    """Same parity contract through STATEFUL server-side updaters.
    adagrad (row-local state) exercises the shard's wave apply — merged
    disjoint sub-ops in one jitted update must leave data AND optimizer
    state bit-identical to per-op applies. adam exercises the merge
    GATE: its global step counter advances once per apply, so windowed
    sub-ops must NOT merge (a merged window used to end with t=K/2 and
    visibly diverged parameters)."""
    from multiverso_tpu.updaters import AddOption
    rng = np.random.default_rng(17)
    rows, cols = 29, 4
    opt = AddOption(learning_rate=0.1, rho=0.05)
    tw = AsyncMatrixTable(rows, cols, name=f"w_{updater}", updater=updater,
                          send_window_ms=30.0, ctx=two_ranks[0])
    AsyncMatrixTable(rows, cols, name=f"w_{updater}", updater=updater,
                     ctx=two_ranks[1])
    tr = AsyncMatrixTable(rows, cols, name=f"r_{updater}", updater=updater,
                          ctx=two_ranks[0])
    AsyncMatrixTable(rows, cols, name=f"r_{updater}", updater=updater,
                     ctx=two_ranks[1])
    for step in range(40):
        k = int(rng.integers(1, 7))
        ids = rng.integers(0, rows, k)
        vals = rng.normal(size=(k, cols)).astype(np.float32)
        tw.add_rows_async(ids, vals, opt)
        tr.add_rows_async(ids, vals, opt)
        if step % 11 == 0:
            q = rng.integers(0, rows, 6)
            assert np.array_equal(tw.get_rows(q), tr.get_rows(q))
    tw.flush()
    tr.flush()
    assert np.array_equal(tw.get(), tr.get())


def test_async_kv_matches_dict_model(two_ranks):
    rng = np.random.default_rng(13)
    t = AsyncKVTable(name="fz_kv", ctx=two_ranks[0])
    AsyncKVTable(name="fz_kv", ctx=two_ranks[1])
    model = {}
    for step in range(60):
        if rng.random() < 0.7:
            keys = rng.integers(0, 40, rng.integers(1, 5)).tolist()
            vals = rng.normal(size=len(keys)).tolist()
            t.add(keys, vals)
            for k, v in zip(keys, vals):
                model[k] = model.get(k, 0.0) + v
        else:
            got = t.get()
            assert set(got) == set(model)
            for k, v in model.items():
                assert abs(got[k] - v) < 1e-3, (k, got[k], v)
    got = t.get()
    for k, v in model.items():
        assert abs(got[k] - v) < 1e-3
