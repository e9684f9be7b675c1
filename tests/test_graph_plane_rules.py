"""The graph plane holds the updater rules the host plane holds.

The cells spend their time in ``Table.functional_add`` (``dlrm-step``)
and ``MatrixTable.functional_add_rows`` (``we-psblock``): a jitted step
threads ``state`` through them and hands the result back with ``adopt``.
These tests hold that path to the host plane's ``add`` / ``add_rows`` on
a twin table, data and every leaf of updater state, bit for bit, for
every rule; to NumPy where ``chip_smoke.py`` keeps the rule in NumPy;
and to the row contract a caller relies on (scratch-row slots, the last
real row, the sorted-ids promise).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.updaters import AddOption

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the NumPy rules the chip run is held to: no third copy
from chip_smoke import _np_adagrad_rows, _np_default_rows  # noqa: E402

UPDATERS = ("sgd", "momentum_sgd", "adagrad", "adam", "ftrl")
ENTRIES = ("state", "program_state")
OPT = AddOption(momentum=0.9, learning_rate=0.05, rho=0.1)
ROWS, COLS = 37, 12


def _init(shards: int) -> None:
    """One device, or the whole test mesh (eight row shards)."""
    if shards == 1:
        mv.init(mesh=jax.sharding.Mesh(np.array(jax.devices()[:1]),
                                       ("table",)))
    else:
        mv.init()


def _twins(make, updater):
    first = np.random.default_rng(7).normal(
        size=(ROWS, COLS)).astype(np.float32)
    return [make(updater, f"{updater}_{k}", first) for k in ("host", "graph")]


def _matrix(updater, name, first):
    return mv.MatrixTable(ROWS, COLS, updater=updater, name=name, init=first)


def _through(table, entry, fn, *args):
    """One step of a jitted loop: take the table's state as ``entry``
    hands it out, run ``fn`` on it in a program, adopt the result."""
    if entry == "state":
        table.adopt(jax.jit(fn)(table.state, *args))
        return
    with table._dispatch_lock:      # program_state's contract
        table.adopt(jax.jit(fn, donate_argnums=0,
                            out_shardings=table.state_format)(
            table.program_state(), *args))


def _same(host, graph):
    np.testing.assert_array_equal(np.asarray(graph.raw()),
                                  np.asarray(host.raw()))
    want, got = host.state["ustate"], graph.state["ustate"]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _check_dense(updater, entry, shards):
    _init(shards)
    host, graph = _twins(_matrix, updater)
    assert graph.num_shards == shards
    rng = np.random.default_rng(11)
    for _ in range(2):
        delta = rng.normal(size=(ROWS, COLS)).astype(np.float32)
        host.add(delta, OPT)
        _through(graph, entry, graph.functional_add,
                 graph.pad_delta(jnp.asarray(delta)), OPT)
        _same(host, graph)
    np.testing.assert_array_equal(graph.get(), host.get())


def _check_rows(updater, entry, shards):
    _init(shards)
    host, graph = _twins(_matrix, updater)
    rng = np.random.default_rng(13)
    for _ in range(2):
        ids = rng.permutation(ROWS)[:9].astype(np.int32)
        vals = rng.normal(size=(9, COLS)).astype(np.float32)
        host.add_rows(ids, vals, OPT)
        # as a jitted step hands them over: in no order, unused slots on
        # the scratch row with zero values
        slots = np.concatenate([ids, [graph.scratch_row] * 3]).astype(
            np.int32)
        padded = np.concatenate([vals, np.zeros((3, COLS), np.float32)])
        _through(graph, entry, graph.functional_add_rows,
                 jnp.asarray(slots), jnp.asarray(padded), OPT)
        _same(host, graph)
    np.testing.assert_array_equal(graph.get(), host.get())


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("updater", UPDATERS)
def test_functional_add_then_adopt_is_add(updater, entry):
    _check_dense(updater, entry, shards=1)


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("updater", UPDATERS)
def test_functional_add_rows_then_adopt_is_add_rows(updater, entry):
    _check_rows(updater, entry, shards=1)


@pytest.mark.parametrize("updater", UPDATERS)
def test_pipelined_add_async_equals_the_rule_applied_in_order(updater):
    """Four ``add_async`` issued before any ``wait`` are four applies of
    the rule in issue order: data and every state leaf equal four
    ``functional_add`` + ``adopt`` on the twin, bit for bit (sgd too: the
    host plane sums no deltas before it applies them)."""
    _init(1)
    host, graph = _twins(_matrix, updater)
    rng = np.random.default_rng(17)
    deltas = [rng.normal(size=(ROWS, COLS)).astype(np.float32) * 10.0 ** k
              for k in (0, -3, 3, -6)]
    ids = [host.add_async(d, OPT) for d in deltas]
    for d in deltas:
        _through(graph, "state", graph.functional_add,
                 graph.pad_delta(jnp.asarray(d)), OPT)
    for i in ids:
        host.wait(i)
    _same(host, graph)
    np.testing.assert_array_equal(graph.get(), host.get())


@pytest.mark.parametrize("check", [_check_dense, _check_rows],
                         ids=["dense", "rows"])
@pytest.mark.parametrize("updater", ["sgd", "adagrad"])
def test_the_same_on_a_table_sharded_over_the_mesh(updater, check):
    check(updater, "program_state", shards=8)


@pytest.mark.parametrize("updater", ["adagrad", "default"])
def test_row_rule_is_the_numpy_rule(updater):
    _init(1)
    first = np.random.default_rng(7).normal(
        size=(ROWS, COLS)).astype(np.float32)
    t = _matrix(updater, f"np_{updater}", first)
    want, g_sqr = first.copy(), np.zeros_like(first)
    rng = np.random.default_rng(17)
    for _ in range(2):
        ids = rng.permutation(ROWS)[:9].astype(np.int32)
        vals = rng.normal(size=(9, COLS)).astype(np.float32)
        _through(t, "state", t.functional_add_rows, jnp.asarray(ids),
                 jnp.asarray(vals), OPT)
        if updater == "adagrad":
            _np_adagrad_rows(want, g_sqr, ids, vals, OPT.learning_rate,
                             OPT.rho)
        else:
            _np_default_rows(want, ids, vals)
    np.testing.assert_allclose(t.get(), want, rtol=1e-6, atol=1e-6)
    if updater == "adagrad":
        np.testing.assert_allclose(
            np.asarray(t.state["ustate"]["g_sqr"])[:ROWS], g_sqr, rtol=1e-6)


# ---------------------------------------------------------------------- #
# the row contract
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shards", [1, 8])
def test_scratch_row_slots_with_zero_values_change_nothing(shards):
    _init(shards)
    for updater in UPDATERS:
        t = _matrix(updater, f"scratch_{updater}", np.random.default_rng(
            7).normal(size=(ROWS, COLS)).astype(np.float32))
        # one real add first, so that no state leaf is all zeros
        _through(t, "state", t.functional_add_rows,
                 jnp.arange(ROWS, dtype=jnp.int32),
                 jnp.full((ROWS, COLS), 0.5, jnp.float32), OPT)
        before = jax.tree.map(np.asarray, t.state)
        _through(t, "state", t.functional_add_rows,
                 jnp.full((5,), t.scratch_row, jnp.int32),
                 jnp.zeros((5, COLS), jnp.float32), OPT)
        after = jax.tree.map(np.asarray, t.state)
        np.testing.assert_array_equal(after["data"][:ROWS],
                                      before["data"][:ROWS])
        for a, b in zip(jax.tree.leaves(after["ustate"]),
                        jax.tree.leaves(before["ustate"])):
            if a.shape == t.padded_shape:       # adam's step count moves
                np.testing.assert_array_equal(a[:ROWS], b[:ROWS])


@pytest.mark.parametrize("shards", [1, 8])
def test_the_last_real_row_is_reachable(shards):
    _init(shards)
    t = _matrix("sgd", "last", np.zeros((ROWS, COLS), np.float32))
    assert t.scratch_row >= ROWS and t.scratch_row == t.padded_shape[0] - 1
    _through(t, "state", t.functional_add_rows,
             jnp.asarray([ROWS - 1, t.scratch_row], jnp.int32),
             jnp.asarray(np.stack([np.ones(COLS), np.zeros(COLS)]),
                         jnp.float32), OPT)
    got = t.get()
    np.testing.assert_array_equal(got[ROWS - 1], -np.ones(COLS, np.float32))
    assert not got[:ROWS - 1].any()


@pytest.mark.parametrize("shards", [1, 8])
def test_sorted_ids_promise_changes_no_result(shards):
    _init(shards)
    promised, plain = _twins(_matrix, "adagrad")
    rng = np.random.default_rng(19)
    ids = np.sort(rng.permutation(ROWS)[:9]).astype(np.int32)
    # ascending, scratch-row slots last: it is the highest row
    slots = jnp.asarray(np.concatenate([ids, [plain.scratch_row] * 3]),
                        jnp.int32)
    vals = jnp.asarray(np.concatenate(
        [rng.normal(size=(9, COLS)), np.zeros((3, COLS))]), jnp.float32)
    for t, promise in ((promised, True), (plain, False)):
        _through(t, "state",
                 lambda s, i, v, o, p=promise, t=t: t.functional_add_rows(
                     s, i, v, o, sorted_ids=p), slots, vals, OPT)
    _same(promised, plain)
