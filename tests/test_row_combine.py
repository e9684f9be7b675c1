"""``ops/row_combine`` (ISSUE 28): a minibatch's duplicate update rows are
summed first, in float32, and the table scatter writes every distinct row
once and no other row at all; the shared-negatives fused epoch built on it
does what a plain sequence of steps with raw scatter-adds does. ISSUE 31:
the rows below ``HEAD`` take one dense add and the walk only the others,
wherever ``HEAD`` falls among the ids."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models import word2vec as w2v
from multiverso_tpu.ops import row_combine

ROWS, WIDTH = 1003, 12
TOL_F32 = 1e-4       # as benchmark/w2v_setup.py holds a float32 step


def _ids(kind: str, b: int, rng) -> np.ndarray:
    if kind == "distinct":
        return rng.choice(ROWS, b, replace=False).astype(np.int32)
    if kind == "one_row":
        return np.full(b, 77, np.int32)
    zipf = (rng.zipf(1.2, b) % ROWS).astype(np.int32)
    return np.sort(zipf) if kind == "sorted" else zipf


def _table(rng) -> np.ndarray:
    table = rng.normal(size=(ROWS, WIDTH)).astype(np.float32)
    table[::5] = -0.0     # a slot that wrote row + 0 would leave +0.0 here
    return table


# HEAD against a table of 1,003 rows and Zipf ids: the whole table (a table
# smaller than HEAD), no row, row 77 (the one_row case's) on either side,
# and a boundary that falls inside a chunk of the walk with duplicates on
# both sides of it
HEADS = [8192, 0, 77, 78, 5, 300]


@pytest.mark.parametrize("made_ahead", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,chunk", [(256, 256), (256, 64), (200, 64)])
@pytest.mark.parametrize("head", HEADS)
@pytest.mark.parametrize("kind", ["distinct", "one_row", "zipf", "sorted"])
def test_add_rows_is_the_scatter_add(kind, head, b, chunk, dtype, made_ahead,
                                     monkeypatch):
    monkeypatch.setattr(row_combine, "CHUNK", chunk)
    monkeypatch.setattr(row_combine, "HEAD", head)
    rng = np.random.default_rng(len(kind) * 1000 + b + chunk)
    ids, table = _ids(kind, b, rng), _table(rng)
    updates = jnp.asarray(rng.normal(size=(b, WIDTH)).astype(np.float32)
                          ).astype(dtype)
    plan = (row_combine.plan_rows(jnp.asarray(ids), ROWS) if made_ahead
            else None)
    # a fresh function: jit would hand back the trace of another HEAD
    got = np.asarray(jax.jit(lambda *a: row_combine.add_rows(*a))(
        jnp.asarray(table), jnp.asarray(ids), updates, plan))
    # whatever the updates' type, a run is summed in float32
    upd32 = np.asarray(updates.astype(jnp.float32))
    want = table.copy()
    np.add.at(want, ids, upd32)
    scale = np.abs(upd32).max() * max(np.bincount(ids).max(), 1)
    assert np.abs(got - want).max() <= 1e-6 * scale
    # every other row bit for bit, the -0.0 rows included
    others = np.setdiff1d(np.arange(ROWS), ids)
    assert others.size and np.signbit(table[others]).any()
    np.testing.assert_array_equal(got[others].view(np.uint32),
                                  table[others].view(np.uint32))
    if kind == "distinct":      # one term a row: no reassociation at all
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("head", HEADS)
@pytest.mark.parametrize("kind", ["distinct", "one_row", "zipf", "sorted"])
def test_plan_rows_names_every_distinct_row_once(kind, head, monkeypatch):
    monkeypatch.setattr(row_combine, "HEAD", head)
    rng = np.random.default_rng(7)
    ids = np.stack([_ids(kind, 96, rng) for _ in range(5)])
    plan = jax.jit(lambda ids: row_combine.plan_rows(ids, ROWS))(
        jnp.asarray(ids))
    run, uniq, count, heads, head_run = (np.asarray(a) for a in plan)
    assert run.shape == uniq.shape == ids.shape
    assert count.shape == heads.shape == (5,)
    assert head_run.shape == (5, min(head, ROWS))
    for k in range(5):
        distinct = np.unique(ids[k])
        assert count[k] == distinct.size
        # the head: the runs below HEAD, and each of its rows' run
        assert heads[k] == (distinct < head).sum()
        held = np.flatnonzero(head_run[k] < 96)
        np.testing.assert_array_equal(held, distinct[distinct < head])
        np.testing.assert_array_equal(uniq[k][head_run[k][held]], held)
        np.testing.assert_array_equal(uniq[k, :count[k]], distinct)
        # the pads: out of range and distinct, so the whole is sorted and
        # unique and a dropping scatter writes nothing for them
        assert (uniq[k, count[k]:] >= ROWS).all()
        assert (np.diff(uniq[k].astype(np.int64)) > 0).all()
        np.testing.assert_array_equal(uniq[k][run[k]], ids[k])


def test_combine_rows_sums_bfloat16_runs_in_float32():
    # 256 x 2^-9 on top of 1.0: a bfloat16 accumulator would stay at 1.0
    updates = jnp.asarray([1.0] + [2.0 ** -9] * 256, jnp.bfloat16)[:, None]
    ids = jnp.zeros(257, jnp.int32)
    sums = row_combine.combine_rows(updates, row_combine.plan_rows(ids, 4))
    assert sums.dtype == jnp.float32 and sums.shape == (257, 1)
    assert float(sums[0, 0]) == 1.5 and not np.asarray(sums[1:]).any()


def _raw_step(win, wout, c, x, nid, lr, nw):
    """``shared_neg_step`` as it was before ISSUE 28, in float32: the
    pairs' update rows go to the tables by raw duplicate scatter-adds."""
    v, up, un = win[c], wout[x], wout[nid]
    pos = jnp.sum(v * up, axis=-1)
    negs = v @ un.T
    gp = (1.0 - jax.nn.sigmoid(pos)) * lr
    gn = -jax.nn.sigmoid(negs) * (lr * nw)
    loss = (-jnp.mean(jax.nn.log_sigmoid(pos))
            - nw * jnp.mean(jnp.sum(jax.nn.log_sigmoid(-negs), axis=-1)))
    win = win.at[c].add(gp[:, None] * up + gn @ un)
    wout = wout.at[x].add(gp[:, None] * v)
    return win, wout.at[nid].add(gn.T @ v), loss


# heads: the whole table of 301 rows, and one it is larger than
@pytest.mark.parametrize("batch,chunk,head", [
    (64, 256, 8192), (192, 64, 8192), (64, 256, 40), (192, 64, 40)])
def test_fused_epoch_equals_sequential_raw_scatter_steps(batch, chunk, head,
                                                         monkeypatch):
    monkeypatch.setattr(row_combine, "CHUNK", chunk)
    monkeypatch.setattr(row_combine, "HEAD", head)
    vocab, dim, pool, batches = 300, 16, 8, 6
    cfg = w2v.W2VConfig(vocab, dim, negatives=4, shared_negatives=pool,
                        learning_rate=0.05)
    rng = np.random.default_rng(3)
    unigram = 1.0 / np.arange(1, vocab + 1)
    cs = (rng.zipf(1.3, (batches, batch)) % vocab).astype(np.int32)
    xs = (rng.zipf(1.3, (batches, batch)) % vocab).astype(np.int32)
    win0 = rng.uniform(-0.5, 0.5, (vocab + 1, dim)).astype(np.float32)
    wout0 = rng.uniform(-0.5, 0.5, (vocab + 1, dim)).astype(np.float32)
    lcg0 = w2v.init_lcg_state(pool, 1)
    epoch = w2v.make_fused_shared_epoch(cfg, unigram, jnp.float32)
    win, wout, loss, lcg, rows = epoch(
        jnp.asarray(win0), jnp.asarray(wout0), jnp.asarray(cs),
        jnp.asarray(xs), jnp.asarray(lcg0))
    # the same pools, from the sampler's own host arithmetic
    slots = w2v.build_negative_table(unigram, 1 << w2v.FUSED_TABLE_BITS)
    states = w2v.lcg_epoch_states(lcg0, batches)
    pools = slots[w2v.lcg_slots(states)]
    rwin, rwout, losses = jnp.asarray(win0), jnp.asarray(wout0), []
    step = jax.jit(_raw_step, static_argnums=(5, 6))
    for k in range(batches):
        rwin, rwout, l = step(rwin, rwout, cs[k], xs[k],
                              pools[k].astype(np.int32), 0.05, 4 / pool)
        losses.append(float(l))
    np.testing.assert_array_equal(np.asarray(lcg), states[-1])
    assert float(loss) == pytest.approx(np.mean(losses), rel=1e-6)
    for got, ref, old in ((win, rwin, win0), (wout, rwout, wout0)):
        delta = np.abs(np.asarray(ref) - old).max()
        assert delta > 0
        assert np.abs(np.asarray(got) - np.asarray(ref)).max() <= (
            TOL_F32 * delta)
    assert np.array_equal(np.asarray(win)[vocab], win0[vocab])  # scratch row
    distinct = [np.unique(r) for r in list(cs) + list(xs)]
    assert rows.tolist() == [sum(d.size for d in distinct),
                             sum((d < head).sum() for d in distinct)]
