"""``ops/row_combine`` (ISSUE 28): a minibatch's duplicate update rows are
summed first, in float32, and the table scatter writes every distinct row
once and no other row at all; the shared-negatives fused epoch built on it
does what a plain sequence of steps with raw scatter-adds does. ISSUE 31:
the rows below ``HEAD`` take one dense add and the walk only the others,
wherever ``HEAD`` falls among the ids. ISSUE 36: on row shards the head is
the first rows of every shard, a shard walks its own rows alone, and words
are dealt round the shards. ISSUE 38: a row-sharded table is read by the
shards that own the rows (``take_rows``), which is ``jnp.take`` bit for
bit whatever the ids. ISSUE 40: the same at a PS block's sizes (a bucket
of 2^19 rows and the dummy row, 8,192 and 6 x 8,192 update rows), and at
8,192 update rows the program ``add_rows`` lowers to is the one it was.
ISSUE 45: where the table is float32 and whole lanes wide the walk is a
Pallas tile read-modify-write, bit for bit XLA's walk; at width 300 and on
row shards the jaxpr is the parent's."""

import re

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


BLOCK_ROWS = 2 ** 19 + 1      # a PS block's bucket and its dummy row


@pytest.mark.parametrize("made_ahead", [True, False])
@pytest.mark.parametrize("b", [8192, 6 * 8192])
@pytest.mark.parametrize("kind", ["block", "padded"])
def test_add_rows_at_a_blocks_sizes_is_the_scatter_add(kind, b, made_ahead):
    """Update rows as a block's minibatch names them: local rows that
    keep the rank order of the words, so a Zipf law over the bucket, and
    for a padded minibatch the dummy row alone."""
    rng = np.random.default_rng(b)
    ids = (np.full(b, BLOCK_ROWS - 1) if kind == "padded"
           else rng.zipf(1.1, b) % (BLOCK_ROWS - 1)).astype(np.int32)
    table = rng.normal(size=(BLOCK_ROWS, 4)).astype(np.float32)
    table[::5] = -0.0
    updates = rng.normal(size=(b, 4)).astype(np.float32)
    plan = (row_combine.plan_rows(jnp.asarray(ids), BLOCK_ROWS)
            if made_ahead else None)
    got = np.asarray(jax.jit(row_combine.add_rows)(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(updates), plan))
    want = table.copy()
    np.add.at(want, ids, updates)
    scale = np.abs(updates).max() * np.bincount(ids).max()
    assert np.abs(got - want).max() <= 1e-6 * scale
    others = np.setdiff1d(np.arange(BLOCK_ROWS), ids)
    np.testing.assert_array_equal(got[others].view(np.uint32),
                                  table[others].view(np.uint32))
    distinct = np.unique(ids)
    if kind == "block":     # rows on both sides of the head, repeats
        assert (distinct < row_combine.HEAD).any()
        assert (distinct >= row_combine.HEAD).any() and distinct.size < b
    if made_ahead:
        walk = -(-(distinct >= row_combine.HEAD).sum() // row_combine.CHUNK)
        assert row_combine.plan_counts(plan).tolist() == [
            distinct.size, (distinct < row_combine.HEAD).sum(),
            walk * row_combine.CHUNK, 0]


def _costly_ops(text: str):
    """Of a lowered program, in order: every sort and loop, and every
    scatter with the types it takes and gives. These are what a v5e's
    time in ``add_rows`` goes to (PERF.md, PRs 28 and 31)."""
    found = re.findall(
        r'stablehlo\.(sort|while)\b|stablehlo\.scatter"\(.*?\n\s*\}\) : '
        r'(\(.*?\) -> \S+)', text, flags=re.S)
    return [name or types for name, types in found]


@pytest.mark.parametrize("made_ahead", [True, False])
def test_add_rows_at_8192_update_rows_lowers_to_the_program_it_was(
        made_ahead):
    """What ``we-fused`` and ``we-fused-x4`` run a minibatch (ISSUE 40
    puts the same program under a PS block's scan): one sum of the 8,192
    update rows into ``[8,192 + CHUNK]`` rows, the head's one dense add,
    and ONE loop whose body scatters a chunk of 256 rows; a plan made
    ahead leaves no sort in it, one made in the step its three."""
    table = jax.ShapeDtypeStruct((1_800_001, 300), jnp.float32)
    ids = jax.ShapeDtypeStruct((8192,), jnp.int32)
    updates = jax.ShapeDtypeStruct((8192, 300), jnp.float32)
    plan = (jax.eval_shape(lambda i: row_combine.plan_rows(i, 1_800_001),
                           ids) if made_ahead else None)
    ops = _costly_ops(jax.jit(row_combine.add_rows).lower(
        table, ids, updates, plan).as_text())
    t, wide = "tensor<1800001x300xf32>", "x300xf32>"
    writes = [
        f"(tensor<8448{wide}, tensor<8192x1xi32>, tensor<8192{wide}) "
        f"-> tensor<8448{wide}",
        f"({t}, tensor<1xi32>, tensor<8192{wide}) -> {t}",
        "while",
        f"({t}, tensor<256x1xi32>, tensor<256{wide}) -> {t}"]
    if made_ahead:
        assert ops == writes
    else:
        # the plan's three sorts, its head_run scatter and its
        # searchsorted's loop come first and change none of the writes
        assert [o for o in ops if o.endswith(wide)] == [
            w for w in writes if w != "while"]
        assert ops.count("sort") == 3 and ops.count("while") == 2


@pytest.mark.parametrize("rows,shards,parent", [
    (1_800_001, 1, "84e0ca7b39d8686d"), (3_000_004, 4, "01e37aa5fcd0f4c6")])
def test_add_rows_at_width_300_traces_to_the_jaxpr_it_was(rows, shards,
                                                          parent):
    """ISSUE 45 chooses the walk by what the table is. A table 300 wide
    (``we-fused``: one shard; ``we-fused-x4``: four row shards) keeps
    XLA's walk, and its ``add_rows`` is to the letter the jaxpr of the
    commit before (digests taken there, jax 0.9.0) wherever the kernel
    could run."""
    import hashlib
    sharding = None if shards == 1 else _row_sharding(shards)
    table = jax.ShapeDtypeStruct((rows, 300), jnp.float32)
    ids = jax.ShapeDtypeStruct((8192,), jnp.int32)
    updates = jax.ShapeDtypeStruct((8192, 300), jnp.float32)
    plan = jax.eval_shape(
        lambda i: row_combine.plan_rows(i, rows, shards), ids)

    def traced():
        return str(jax.make_jaxpr(lambda t, i, u, p: row_combine.add_rows(
            t, i, u, p, sharding))(table, ids, updates, plan))

    text = traced()
    assert "pallas_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == parent
    with pytest.MonkeyPatch.context() as on_a_tpu:
        on_a_tpu.setattr(row_combine, "_kernel_interpret", lambda: False)
        assert traced() == text


def _padded(rows: int, shards: int) -> int:
    """Rows of a table of ``rows`` rows in ``shards`` row shards: as it is
    on one, else with a spare row and padded to equal shards."""
    return rows if shards == 1 else -(-(rows + 1) // shards) * shards


# one shard: the plan of a whole table, as it was before ISSUE 36
@pytest.mark.parametrize("shards", [1, 2, 4, 8])
@pytest.mark.parametrize("head", HEADS)
@pytest.mark.parametrize("kind", ["distinct", "one_row", "zipf", "sorted"])
def test_plan_rows_names_every_distinct_row_once(kind, head, shards,
                                                 monkeypatch):
    monkeypatch.setattr(row_combine, "HEAD", head)
    rng = np.random.default_rng(7)
    ids = np.stack([_ids(kind, 96, rng) for _ in range(5)])
    rows = _padded(ROWS, shards)
    per = rows // shards
    part = min(head // shards, per)         # head rows a shard
    plan = jax.jit(lambda ids: row_combine.plan_rows(ids, rows, shards))(
        jnp.asarray(ids))
    run, uniq, count, heads, ends, head_run, place = (np.asarray(a)
                                                      for a in plan)
    assert run.shape == uniq.shape == ids.shape
    # ISSUE 38: where take_rows finds each update row among the rows the
    # shards hand round; one shard hands nothing round
    cap = row_combine.gather_cap(96, shards)
    assert place.shape == ((5, 96) if shards > 1 else (5, 0))
    assert cap == (3 * 96 // (4 * shards) if shards > 1 else 96)
    assert count.shape == (5,) and heads.shape == ends.shape == (5, shards)
    assert head_run.shape == (5, shards * part)
    for k in range(5):
        distinct = np.unique(ids[k])
        assert count[k] == distinct.size
        np.testing.assert_array_equal(uniq[k, :count[k]], distinct)
        # the pads: out of range and distinct, so the whole is sorted and
        # unique and a dropping scatter writes nothing for them
        assert (uniq[k, count[k]:] >= rows).all()
        assert (np.diff(uniq[k].astype(np.int64)) > 0).all()
        np.testing.assert_array_equal(uniq[k][run[k]], ids[k])
        named = []
        for s in range(shards):
            mine = distinct[distinct // per == s]
            in_head = mine % per < part
            # the shard's head: each of its rows' run
            runs = head_run[k, s * part:(s + 1) * part]
            held = np.flatnonzero(runs < 96)
            np.testing.assert_array_equal(held, mine[in_head] - s * per)
            np.testing.assert_array_equal(uniq[k][runs[held]], mine[in_head])
            # its walk: the slots of its other rows, and no other's
            np.testing.assert_array_equal(uniq[k, heads[k, s]:ends[k, s]],
                                          mine[~in_head])
            named += [mine[in_head], uniq[k, heads[k, s]:ends[k, s]]]
            if shards > 1:
                # its rows in order, cap of them a round, after the
                # shards before it in the round
                begin = ends[k, s - 1] if s else 0
                np.testing.assert_array_equal(uniq[k, begin:ends[k, s]], mine)
                at = np.arange(mine.size)
                np.testing.assert_array_equal(
                    place[k][np.isin(ids[k], mine)],
                    ((at // cap * shards + s) * cap + at % cap)[
                        np.searchsorted(mine, ids[k][np.isin(ids[k], mine)])])
        # every distinct row once, by its owner's head or its owner's walk
        np.testing.assert_array_equal(np.sort(np.concatenate(named)),
                                      distinct)
        assert ends[k, -1] == count[k]
        if shards == 1:     # the runs below HEAD, then the walk to the last
            assert heads[k, 0] == (distinct < head).sum()
    # and what the writes are handed: distinct rows, the heads' share, and
    # every shard's walk in whole chunks (of 96 slots here: B < CHUNK)
    counts = np.asarray(row_combine.plan_counts(plan))
    assert counts.shape == (3 + shards,)
    tails = ends - heads
    assert counts[0] == count.sum()
    assert counts[1] == count.sum() - tails.sum()
    np.testing.assert_array_equal(counts[2:2 + shards],
                                  (-(-tails // 96) * 96).sum(axis=0))
    # and the reads (ISSUE 38): every shard cap slots a round, as many
    # rounds as the busiest shard's distinct rows need, one at the least;
    # counted are those past the first
    owned = np.diff(np.concatenate([np.zeros((5, 1), int), ends], axis=1))
    rounds = np.maximum(-(-owned.max(axis=1) // cap), 1)
    assert counts[-1] == (rounds - 1).sum()
    if kind == "one_row":
        assert (rounds == 1).all()
    if kind == "distinct" and shards > 1:
        assert (rounds > 1).any()   # 96 distinct rows are never dealt even


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_striped_rows_deal_the_ranks_round_the_shards(shards):
    words = 1003
    rows = row_combine.striped_table_rows(words, shards)
    per = _padded(rows, shards) // shards
    w = np.arange(words)
    r = row_combine.striped_row(w, shards, per)
    # a table of that many rows holds every word, each in a row of its own
    assert r.max() < rows and np.unique(r).size == words
    np.testing.assert_array_equal(row_combine.striped_word(r, shards, per), w)
    # rank w lives in shard w % shards, the shard's (w // shards)-th row
    np.testing.assert_array_equal(r // per, w % shards)
    np.testing.assert_array_equal(r % per, w // shards)
    if shards == 1:
        assert rows == words and r is w


# row-sharded over the CPU's virtual devices: every shard writes its own
# rows in one shard_map, and the table is the one a plain scatter-add leaves
@pytest.mark.parametrize("shards", [2, 4, 8])
@pytest.mark.parametrize("head", [8192, 0, 77, 300])
@pytest.mark.parametrize("kind", ["distinct", "zipf"])
def test_add_rows_on_row_shards_is_the_scatter_add(kind, head, shards,
                                                   monkeypatch):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    monkeypatch.setattr(row_combine, "CHUNK", 64)
    monkeypatch.setattr(row_combine, "HEAD", head)
    rng = np.random.default_rng(shards)
    rows = _padded(ROWS, shards)
    ids = _ids(kind, 200, rng)
    table = np.concatenate(
        [_table(rng), np.zeros((rows - ROWS, WIDTH), np.float32)])
    updates = rng.normal(size=(200, WIDTH)).astype(np.float32)
    sharding = NamedSharding(
        Mesh(np.asarray(jax.devices()[:shards]), ("mv",)),
        PartitionSpec("mv", None))
    got = jax.jit(lambda t, i, u: row_combine.add_rows(
        t, i, u, None, sharding), out_shardings=sharding)(
            jax.device_put(table, sharding), jnp.asarray(ids),
            jnp.asarray(updates))
    one = jax.jit(lambda t, i, u: row_combine.add_rows(t, i, u))(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(updates))
    # the same float32 sums to the same rows: one device's table, bit for
    # bit, the -0.0 rows that no update names included
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(one).view(np.uint32))
    want = table.copy()
    np.add.at(want, ids, updates)
    assert np.abs(np.asarray(got) - want).max() <= 1e-6 * (
        np.abs(updates).max() * np.bincount(ids).max())


def _row_sharding(shards: int):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    return NamedSharding(Mesh(np.asarray(jax.devices()[:shards]), ("mv",)),
                         PartitionSpec("mv", None))


def _take_ids(kind: str, b: int, rows: int, shards: int, rng) -> np.ndarray:
    """Ids for ``take_rows`` on ``shards`` row shards of ``rows // shards``
    rows: dealt evenly; a few rows many times; all in the last shard (more
    distinct rows than a round holds); all in the shards' heads; and the
    table's last row among them."""
    per = rows // shards
    if kind == "even":
        return rng.integers(0, rows, b).astype(np.int32)
    if kind == "duplicates":
        return rng.choice(rng.integers(0, rows, 5), b).astype(np.int32)
    if kind == "one_shard":
        return (rows - 1 - rng.choice(per, b, replace=False)).astype(np.int32)
    if kind == "head":
        return (rng.integers(0, shards, b) * per
                + rng.integers(0, 4, b)).astype(np.int32)
    ids = rng.integers(0, rows, b).astype(np.int32)
    ids[::7] = rows - 1
    return ids


@pytest.mark.parametrize("made_ahead", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("kind", ["even", "duplicates", "one_shard", "head",
                                  "last_row"])
def test_take_rows_is_jnp_take(kind, shards, dtype, made_ahead, monkeypatch):
    """ISSUE 38: the rows a shard owns, read by it alone and handed round,
    are ``jnp.take``'s rows bit for bit in the ids' own order, the -0.0
    rows and the rounds past the first included."""
    monkeypatch.setattr(row_combine, "HEAD", 16)
    rng = np.random.default_rng(len(kind) * 10 + shards)
    rows, b = _padded(ROWS, shards), 96
    table = np.concatenate(
        [_table(rng), np.zeros((rows - ROWS, WIDTH), np.float32)])
    ids = _take_ids(kind, b, rows, shards, rng)
    sharding = _row_sharding(shards)
    plan = (jax.jit(lambda i: row_combine.plan_rows(i, rows, shards))(ids)
            if made_ahead else None)
    got = jax.jit(lambda t, i, p: row_combine.take_rows(
        t, i, p, sharding, dtype))(
            jax.device_put(table, sharding), jnp.asarray(ids), plan)
    want = jnp.asarray(table[ids]).astype(dtype)
    assert got.dtype == want.dtype and got.shape == (b, WIDTH)
    bits = np.uint32 if dtype == jnp.float32 else np.uint16
    np.testing.assert_array_equal(np.asarray(got).view(bits),
                                  np.asarray(want).view(bits))
    assert np.signbit(np.asarray(want, np.float32)).any()
    # how many rounds the ids took, from the plan's own counts
    counts = np.asarray(row_combine.plan_counts(
        row_combine.plan_rows(jnp.asarray(ids), rows, shards)))
    cap = row_combine.gather_cap(b, shards)
    rounds = counts[-1] + 1
    owned = np.bincount(np.unique(ids) // (rows // shards), minlength=shards)
    assert rounds == max(-(-owned.max() // cap), 1)
    if kind == "one_shard" and shards > 1:
        # 96 distinct rows of one shard, three quarters of 96 // S a round
        assert rounds == -(-96 // cap) > shards
    if kind in ("duplicates", "head") or shards == 1:
        assert rounds == 1


def test_take_rows_on_one_shard_is_the_program_it_was():
    """On a whole table ``take_rows`` is ``jnp.take`` and a cast, the
    lowered text letter for letter: with or without a plan, and whether
    the table has no sharding or one over a mesh of one device."""
    table = jnp.zeros((ROWS, WIDTH), jnp.float32)
    ids = jnp.zeros(96, jnp.int32)
    plan = row_combine.plan_rows(ids, ROWS)
    was = jax.jit(lambda t, i: jnp.take(t, i, axis=0).astype(
        jnp.bfloat16)).lower(table, ids).as_text()
    for p in (plan, None):
        for sharding in (None, _row_sharding(1)):
            now = jax.jit(lambda t, i: row_combine.take_rows(
                t, i, p, sharding, jnp.bfloat16)).lower(table, ids).as_text()
            assert now == was


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
    chunk = min(chunk, batch)
    assert rows.tolist() == [
        sum(d.size for d in distinct),
        sum((d < head).sum() for d in distinct),
        # one shard: the walks' slots, the pads of a last chunk included
        sum(-(-(d >= head).sum() // chunk) * chunk for d in distinct),
        # and the reads' rounds past the first: one shard takes none
        0]


# ---------------------------------------------------------------------- #
# ISSUE 45: on a float32 table whole lanes wide the walk is a Pallas tile
# read-modify-write (here in the interpreter), bit for bit XLA's walk
# ---------------------------------------------------------------------- #
KERNEL_HEAD = 16      # a head that leaves most of a small table to the walk


def _kernel_ids(kind: str, b: int, rows: int, rng) -> np.ndarray:
    """Ids past the head unless the kind says otherwise."""
    past = np.arange(KERNEL_HEAD, rows)
    if kind == "distinct":
        return rng.choice(rows, b, replace=False)
    if kind == "one_row":
        return np.full(b, 77)
    if kind == "zipf":
        return rng.zipf(1.2, b) % rows
    if kind == "one_tile":          # several rows of one 8-row tile
        return 8 * 50 + rng.choice([1, 2, 5, 6], b)
    if kind == "tile_edges":        # a tile's first and last line
        return rng.choice(past[(past % 8 == 0) | (past % 8 == 7)], b)
    if kind == "last_row":          # among others, the table's last
        return np.where(rng.random(b) < 0.2, rows - 1, rng.choice(past, b))
    assert kind == "all_head"       # an empty walk
    return rng.integers(0, KERNEL_HEAD, b)


@pytest.fixture
def walked(monkeypatch):
    """``add_rows`` jitted afresh under one way of walking: the tile
    kernel in the interpreter, or XLA's scatter."""
    monkeypatch.setattr(row_combine, "HEAD", KERNEL_HEAD)

    def walk(kernel: bool):
        monkeypatch.setattr(row_combine, "_kernel_interpret",
                            lambda: True if kernel else None)
        return jax.jit(lambda *a: row_combine.add_rows(*a))

    return walk


@pytest.mark.parametrize("made_ahead", [True, False])
@pytest.mark.parametrize("rows,width", [(1003, 128), (1000, 384),
                                        (1001, 384), (1008, 128)])
@pytest.mark.parametrize("kind", ["distinct", "one_row", "zipf", "one_tile",
                                  "tile_edges", "last_row", "all_head"])
def test_the_tile_kernel_writes_what_the_scatter_walk_writes(
        kind, rows, width, made_ahead, walked):
    rng = np.random.default_rng(len(kind) * 100 + rows + width)
    b = 256
    ids = _kernel_ids(kind, b, rows, rng).astype(np.int32)
    table = rng.normal(size=(rows, width)).astype(np.float32)
    table[::5] = -0.0
    updates = rng.normal(size=(b, width)).astype(np.float32)
    plan = (row_combine.plan_rows(jnp.asarray(ids), rows) if made_ahead
            else None)
    args = (jnp.asarray(table), jnp.asarray(ids), jnp.asarray(updates), plan)
    assert row_combine.tile_walk(args[0]) is None     # off the chip
    scatter = np.asarray(walked(False)(*args))
    kernel = walked(True)
    assert "pallas_call" in str(jax.make_jaxpr(kernel)(*args))
    got = np.asarray(kernel(*args))
    # XLA's walk bit for bit: the same float32 sums added once to a row
    np.testing.assert_array_equal(got.view(np.uint32),
                                  scatter.view(np.uint32))
    want = table.copy()
    np.add.at(want, ids, updates)
    assert np.abs(got - want).max() <= 1e-6 * (
        np.abs(updates).max() * np.bincount(ids).max())
    # every row no update names, to the bit: the other lines of a tile
    # that was written, -0.0 rows among them
    others = np.setdiff1d(np.arange(rows), ids)
    assert np.signbit(table[others]).any()
    np.testing.assert_array_equal(got[others].view(np.uint32),
                                  table[others].view(np.uint32))
    named = np.unique(ids)
    assert (named >= KERNEL_HEAD).any() == (kind != "all_head")
    if kind == "one_tile":
        assert named.size > 1 and np.unique(named // 8).size == 1
    if kind == "last_row":
        assert rows - 1 in named


def test_the_tile_kernel_takes_straight_rounds_on_a_sparse_walk(walked):
    """Enough single-row tiles that most rounds are the unrolled ones."""
    rng = np.random.default_rng(45)
    rows, width, b = 20_001, 128, 512
    ids = rng.choice(rows, b, replace=False).astype(np.int32)
    assert np.unique(ids // 8).size > 8 * row_combine.ROUND
    table = rng.normal(size=(rows, width)).astype(np.float32)
    updates = rng.normal(size=(b, width)).astype(np.float32)
    args = (jnp.asarray(table), jnp.asarray(ids), jnp.asarray(updates))
    np.testing.assert_array_equal(
        np.asarray(walked(True)(*args)).view(np.uint32),
        np.asarray(walked(False)(*args)).view(np.uint32))


@pytest.mark.parametrize("rows", [1003, 1000, 5])
def test_the_tile_kernel_on_an_empty_plan_writes_nothing(rows):
    """No slot between start and end: every bit of the table stays, the
    rows of a last part-tile (and a table of under one tile) too."""
    rng = np.random.default_rng(rows)
    table = rng.normal(size=(rows, 128)).astype(np.float32)
    table[::3] = -0.0
    uniq = jnp.arange(rows, rows + 320, dtype=jnp.int32)      # all pads
    sums = jnp.asarray(rng.normal(size=(320, 128)).astype(np.float32))
    for at in (0, 17):
        got = jax.jit(lambda t: row_combine._walk_tiles(
            t, uniq, sums, jnp.int32(at), jnp.int32(at), True))(
                jnp.asarray(table))
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                      table.view(np.uint32))


def test_the_tile_kernel_waits_once_for_every_copy_it_starts():
    """The plain interpreter lets a kernel wait for a DMA it never
    started; the chip hangs on it. Mosaic's own interpreter keeps the
    semaphores as the chip does, so the kernel runs under it, in a
    process of its own with a time limit: walks that end inside every
    kind of round (none, one tile, a round and a bank to the tile, the
    straight rounds, several rows a tile) all come back, and right."""
    import subprocess
    import sys
    code = """
import numpy as np, jax, jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from multiverso_tpu.ops import row_combine as rc
rng = np.random.default_rng(0)
rows, width, slots = 4003, 128, 320
walk = jax.jit(lambda t, u, s, n: rc._walk_tiles(
    t, u, s, jnp.int32(0), n, pltpu.InterpretParams()))
for n in (0, 1, 15, 16, 17, 47, 48, 49, 64, 65, 200, 256):
    dense = n == 200        # several rows a tile
    ids = np.sort(rng.choice(300 if dense else rows, n, replace=False))
    uniq = np.concatenate([ids, rows + np.arange(slots - n)]).astype(np.int32)
    sums = rng.normal(size=(slots, width)).astype(np.float32)
    tab = rng.normal(size=(rows, width)).astype(np.float32)
    got = np.asarray(walk(jnp.asarray(tab), jnp.asarray(uniq),
                          jnp.asarray(sums), jnp.int32(n)))
    tab[ids] += sums[:n]
    assert np.array_equal(got, tab), n
print("every copy waited for")
"""
    done = subprocess.run([sys.executable, "-c", code], timeout=600,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-2000:]
    assert "every copy waited for" in done.stdout


@pytest.mark.parametrize("what,walks", [
    ("f32[1003,384]", True), ("f32[1003,128]", True),
    ("f32[1003,300]", False), ("bf16[1003,384]", False),
    ("f32[1003,384] on row shards", False), ("f32[384]", False)])
def test_the_walk_is_chosen_by_what_the_table_is(what, walks, monkeypatch):
    """No flag: float32, whole lanes wide, not row-sharded, on a TPU (here
    the interpreter stands in for one); and off a TPU never."""
    shape = [int(n) for n in re.findall(r"\d+", what.split("[")[1])]
    table = jax.ShapeDtypeStruct(
        tuple(shape), jnp.bfloat16 if what.startswith("bf16") else jnp.float32)
    axis = "mv" if "shards" in what else None
    assert row_combine.tile_walk(table, axis) is None
    assert row_combine.kernel_rows(table, None, 50, 20) == 0
    monkeypatch.setattr(row_combine, "_kernel_interpret", lambda: True)
    assert (row_combine.tile_walk(table, axis) is True) == walks
    if axis is None:
        assert row_combine.kernel_rows(table, None, 50, 20) == 30 * walks
    assert row_combine.lane_wide(300) == 384 == row_combine.lane_wide(384)
