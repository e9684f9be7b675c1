"""The sharded table path (ISSUE 27): tables row-sharded over a mesh are
built a shard at a time, a vocabulary of counts alone trains, and
``train_fused`` on row-sharded tables does what the plain reference
does, says which shard owns its update rows, and touches no other row.
ISSUE 36: words are dealt round the row shards, every shard walks its own
rows alone, and what the app hands out is in word order all the same.
ISSUE 38: every shard reads its own rows alone and the shards hand them
round; the tables and the loss are those of the partitioner's gather.

Meshes of 1, 2, 4 and 8 of the CPU's eight virtual devices stand in for
one chip and for a four-chip host; weights are seeded."""

import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import multiverso_tpu as mv
from benchmark.reference import w2v_sgns
from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                load_embeddings)
from multiverso_tpu.data.dictionary import Dictionary
from multiverso_tpu.telemetry import trace as ttrace

VOCAB, WIDTH = 203, 8          # 203: no multiple of any shard count


def _init(shards: int) -> None:
    if mv.Zoo.get().started:    # a second mesh in one test: init again,
        mv.shutdown()           # a start on a started Zoo changes nothing
    mv.init(mesh=Mesh(np.asarray(jax.devices()[:shards]), ("mv",)))


def _counts(vocab: int = VOCAB) -> np.ndarray:
    return np.maximum((1e6 / np.arange(1, vocab + 1) ** 1.1).astype(np.int64),
                      5)


def _stream(n: int, seed: int = 0, vocab: int = VOCAB) -> np.ndarray:
    p = _counts(vocab) / _counts(vocab).sum()
    return np.random.default_rng(seed).choice(vocab, n, p=p).astype(np.int64)


def _we(words=None, **kw) -> WordEmbedding:
    cfg = WEConfig(**{**dict(size=WIDTH, min_count=5, batch_size=64,
                             negative=5, shared_negatives=16, window=2,
                             epoch=1, sample=0, seed=3), **kw})
    return WordEmbedding(cfg, Dictionary.from_counts(words, _counts(), 5))


def _spans(name: str, since: int = 0):
    return [e for e in ttrace.events()[since:] if e["name"] == name]


@pytest.fixture
def set_head(monkeypatch):
    """``row_combine.HEAD`` for one test. ``jax.jit`` keeps a trace by the
    function it wraps, and the app wraps ``plan_rows`` itself, so another
    HEAD's traces are dropped on the way in and on the way out."""
    from multiverso_tpu.ops import row_combine

    def set_(head: int) -> None:
        monkeypatch.setattr(row_combine, "HEAD", head)
        jax.clear_caches()

    yield set_
    jax.clear_caches()


# ---------------------------------------------------------------------- #
# a table built by shards is the table drawn whole
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shards", [1, 4, 8])
def test_table_built_by_shards_equals_the_whole_draw(shards):
    _init(shards)
    start = len(ttrace.events())
    seed, scale, rows, width = 2 ** 40 + 17, 0.5 / 300, 1001, 300
    t = mv.MatrixTable(rows, width, seed=seed, init_scale=scale,
                       name="by_shards")
    want = np.random.default_rng(seed).uniform(
        -scale, scale, t.padded_shape).astype(np.float32)
    want[rows:] = 0
    np.testing.assert_array_equal(np.asarray(t.raw()), want)
    [init] = _spans("table.init", start)
    a = init["args"]
    assert (t.num_shards, a["shards"]) == (shards, shards)
    assert t.rows_per_shard * shards == t.padded_shape[0]
    # the host held one shard at a time, never the table
    assert a["host_bytes"] == t.rows_per_shard * width * 4
    assert a["host_bytes"] * shards == a["bytes"]
    kids = [e for e in _spans("table.init.shard", start)
            if e["parent"] == init["id"]]
    assert [k["args"]["shard"] for k in kids] == list(range(shards))
    assert all(k["args"]["rows"] == t.rows_per_shard
               and k["args"]["host_bytes"] == a["host_bytes"] for k in kids)
    inside = {e["name"] for e in ttrace.events()[start:]
              if e["parent"] in {k["id"] for k in kids}}
    assert inside == {"table.init.host", "table.init.put"}


def test_given_values_and_zeros_are_built_by_shards_too():
    _init(4)
    start = len(ttrace.events())
    init = np.arange(37 * 3, dtype=np.float32).reshape(37, 3)
    t = mv.MatrixTable(37, 3, init=init, name="given")
    np.testing.assert_array_equal(t.get(), init)
    assert not np.asarray(t.raw())[37:].any()
    z = mv.ArrayTable(50, name="zeros")
    assert not np.asarray(z.raw()).any()
    given, zeros = _spans("table.init", start)
    assert given["args"]["host_bytes"] == t.rows_per_shard * 3 * 4
    assert (zeros["args"]["host_bytes"], zeros["args"]["shards"]) == (0, 4)
    with pytest.raises(ValueError, match="init shape"):
        mv.MatrixTable(5, 3, init=np.zeros((4, 3), np.float32))


# ---------------------------------------------------------------------- #
# a vocabulary of counts alone
# ---------------------------------------------------------------------- #
def test_counts_only_vocabulary_trains_to_the_same_tables():
    mv.init()
    ids = _stream(4_000)
    named = _we(words=[f"w{i}" for i in range(VOCAB)])
    bare = _we(words=None)
    assert len(bare.dict) == len(named.dict) == VOCAB
    for we in (named, bare):
        for _ in range(2):
            out = we.train_fused(ids, epochs=2)
        assert np.isfinite(out["loss"])
    for a, b in ((named.table_in, bare.table_in),
                 (named.table_out, bare.table_out)):
        np.testing.assert_array_equal(a.get(), b.get())
    assert named.table_out.get().any()
    # nothing asked for a word, so none was made
    assert bare.dict._words is None and bare.dict._word2id is None


def test_words_are_made_when_asked_for():
    d = Dictionary.from_counts(None, _counts(12), 5)
    assert len(d) == 12 and d._words is None
    assert d.words[7] == "7" and d.word2id["11"] == 11
    np.testing.assert_array_equal(d.encode(["3", "x", "0"]), [3, 0])
    named = Dictionary.from_counts(["a", "b"], [9, 7], 5)
    assert named._word2id is None           # the map waits for a lookup
    assert named.word2id == {"a": 0, "b": 1} and len(named) == 2
    named.words = ["c", "d"]
    assert named.word2id == {"c": 0, "d": 1}


# ---------------------------------------------------------------------- #
# train_fused on row-sharded tables against the plain reference
# ---------------------------------------------------------------------- #
def _seed_out(we: WordEmbedding, seed: int = 11, table=None) -> None:
    """embed_out starts as zeros; give it seeded values so that every
    gradient of the batch is alive. The values are drawn a WORD each, so
    a word starts from the same vector wherever its row lives."""
    t = table or we.table_out
    vals = np.zeros(t.padded_shape, np.float32)
    vals[_word_rows(we)] = np.random.default_rng(seed).uniform(
        -0.5, 0.5, (VOCAB, WIDTH))
    t.adopt({"data": jax.device_put(vals, t.sharding),
             "ustate": t.state["ustate"]})


def _word_rows(we: WordEmbedding) -> np.ndarray:
    """The row of every word, by the placement itself and not by the
    program's map: rank ``w`` is shard ``w % S``'s ``w // S``-th row."""
    t, w = we.table_in, np.arange(VOCAB)
    return (w % t.num_shards) * t.rows_per_shard + w // t.num_shards


def _owners(we: WordEmbedding, rows) -> np.ndarray:
    return np.asarray(rows) // we.table_in.rows_per_shard


@pytest.mark.parametrize("shards", [2, 8])
def test_one_batch_on_row_sharded_tables_matches_the_reference(shards):
    _init(shards)
    we = _we()
    _seed_out(we)
    ids = _stream(40, seed=5)       # 64 to 127 pairs: one batch of 64
    cb, xb, pairs = we._device_pairs(ids)
    assert cb.shape == (1, 64)
    centers, contexts = np.asarray(cb[0]), np.asarray(xb[0])
    pool = we.fused_pool(next_batches=1)[0]
    old = we.table_in.get(), we.table_out.get()
    out = we.train_fused(ids, epochs=1)
    new = we.table_in.get(), we.table_out.get()
    np.testing.assert_array_equal(we.fused_pool(), pool)
    loss, ref = w2v_sgns.step(old[0], old[1], centers, contexts, pool,
                              we.cfg.alpha, we.cfg.negative / pool.size)
    assert out["loss"] == pytest.approx(loss, rel=1e-4)
    for k, side in ((0, "in"), (1, "out")):
        touched, want = ref[side + "_ids"], ref[side + "_delta"]
        got = new[k][touched] - old[k][touched]
        assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
        assert np.abs(want).max() > 0
        others = np.setdiff1d(np.arange(VOCAB), touched)
        assert others.size
        np.testing.assert_array_equal(new[k][others], old[k][others])
    # the rows the batch touched lie in more than one shard
    assert np.unique(_owners(we, ref["in_ids"])).size > 1


# 52 rows a shard on four, and a quarter of HEAD the head of each: the
# whole of every shard, 7 rows, 30 rows, none
@pytest.mark.parametrize("head", [8192, 30, 120, 0])
def test_combined_scatters_on_row_sharded_tables_equal_one_device(
        head, set_head, monkeypatch, same_floats):
    """ISSUE 28: the epoch combines a minibatch's duplicate update rows
    and walks the distinct ones a chunk at a time; partitioned over four
    row shards it leaves the tables one device leaves, and no other row
    moves. Chunks of 64 slots, so a batch of 192 walks up to three.
    ISSUE 31: the rows of the head take a dense add, which every shard
    makes to its own rows. ISSUE 36: the words are dealt round the four
    shards and each walks its own rows, so the tables are compared in
    WORD order. ISSUE 38: bit for bit where every float operation is done
    as written, to last bits at the default compile (``same_floats``)."""
    from multiverso_tpu.ops import row_combine
    monkeypatch.setattr(row_combine, "CHUNK", 64)
    set_head(head)
    ids = _stream(900, seed=11)
    got = {}
    for shards in (1, 4):
        _init(shards)
        we = _we(batch_size=192)
        _seed_out(we, 5, we.table_in)
        _seed_out(we)
        rows = _word_rows(we)
        cb, xb, _ = we._device_pairs(ids)
        assert cb.shape[0] >= 3 and cb.shape[1] == 192
        old = we.table_in.get(), we.table_out.get()
        pools = we.fused_pool(next_batches=int(cb.shape[0]))
        out = we.train_fused(ids, epochs=1)
        new = we.table_in.get(), we.table_out.get()
        got[shards] = (new[0][rows], new[1][rows], out["loss"],
                       np.asarray(cb), pools)
        # the arrays the epoch scans and the pools it draws name ROWS
        touched = (np.unique(np.asarray(cb)),
                   np.unique(np.concatenate([np.asarray(xb).ravel(),
                                             pools.ravel()])))
        for k in (0, 1):
            assert np.isin(touched[k], rows).all()
            others = np.setdiff1d(np.arange(new[k].shape[0]), touched[k])
            assert others.size
            np.testing.assert_array_equal(new[k][others], old[k][others])
            assert (new[k][touched[k]] != old[k][touched[k]]).any()
    assert np.unique(_owners(we, touched[0])).size == 4
    for k in (0, 1, 2):
        same_floats(got[4][k], got[1][k])
    # the same words in the same order, under other row ids
    for k in (3, 4):
        assert not np.array_equal(got[4][k], got[1][k])
        np.testing.assert_array_equal(we._words(got[4][k]), got[1][k])


# ---------------------------------------------------------------------- #
# the striped placement
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_words_are_dealt_round_the_row_shards(shards):
    _init(shards)
    we = _we()
    words = np.arange(VOCAB)
    rows = we._rows(words)
    np.testing.assert_array_equal(rows, _word_rows(we))
    np.testing.assert_array_equal(we._words(rows), words)
    # every word has a row of its own among the table's logical rows, and
    # the scratch row is no word's
    assert np.unique(rows).size == VOCAB
    assert rows.max() < we.table_in.shape[0] <= we.table_in.scratch_row
    assert we.table_in.shape == we.table_out.shape
    # the hottest ranks lie on different shards, and the shares are even
    np.testing.assert_array_equal(_owners(we, rows[:shards]),
                                  np.arange(shards))
    share = np.bincount(_owners(we, rows), minlength=shards)
    assert share.max() - share.min() <= 1
    if shards == 1:         # the identity: nothing is computed at all
        assert rows is words and we.table_in.shape[0] == VOCAB


def _trained(shards: int, path, **kw):
    """A run of two calls on ``shards`` row shards from word-seeded
    tables: the app, its embeddings, and what it saved at ``path``."""
    _init(shards)
    we = _we(words=[f"w{i}" for i in range(VOCAB)], **kw)
    _seed_out(we, 5, we.table_in)
    _seed_out(we)
    for _ in range(2):
        we.train_fused(_stream(2_000, seed=4), epochs=1)
    we.save_embeddings(str(path), binary=True)
    return we, we.embeddings(), load_embeddings(str(path))


def test_embeddings_and_saves_are_in_word_order_on_any_shards(
        tmp_path, same_floats):
    one = _trained(1, tmp_path / "one.bin")
    four = _trained(4, tmp_path / "four.bin")
    assert one[1].shape == four[1].shape == (VOCAB, WIDTH)
    same_floats(four[1], one[1])
    assert four[2][0] == one[2][0] == [f"w{i}" for i in range(VOCAB)]
    same_floats(four[2][1], one[2][1])
    np.testing.assert_array_equal(four[2][1], four[1])
    # in the table itself the words lie striped
    raw = np.asarray(four[0].table_in.raw())
    np.testing.assert_array_equal(raw[_word_rows(four[0])], four[1])
    assert not np.array_equal(raw[:VOCAB], four[1])
    assert four[0].nearest("w3", 5) == one[0].nearest("w3", 5)


@pytest.mark.parametrize("use_ps", [0, 1])
@pytest.mark.parametrize("cbow,hs", [(0, 0), (1, 0), (0, 1), (1, 1)])
def test_every_mode_trains_the_words_rows_on_row_shards(cbow, hs, use_ps):
    """The other epochs and the block path turn words into rows by the
    same map: after a pass on four shards the rows that moved are words'
    rows, most words' rows moved, and no row without a word did."""
    _init(4)
    we = _we(cbow=cbow, hs=hs, shared_negatives=0, use_ps=use_ps,
             data_block_size=1_000)
    ids = _stream(2_000, seed=6)
    old = we.table_in.get(), we._sec_table().get()
    (we.train_ps_blocks if use_ps else we.train_fused)(ids, epochs=1)
    moved = (we.table_in.get() != old[0]).any(axis=1)
    rows = _word_rows(we)
    seen = np.unique(ids)
    assert moved[rows[seen]].mean() > 0.9
    assert not np.delete(moved, rows[seen]).any()
    if not hs:      # embed_out is a word table too
        moved = (we.table_out.get() != old[1]).any(axis=1)
        assert moved[rows[seen]].mean() > 0.9
        assert not np.delete(moved, rows).any()


# ---------------------------------------------------------------------- #
# the counts on the we.fused span
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shards,epochs,head", [
    (4, 1, 8192), (4, 2, 40), (1, 1, 40), (1, 2, 8192)])
def test_fused_span_counts_update_rows_by_shard(shards, epochs, head,
                                                set_head):
    set_head(head)
    _init(shards)
    we = _we()
    ids = _stream(3_000, seed=9)
    cb, xb, pairs = we._device_pairs(ids)
    batches = int(cb.shape[0])
    assert batches > 3
    we.train_fused(ids, epochs=1)               # pairs cached from here on
    pools = we.fused_pool(next_batches=epochs * batches)
    start = len(ttrace.events())
    we.train_fused(ids, epochs=epochs)
    [call] = _spans("we.fused", start)
    a = call["args"]
    rows = np.concatenate([np.tile(np.asarray(cb).ravel(), epochs),
                           np.tile(np.asarray(xb).ravel(), epochs),
                           pools.ravel()])
    # ISSUE 36: the owner of a row is the shard its word was dealt to
    want = np.bincount(we._words(rows) % shards, minlength=shards)
    assert a["shards"] == shards
    assert a["update_rows_by_shard"] == want.tolist()
    assert want.tolist() == np.bincount(_owners(we, rows),
                                        minlength=shards).tolist()
    assert want.min() > 0
    assert sum(a["update_rows_by_shard"]) == epochs * batches * (2 * 64 + 16)
    # ISSUE 28: the pairs' update rows before combining, and the distinct
    # rows the table scatters were handed after (centres plus contexts)
    assert a["update_rows"] == epochs * batches * 2 * 64
    distinct = [np.unique(r)
                for r in list(np.asarray(cb)) + list(np.asarray(xb))]
    assert a["unique_rows"] == epochs * sum(d.size for d in distinct)
    assert a["unique_rows"] < a["update_rows"]
    # ISSUE 31: and those of them that the tables' heads took, the first
    # HEAD // shards rows of every shard
    per = we.table_in.rows_per_shard
    tails = np.asarray([[((d // per == s) & (d % per >= head // shards)).sum()
                         for s in range(shards)] for d in distinct])
    assert a["head_rows"] == a["unique_rows"] - epochs * tails.sum()
    assert (a["head_rows"] < a["unique_rows"]) == (head < VOCAB)
    # ISSUE 36: a shard's walks were handed its own tail rows, in whole
    # chunks (of 64 slots here: a batch is smaller than CHUNK), and so no
    # more than the tail rows and a chunk a shard, a table and a minibatch
    walk = np.asarray(a["walk_slots_by_shard"])
    np.testing.assert_array_equal(walk,
                                  epochs * (-(-tails // 64) * 64).sum(axis=0))
    assert walk.sum() <= epochs * (tails.sum() + tails.shape[0] * shards * 64)
    # ISSUE 38: and its reads took a round of cap slots a shard, a table
    # and a minibatch, and one round more wherever the busiest shard
    # owned more distinct rows than that; one shard takes no rounds
    from multiverso_tpu.ops import row_combine
    cap = row_combine.gather_cap(64, shards)
    assert cap == (12 if shards > 1 else 64)     # 3/4 of 64 // 4
    owned = np.asarray([[(d // per == s).sum() for s in range(shards)]
                        for d in distinct])
    rounds = np.maximum(-(-owned.max(axis=1) // cap), 1)
    assert a["gather_rounds"] == epochs * int((rounds - 1).sum())
    assert a["gather_rounds"] == 0 or shards > 1
    per_batch = (2 * 64 + 16) * WIDTH * 4       # float32 on the CPU
    assert a["allreduce_bytes"] == (shards > 1) * epochs * batches * per_batch
    # the pairs' share was counted when the pairs were generated
    [pairs_span] = [e for e in _spans("we.fused.pairs", start)]
    assert pairs_span["args"]["cache_hit"] == 1
    # the pool of the call's last batch is what the sampler hands back
    np.testing.assert_array_equal(we.fused_pool(), pools[-1])


# ---------------------------------------------------------------------- #
# ISSUE 38: rows read by their owners
# ---------------------------------------------------------------------- #
def _partitioners_take(table, ids, plan=None, sharding=None, dtype=None):
    """The read as it was before ISSUE 38: ``jnp.take`` on the sharded
    table, left to the partitioner."""
    import jax.numpy as jnp
    return jnp.take(table, ids, axis=0).astype(dtype or table.dtype)


@pytest.mark.parametrize("stream", ["zipf", "one_shard"])
@pytest.mark.parametrize("shards", [2, 4])
def test_rows_read_by_their_owners_leave_the_partitioners_tables(
        shards, stream, monkeypatch, same_floats):
    """Two calls on row shards with ``take_rows`` and with the gather the
    partitioner made before it: the same tables and the same loss
    (``same_floats``: bit for bit, or to last bits). ``one_shard``: a stream of words that all live in shard 0,
    so every minibatch takes rounds past the first."""
    from multiverso_tpu.ops import row_combine
    ids = (_stream(1_500, seed=13) if stream == "zipf" else
           np.random.default_rng(13).integers(0, VOCAB // shards, 1_500)
           * shards)
    got = {}
    for how in ("owners", "partitioner"):
        if how == "partitioner":
            monkeypatch.setattr(row_combine, "take_rows", _partitioners_take)
        jax.clear_caches()
        _init(shards)
        we = _we()
        _seed_out(we, 5, we.table_in)
        _seed_out(we)
        start = len(ttrace.events())
        losses = [we.train_fused(ids, epochs=1)["loss"] for _ in range(2)]
        got[how] = (we.table_in.get(), we.table_out.get(), losses)
        rounds = [e["args"]["gather_rounds"]
                  for e in _spans("we.fused", start)]
        assert len(rounds) == 2
        if stream == "one_shard":
            assert _owners(we, we._rows(ids)).max() == 0
            assert min(rounds) > 0
    jax.clear_caches()
    for k in (0, 1, 2):
        same_floats(got["owners"][k], got["partitioner"][k])
        assert np.any(got["owners"][k])


@pytest.mark.parametrize("batch", [32, 64])
@pytest.mark.parametrize("shards", [2, 8])
def test_the_default_compiled_epoch_is_one_on_any_row_shards(shards, batch):
    """What the default compile does keep bit for bit: the epoch that
    reads rows by their owners leaves the same words' rows and the same
    loss on 2, 4 and 8 row shards, whose rounds, owners and row ids all
    differ (6 or 12 slots a round on four shards, 3 or 6 on eight)."""
    ids = _stream(2_500, seed=17)
    got = {}
    for n in (shards, 4):
        _init(n)
        we = _we(batch_size=batch)
        _seed_out(we, 5, we.table_in)
        _seed_out(we)
        start = len(ttrace.events())
        losses = [we.train_fused(ids, epochs=1)["loss"] for _ in range(2)]
        rows = _word_rows(we)
        got[n] = (we.table_in.get()[rows], we.table_out.get()[rows], losses,
                  sum(e["args"]["gather_rounds"]
                      for e in _spans("we.fused", start)))
    for k in (0, 1, 2):
        np.testing.assert_array_equal(
            np.asarray(got[shards][k], np.float32).view(np.uint32),
            np.asarray(got[4][k], np.float32).view(np.uint32))
    assert got[4][3] > 0 and got[shards][3] != got[4][3]


@pytest.mark.parametrize("shards", [1, 4])
def test_the_lowered_epoch_gathers_over_the_mesh_on_row_shards_only(shards):
    """One shard lowers the epoch it lowered before ISSUE 38: no
    all-gather and no ``shard_map``; on row shards each of the two reads
    is one ``shard_map`` beside the two writes', and its all-gather sits
    in the first round and in the loop of later rounds."""
    import jax.numpy as jnp
    from multiverso_tpu.ops import row_combine
    _init(shards)
    we = _we()
    cb, xb, _ = we._device_pairs(_stream(1_000, seed=2))
    fn, shared = we._fused_epoch_fn()
    assert shared
    rows = we.table_in.padded_shape[0]
    plans = tuple(row_combine.plan_rows(i, rows, shards) for i in (cb, xb))
    text = fn.lower(we.table_in.raw(), we.table_out.raw(), cb, xb, we._lcg,
                    plans).as_text()
    many = shards > 1
    assert text.count("stablehlo.all_gather") == 4 * many
    # the two reads' and the two writes'; a mesh of one device lowers none
    assert text.count("sdy.manual_computation") == 4 * many
    # what is left to the partitioner: the pool's rows
    assert ("stablehlo.all_reduce" in text) is False
    assert text.count("stablehlo.gather") >= 3


def test_fused_pool_is_only_for_the_shared_negatives_epoch():
    mv.init()
    we = _we(shared_negatives=0)
    with pytest.raises(ValueError, match="shared-negatives"):
        we.fused_pool()
