"""word2vec model + WordEmbedding app tests (ref tier-4: WE text8 analogue)."""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                synthetic_corpus)
from multiverso_tpu.data.dictionary import Dictionary, build_huffman
from multiverso_tpu.models import word2vec as w2v


@pytest.fixture(autouse=True)
def _init():
    mv.init()
    yield
    mv.shutdown()


class TestDictionary:
    def test_build_prunes_and_sorts(self):
        d = Dictionary.build("a a a b b c".split(), min_count=2)
        assert d.words == ["a", "b"]
        assert d.word2id == {"a": 0, "b": 1}
        np.testing.assert_array_equal(d.counts, [3, 2])

    def test_encode_drops_oov(self):
        d = Dictionary.build("a a b b".split(), min_count=2)
        np.testing.assert_array_equal(d.encode("a x b".split()), [0, 1])

    def test_subsample_keeps_rare(self):
        counts = ["common"] * 10000 + ["rare"] * 10
        d = Dictionary.build(counts, min_count=5)
        ids = d.encode(counts)
        kept = d.subsample(ids, t=1e-4, seed=0)
        rare_id = d.word2id["rare"]
        rare_rate = np.sum(kept == rare_id) / 10
        common_rate = np.sum(kept == d.word2id["common"]) / 10000
        # rare words survive at a much higher rate than common ones
        assert rare_rate > common_rate * 3
        assert common_rate < 0.2

    def test_unigram_table(self):
        d = Dictionary.build("a a a a b b".split(), min_count=1)
        p = d.unigram_table()
        assert p.sum() == pytest.approx(1.0)
        assert p[0] > p[1]


class TestHuffman:
    def test_tree_shapes(self):
        counts = np.array([50, 30, 10, 5, 5])
        codes, points, lengths = build_huffman(counts)
        assert codes.shape == points.shape
        assert lengths.min() >= 1
        # frequent words get shorter codes
        assert lengths[0] <= lengths[-1]
        # points index inner nodes only
        assert points.max() <= len(counts) - 2

    def test_codes_unique(self):
        counts = np.array([8, 4, 2, 1, 1])
        codes, points, lengths = build_huffman(counts)
        paths = set()
        for w in range(len(counts)):
            paths.add(tuple(codes[w, :lengths[w]]))
        assert len(paths) == len(counts)


class TestSteps:
    def test_skipgram_ns_reduces_loss(self):
        rng = np.random.default_rng(0)
        v, d, b, k = 50, 16, 32, 4
        win, wout = w2v.init_embeddings(w2v.W2VConfig(v, d))
        win, wout = np.asarray(win), np.asarray(wout)
        centers = rng.integers(0, v, b).astype(np.int32)
        contexts = ((centers + 1) % v).astype(np.int32)
        negs = rng.integers(0, v, (b, k)).astype(np.int32)
        import jax.numpy as jnp
        win, wout = jnp.asarray(win), jnp.asarray(wout)
        losses = []
        for _ in range(30):
            win, wout, loss = w2v.skipgram_ns_step(
                win, wout, jnp.asarray(centers), jnp.asarray(contexts),
                jnp.asarray(negs), 0.2)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.7

    def test_cbow_ns_runs(self):
        import jax.numpy as jnp
        v, d, b, w, k = 30, 8, 16, 4, 3
        rng = np.random.default_rng(1)
        win, wout = map(jnp.asarray, w2v.init_embeddings(w2v.W2VConfig(v, d)))
        windows = jnp.asarray(rng.integers(0, v, (b, w)), jnp.int32)
        mask = jnp.ones((b, w), bool)
        tgt = jnp.asarray(rng.integers(0, v, b), jnp.int32)
        negs = jnp.asarray(rng.integers(0, v, (b, k)), jnp.int32)
        l0 = None
        for i in range(20):
            win, wout, loss = w2v.cbow_ns_step(win, wout, windows, mask, tgt,
                                               negs, 0.2)
            l0 = l0 or float(loss)
        assert float(loss) < l0

    def test_hs_step_runs(self):
        import jax.numpy as jnp
        counts = np.array([40, 20, 10, 8, 6, 4])
        codes, points, lengths = build_huffman(counts)
        v, d, b = len(counts), 8, 12
        rng = np.random.default_rng(2)
        win, _ = map(jnp.asarray, w2v.init_embeddings(w2v.W2VConfig(v, d)))
        hs_out = jnp.zeros((v - 1, d))
        centers = rng.integers(0, v, b).astype(np.int32)
        ctx = ((centers + 1) % v)
        c = jnp.asarray(codes[ctx]); p = jnp.asarray(points[ctx])
        m = jnp.arange(codes.shape[1])[None, :] < jnp.asarray(lengths[ctx])[:, None]
        l0 = None
        for _ in range(20):
            win, hs_out, loss = w2v.skipgram_hs_step(
                win, hs_out, jnp.asarray(centers), c, p, m, 0.2)
            l0 = l0 or float(loss)
        assert float(loss) < l0

    def test_generate_pairs(self):
        ids = np.arange(5)
        c, x = w2v.generate_pairs(ids, window=1, dynamic=False)
        # each interior token pairs with both neighbors
        assert (c == 2).sum() == 2
        assert set(x[c == 2]) == {1, 3}

    def test_shared_neg_step_matches_numpy(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        v_sz, d, b, k = 30, 8, 16, 6
        win = rng.normal(size=(v_sz, d)).astype(np.float32)
        wout = rng.normal(size=(v_sz, d)).astype(np.float32) * 0.1
        c = rng.integers(0, v_sz, b).astype(np.int32)
        x = rng.integers(0, v_sz, b).astype(np.int32)
        nid = rng.choice(v_sz, k, replace=False).astype(np.int32)
        lr, nw = 0.05, 0.5

        def sigmoid(z):
            return 1.0 / (1.0 + np.exp(-z))

        vv, up, un = win[c], wout[x], wout[nid]
        pos = (vv * up).sum(-1)
        negs = vv @ un.T
        gp = (1.0 - sigmoid(pos)) * lr
        gn = -sigmoid(negs) * lr * nw
        exp_win, exp_wout = win.copy(), wout.copy()
        np.add.at(exp_win, c, gp[:, None] * up + gn @ un)
        np.add.at(exp_wout, x, gp[:, None] * vv)
        np.add.at(exp_wout, nid, gn.T @ vv)

        got_win, got_wout, loss = w2v.shared_neg_step(
            jnp.asarray(win), jnp.asarray(wout), jnp.asarray(c),
            jnp.asarray(x), jnp.asarray(nid), lr, nw,
            compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got_win), exp_win, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_wout), exp_wout, atol=1e-5)
        exp_loss = (-np.mean(np.log(sigmoid(pos)))
                    - nw * np.mean(np.log(sigmoid(-negs)).sum(-1)))
        assert abs(float(loss) - exp_loss) < 1e-4

    def test_shared_epoch_reduces_loss(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(1)
        v_sz, d, b = 50, 16, 64
        cfg = w2v.W2VConfig(v_sz, d, negatives=4, shared_negatives=8,
                            learning_rate=0.1)
        win, wout = w2v.init_embeddings(cfg, seed=0)
        # corpus where context == center makes loss trivially reducible
        cs = rng.integers(0, v_sz, (20, b)).astype(np.int32)
        epoch_fn = w2v.make_fused_shared_epoch(
            cfg, np.ones(v_sz), compute_dtype=jnp.float32)
        win, wout = jnp.asarray(win), jnp.asarray(wout)
        lcg = jnp.asarray(w2v.init_lcg_state(8, 0))
        losses = []
        for _ in range(6):
            win, wout, loss, lcg, _ = epoch_fn(win, wout, jnp.asarray(cs),
                                               jnp.asarray(cs), lcg)
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestWordEmbeddingApp:
    def _make(self, **kw):
        tokens = synthetic_corpus(30_000, vocab=200, seed=3)
        cfg = WEConfig(size=32, min_count=5, batch_size=256, negative=4,
                       epoch=1, **kw)
        d = Dictionary.build(tokens, cfg.min_count)
        we = WordEmbedding(cfg, d)
        return we, we.prepare_ids(tokens)

    def test_fused_training_learns(self):
        we, ids = self._make()
        stats = we.train_fused(ids, epochs=2)
        assert stats["words_per_sec"] > 0
        assert stats["loss"] < 3.0
        emb = we.embeddings()
        assert np.linalg.norm(emb) > 0

    def test_ps_block_training(self):
        we, ids = self._make(data_block_size=5000)
        stats = we.train_ps_blocks(ids[:10_000], epochs=1)
        assert stats["loss"] > 0
        assert we.word_count[0] > 0

    def test_save_and_nearest(self, tmp_path):
        we, ids = self._make()
        we.train_fused(ids, epochs=1)
        out = tmp_path / "vec.txt"
        we.save_embeddings(str(out))
        header = out.read_text().splitlines()[0].split()
        assert int(header[0]) == len(we.dict)
        assert int(header[1]) == 32
        word = we.dict.words[0]
        nbrs = we.nearest(word, k=3)
        assert len(nbrs) == 3 and word not in nbrs

    def test_binary_output_roundtrips_bit_exact(self, tmp_path):
        """-binary 1 (ref util.h:26, writer
        distributed_wordembedding.cpp:310-325): classic word2vec .bin —
        raw float32 rows reload bit-exact; text mode loads too (lossy)."""
        from multiverso_tpu.apps.word_embedding import load_embeddings
        we, ids = self._make()
        we.train_fused(ids, epochs=1)
        emb = we.embeddings()
        bpath, tpath = tmp_path / "vec.bin", tmp_path / "vec.txt"
        we.save_embeddings(str(bpath), binary=True)
        we.save_embeddings(str(tpath), binary=False)
        words_b, emb_b = load_embeddings(str(bpath))
        assert words_b == list(we.dict.words)
        np.testing.assert_array_equal(emb_b, np.asarray(emb, np.float32))
        words_t, emb_t = load_embeddings(str(tpath))
        assert words_t == words_b
        np.testing.assert_allclose(emb_t, emb_b, atol=1e-6)

    def test_stopwords_dropped_from_training_stream(self, tmp_path):
        """-stopwords 1 -sw_file (ref reader.cpp:11-47): listed words stay
        in the vocab but never reach the training stream."""
        from multiverso_tpu.apps.word_embedding import (WEConfig,
                                                        load_corpus)
        corpus = tmp_path / "c.txt"
        toks = (["the", "cat", "sat"] * 400) + (["dog"] * 100)
        corpus.write_text(" ".join(toks))
        sw = tmp_path / "sw.txt"
        sw.write_text("the\nsat\n")
        cfg = WEConfig(train_file=str(corpus), min_count=5, sample=0,
                       stopwords="1", sw_file=str(sw))
        d, ids = load_corpus(cfg)
        assert "the" in d.word2id and "sat" in d.word2id   # vocab keeps them
        banned = {d.word2id["the"], d.word2id["sat"]}
        assert not banned & set(np.unique(ids).tolist())   # stream drops them
        assert d.word2id["cat"] in set(np.unique(ids).tolist())

    def test_stopwords_flag_requires_sw_file(self):
        from multiverso_tpu.apps.word_embedding import WEConfig
        with pytest.raises(ValueError, match="sw_file"):
            WEConfig(stopwords="1")


class TestModesAndRegressions:
    def _tokens(self):
        return synthetic_corpus(20_000, vocab=150, seed=5)

    def test_cbow_fused(self):
        tokens = self._tokens()
        cfg = WEConfig(size=16, min_count=5, batch_size=256, negative=3,
                       cbow=1)
        d = Dictionary.build(tokens, cfg.min_count)
        we = WordEmbedding(cfg, d)
        stats = we.train_fused(we.prepare_ids(tokens), epochs=1)
        assert stats["loss"] > 0
        assert np.linalg.norm(we.embeddings()) > 0

    def test_hs_fused(self):
        tokens = self._tokens()
        cfg = WEConfig(size=16, min_count=5, batch_size=256, hs=1)
        d = Dictionary.build(tokens, cfg.min_count)
        we = WordEmbedding(cfg, d)
        stats = we.train_fused(we.prepare_ids(tokens), epochs=1)
        assert stats["loss"] > 0
        # the HS output table actually trained
        assert np.linalg.norm(we.table_hs.get()) > 0

    def test_cbow_hs_step_reduces_loss_and_matches_grad(self):
        import jax
        import jax.numpy as jnp
        counts = np.array([40, 20, 10, 8, 6, 4])
        codes, points, lengths = build_huffman(counts)
        v, d, b, w = len(counts), 8, 12, 4
        rng = np.random.default_rng(3)
        win, _ = map(jnp.asarray, w2v.init_embeddings(w2v.W2VConfig(v, d)))
        hs_out = jnp.asarray(rng.normal(0, 0.1, (v - 1, d)), jnp.float32)
        windows = jnp.asarray(rng.integers(0, v, (b, w)), jnp.int32)
        wmask = jnp.asarray(rng.random((b, w)) > 0.2)
        targets = rng.integers(0, v, b)
        c = jnp.asarray(codes[targets]); p = jnp.asarray(points[targets])
        m = (jnp.arange(codes.shape[1])[None, :]
             < jnp.asarray(lengths[targets])[:, None])

        # the manual ascent deltas must equal -lr * d(sum-loss)/d(params)
        def total_loss(win, hs_out):
            ctx = jnp.take(win, windows, axis=0)
            mm = wmask.astype(ctx.dtype)[..., None]
            vvec = (ctx * mm).sum(1) / jnp.maximum(mm.sum(1), 1.0)
            u = jnp.take(hs_out, p, axis=0)
            s = jnp.einsum("bd,bld->bl", vvec, u)
            masked = jnp.where(m, s * (1 - 2 * c), 0.0)
            # per-sample sum (the step's g has no 1/B factor)
            return -jnp.sum(jax.nn.log_sigmoid(masked) * m)

        lr = 0.2
        gw, gh = jax.grad(total_loss, argnums=(0, 1))(win, hs_out)
        win2, hs2, _ = w2v.cbow_hs_step(win, hs_out, windows, wmask,
                                        c, p, m, lr)
        np.testing.assert_allclose(np.asarray(win2 - win),
                                   np.asarray(-lr * gw), atol=1e-5)
        np.testing.assert_allclose(np.asarray(hs2 - hs_out),
                                   np.asarray(-lr * gh), atol=1e-5)

        l0 = None
        for _ in range(30):
            win, hs_out, loss = w2v.cbow_hs_step(
                win, hs_out, windows, wmask, c, p, m, lr)
            l0 = l0 or float(loss)
        assert float(loss) < l0

    def test_cbow_hs_fused(self):
        tokens = self._tokens()
        cfg = WEConfig(size=16, min_count=5, batch_size=256, cbow=1, hs=1)
        d = Dictionary.build(tokens, cfg.min_count)
        we = WordEmbedding(cfg, d)
        stats = we.train_fused(we.prepare_ids(tokens), epochs=1)
        assert stats["loss"] > 0
        assert np.linalg.norm(we.table_hs.get()) > 0
        assert np.linalg.norm(we.embeddings()) > 0

    @pytest.mark.parametrize("cbow,hs", [(1, 0), (0, 1), (1, 1)])
    def test_ps_blocks_all_variants(self, cbow, hs):
        # the reference's distributed path trains every variant; so does
        # the PS block path here (skipgram-NS is covered elsewhere)
        tokens = self._tokens()
        cfg = WEConfig(size=16, min_count=5, batch_size=128, cbow=cbow,
                       hs=hs, negative=3, data_block_size=4000)
        d = Dictionary.build(tokens, cfg.min_count)
        we = WordEmbedding(cfg, d)
        stats = we.train_ps_blocks(we.prepare_ids(tokens), epochs=1)
        assert stats["loss"] > 0
        assert np.linalg.norm(we.embeddings()) > 0
        if hs:
            assert np.linalg.norm(we.table_hs.get()) > 0
        else:
            assert np.linalg.norm(we.table_out.get()) > 0

    @pytest.mark.parametrize("cbow,hs", [(0, 0), (0, 1), (1, 0), (1, 1)])
    def test_ps_device_plane_matches_host_plane(self, cbow, hs):
        # the fused single-dispatch device plane and the host Get/Add plane
        # must train to the same state: same seed => identical pair/negative
        # draws => the only divergence allowed is float reassociation
        tokens = self._tokens()
        emb = {}
        for mode in ("0", "1"):
            cfg = WEConfig(size=16, min_count=5, batch_size=128, negative=3,
                           cbow=cbow, hs=hs, data_block_size=4000,
                           ps_device_plane=mode, seed=9)
            d = Dictionary.build(tokens, cfg.min_count)
            we = WordEmbedding(cfg, d)
            stats = we.train_ps_blocks(we.prepare_ids(tokens), epochs=1)
            assert stats["loss"] > 0
            emb[mode] = (we.embeddings(),
                         (we.table_hs if hs else we.table_out).get())
        np.testing.assert_allclose(emb["0"][0], emb["1"][0], atol=1e-3)
        np.testing.assert_allclose(emb["0"][1], emb["1"][1], atol=1e-3)

    def test_ps_block_dtype_bf16_trains_close_to_f32(self):
        # bf16 scan mode: same draws, loss lands near the f32 run (deltas
        # are measured against the bf16-rounded baseline, so untrained
        # rows get exactly-zero deltas — regression for the phantom-delta
        # bug) and bad values are a typed config error
        tokens = self._tokens()
        losses = {}
        for dt in ("f32", "bf16"):
            cfg = WEConfig(size=16, min_count=5, batch_size=128, negative=3,
                           data_block_size=4000, seed=9, ps_block_dtype=dt)
            d = Dictionary.build(tokens, cfg.min_count)
            we = WordEmbedding(cfg, d)
            st = we.train_ps_blocks(we.prepare_ids(tokens), epochs=1)
            losses[dt] = st["loss"]
        assert abs(losses["bf16"] - losses["f32"]) < 0.15, losses
        with pytest.raises(ValueError, match="ps_block_dtype"):
            WEConfig(ps_block_dtype="bf61")

    def test_words_per_sec_counts_tokens(self):
        tokens = self._tokens()
        cfg = WEConfig(size=16, min_count=5, batch_size=256, negative=3)
        d = Dictionary.build(tokens, cfg.min_count)
        we = WordEmbedding(cfg, d)
        ids = we.prepare_ids(tokens)
        stats = we.train_fused(ids, epochs=1)
        implied_words = stats["words_per_sec"] * stats["seconds"]
        assert implied_words == pytest.approx(ids.size, rel=0.01)
        assert stats["pairs"] > ids.size  # pairs are reported separately


# ---------------------------------------------------------------------- #
# ISSUE 40: skipgram_ns_step writes both tables through
# row_combine.add_rows, with plans made ahead of it or in it
# ---------------------------------------------------------------------- #
def _raw_ns_step(win, wout, centers, contexts, negatives, lr):
    """``skipgram_ns_step`` as it was before ISSUE 40, the plain
    reference: every update row goes to its table by a raw duplicate
    scatter-add."""
    import jax
    import jax.numpy as jnp
    targets = jnp.concatenate([contexts[:, None], negatives], axis=1)
    v, u = jnp.take(win, centers, axis=0), jnp.take(wout, targets, axis=0)
    scores = jnp.einsum("bd,btd->bt", v, u)
    labels = jnp.zeros(targets.shape, v.dtype).at[:, 0].set(1.0)
    g = (labels - jax.nn.sigmoid(scores)) * lr
    win = win.at[centers].add(jnp.einsum("bt,btd->bd", g, u))
    du = g[..., None] * v[:, None, :]
    return win, wout.at[targets.reshape(-1)].add(
        du.reshape(-1, du.shape[-1]))


NS_ROWS, NS_B, NS_K = 1003, 64, 5     # the last row stands for the dummy


def _ns_ids(kind, rng):
    if kind == "padded":        # a padded minibatch: every id the dummy
        return (np.full(NS_B, NS_ROWS - 1, np.int32),) * 2 + (
            np.full((NS_B, NS_K), NS_ROWS - 1, np.int32),)
    if kind == "distinct":      # no row twice, in a table or a column
        ids = rng.permutation(NS_ROWS - 1)[:NS_B * (NS_K + 2)].astype(
            np.int32)
        return (ids[:NS_B], ids[NS_B:2 * NS_B],
                ids[2 * NS_B:].reshape(NS_B, NS_K))
    draw = lambda *shape: (rng.zipf(1.2, shape) % 300).astype(np.int32)
    return draw(NS_B), draw(NS_B), draw(NS_B, NS_K)


# heads: the whole table, and one that leaves the walk most of the rows
@pytest.mark.parametrize("head", [8192, 40])
@pytest.mark.parametrize("plans", ["made_ahead", "made_in_the_step"])
@pytest.mark.parametrize("kind", ["repeats", "distinct", "padded"])
def test_skipgram_ns_step_writes_what_the_raw_scatters_wrote(
        kind, plans, head, monkeypatch):
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.ops import row_combine
    monkeypatch.setattr(row_combine, "HEAD", head)
    monkeypatch.setattr(row_combine, "CHUNK", 16)
    rng = np.random.default_rng(40)
    c, x, g = (jnp.asarray(a) for a in _ns_ids(kind, rng))
    tables = rng.uniform(-0.5, 0.5, (2, NS_ROWS, 12)).astype(np.float32)
    tables[:, ::5] = -0.0   # a slot that wrote row + 0 would leave +0.0
    win0, wout0 = jnp.asarray(tables[0]), jnp.asarray(tables[1])
    made = (None, None)
    if plans == "made_ahead":
        made = (row_combine.plan_rows(c, NS_ROWS),
                row_combine.plan_rows(w2v.target_columns(x, g), NS_ROWS))
        assert made[1].run.shape == (NS_K + 1, NS_B)
    # every float operation as it is written: two programs then round
    # alike (tests/conftest.py, same_floats)
    as_written = {"xla_backend_optimization_level": 0}
    win, wout, loss = jax.jit(
        lambda *a: w2v.skipgram_ns_step(*a, 0.05, plans=made),
        compiler_options=as_written)(win0, wout0, c, x, g)
    rwin, rwout = jax.jit(lambda *a: _raw_ns_step(*a, 0.05),
                          compiler_options=as_written)(win0, wout0, c, x, g)
    assert np.isfinite(float(loss))
    for got, ref, old, ids in ((win, rwin, tables[0], c),
                               (wout, rwout, tables[1],
                                np.concatenate([x, g.reshape(-1)]))):
        got, ref = np.asarray(got), np.asarray(ref)
        delta = np.abs(ref - old).max()
        assert delta > 0
        # the same float32 sum in another order
        assert np.abs(got - ref).max() <= 1e-5 * delta
        # every row no id names keeps its bits, the -0.0 rows included:
        # under a padded minibatch every real row
        others = np.setdiff1d(np.arange(NS_ROWS), np.asarray(ids))
        assert np.signbit(old[others]).any()
        np.testing.assert_array_equal(got[others].view(np.uint32),
                                      old[others].view(np.uint32))
        if kind == "padded":
            assert others.size == NS_ROWS - 1
        if kind == "distinct":
            # one term a row, no reassociation: the same float, bit for
            # bit but for the sign of a zero (a sum starts from +0.0)
            np.testing.assert_array_equal(got, ref)
