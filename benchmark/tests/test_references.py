"""The references against ``chip_smoke.py``'s NumPy rules on a tiny
table, against hand-written formulas, and the generators' promises."""

import numpy as np
import pytest

import chip_smoke
from benchmark import gen, shapes
from benchmark.reference import dlrm as ref_dlrm
from benchmark.reference import rules, w2v_sgns


def _batch(rng, rows=50, n=200, d=8):
    ids = rng.integers(0, rows, n)              # many duplicates
    return ids, rng.normal(size=(n, d)).astype(np.float32)


def test_rules_are_chip_smokes_rules():
    rng = np.random.default_rng(0)
    ids, vals = _batch(rng)
    u1, d1 = rules.dedupe(ids, vals)
    u2, d2 = chip_smoke._np_dedupe(ids, vals)
    assert np.array_equal(u1, u2) and np.array_equal(d1, d2)
    a, ga = (rng.normal(size=(50, 8)).astype(np.float32),
             np.abs(rng.normal(size=(50, 8))).astype(np.float32))
    b, gb = a.copy(), ga.copy()
    rules.adagrad_rows(a, ga, ids, vals, 0.05, 0.1)
    chip_smoke._np_adagrad_rows(b, gb, ids, vals, 0.05, 0.1)
    assert np.array_equal(a, b) and np.array_equal(ga, gb)
    rules.default_rows(a, ids, vals)
    chip_smoke._np_default_rows(b, ids, vals)
    assert np.array_equal(a, b)


def test_adagrad_step_is_the_rule_and_monotone():
    g = np.linspace(-1, 1, 41)
    s = rules.adagrad_step(g, 0.3, 0.05, 0.1)
    data, hist = np.zeros((41, 1), np.float32), np.full((41, 1), 0.3, np.float32)
    rules.adagrad_rows(data, hist, np.arange(41),
                       g[:, None].astype(np.float32), 0.05, 0.1)
    assert np.allclose(-data[:, 0], s, rtol=1e-5, atol=1e-7)
    assert np.all(np.diff(s) > 0)


def test_sgns_reference_matches_the_hand_derived_gradient():
    rng = np.random.default_rng(1)
    win = rng.normal(0, 0.3, (30, 6)).astype(np.float32)
    wout = rng.normal(0, 0.3, (30, 6)).astype(np.float32)
    c, x = rng.integers(0, 30, 16), rng.integers(0, 30, 16)
    neg = rng.integers(0, 30, (16, 3))
    loss, ref = w2v_sgns.step(win, wout, c, x, neg, 0.025)
    sig = lambda z: 1 / (1 + np.exp(-z))
    want_in = np.zeros((30, 6)); want_out = np.zeros((30, 6)); want_loss = 0.0
    for i in range(16):
        v = win[c[i]].astype(np.float64)
        for t, label in [(x[i], 1.0)] + [(n, 0.0) for n in neg[i]]:
            u = wout[t].astype(np.float64)
            g = (label - sig(v @ u)) * 0.025
            want_in[c[i]] += g * u
            want_out[t] += g * v
            want_loss -= np.log(sig(v @ u if label else -(v @ u)))
    got_in = np.zeros((30, 6)); got_in[ref["in_ids"]] = ref["in_delta"]
    got_out = np.zeros((30, 6)); got_out[ref["out_ids"]] = ref["out_delta"]
    assert np.allclose(got_in, want_in, atol=1e-6)
    assert np.allclose(got_out, want_out, atol=1e-6)
    assert loss == pytest.approx(want_loss / 16, rel=1e-5)


def test_sgns_shared_pool_is_the_weighted_per_pair_objective():
    rng = np.random.default_rng(2)
    win = rng.normal(0, 0.3, (20, 4)).astype(np.float32)
    wout = rng.normal(0, 0.3, (20, 4)).astype(np.float32)
    c, x = rng.integers(0, 20, 8), rng.integers(0, 20, 8)
    pool = rng.integers(0, 20, 5)
    l1, r1 = w2v_sgns.step(win, wout, c, x, pool, 0.025, neg_weight=1.0)
    l2, r2 = w2v_sgns.step(win, wout, c, x, np.tile(pool, (8, 1)), 0.025)
    assert l1 == pytest.approx(l2, rel=1e-6)
    assert np.allclose(r1["out_delta"], r2["out_delta"], atol=1e-7)


def test_dlrm_reference_matches_the_programs_forward_on_the_cpu():
    """Not independence (that is the point of the reference) but a guard
    that both state the same architecture."""
    import jax.numpy as jnp
    from multiverso_tpu.models import dlrm

    cfg = dlrm.DLRMConfig(vocab_sizes=(11, 7, 5), embed_dim=8, dense_dim=4,
                          bottom_mlp=(16, 8), top_mlp=(16, 1))
    mlp = dlrm.init_mlp_params(cfg, 0)
    rng = np.random.default_rng(3)
    rows = rng.normal(size=(6, 3, 8)).astype(np.float32)
    dense = rng.normal(size=(6, 4)).astype(np.float32)
    labels = (rng.random(6) < 0.5).astype(np.float32)
    want = float(dlrm.loss_fn(mlp, jnp.asarray(rows), jnp.asarray(dense),
                              jnp.asarray(labels), cfg))
    got, g_mlp, g_rows = ref_dlrm.grads(
        {k: [np.asarray(a) for a in v] for k, v in mlp.items()},
        rows, dense, labels)
    assert got == pytest.approx(want, rel=1e-5)
    assert g_rows.shape == rows.shape
    ids = np.array([[0, 11, 18]] * 6)
    uids, acc = ref_dlrm.row_gradients(ids, g_rows)
    assert list(uids) == [0, 11, 18]
    assert np.allclose(acc, g_rows.sum(axis=0), atol=1e-6)


LAW = {"topic_zipf_a": 1.1, "offset_zipf_a": 1.3, "band": 50, "run_lo": 5,
       "run_hi": 50}


def test_generators_are_functions_of_the_seed():
    a = gen.corpus_ids(50_000, 5000, 2 ** 31 + 7, LAW)
    assert np.array_equal(a, gen.corpus_ids(50_000, 5000, 2 ** 31 + 7, LAW))
    assert not np.array_equal(a, gen.corpus_ids(50_000, 5000, 2 ** 31 + 8, LAW))
    assert a.dtype == np.int32 and a.size == 50_000 and 0 <= a.min() and a.max() < 5000
    p = gen.token_pmf(5000, LAW)
    emp = np.bincount(gen.corpus_ids(400_000, 5000, 1, LAW), minlength=5000) / 4e5
    assert abs(emp[:60] - p[:60]).max() < 0.01 and p.sum() == pytest.approx(1)
    # ids are ranks, and the law has a head as text has: the first ten
    # words are a good part of the stream, which a uniform law's are not
    assert np.all(np.diff(p) <= 0) and p[:10].sum() > 0.15
    flat = gen.token_pmf(5000, {**LAW, "topic_zipf_a": 0.0})
    assert flat[:10].sum() < 0.01
    assert gen.vocab_counts(5000, 10 ** 9, 5, LAW).min() >= 5


def test_block_reference_pairs_and_training():
    from benchmark.reference import w2v_sgns
    c, x = w2v_sgns.dynamic_window_pairs(np.arange(100), 5,
                                         np.random.default_rng(0))
    assert np.all(c != x) and np.abs(c - x).max() <= 5
    assert np.all(np.diff(c) >= 0) and 2 * 100 <= c.size <= 10 * 100
    rng = np.random.default_rng(1)
    win = rng.normal(size=(50, 8)).astype(np.float32) * 0.1
    wout = np.zeros((50, 8), np.float32)
    ids = rng.integers(0, 20, 400)
    c, x, n = w2v_sgns.block_inputs(ids, np.arange(50), 3, 2, 0)
    assert n.shape == (c.size, 2) and c.size == x.size
    win1, wout1, first = w2v_sgns.train_pairs(win, wout, c, x, n, 64, 0.05)
    _, _, later = w2v_sgns.train_pairs(win1, wout1, c, x, n, 64, 0.05)
    assert later < first and np.any(wout1[:20] != 0) and not wout.any()
    # one whole minibatch is step()'s arithmetic
    loss, d = w2v_sgns.step(win, wout, c[:64], x[:64], n[:64], 0.05)
    got_in, got_out, got = w2v_sgns.train_pairs(win, wout, c[:64], x[:64],
                                                n[:64], 64, 0.05)
    assert got == pytest.approx(loss, rel=1e-5)
    assert np.allclose(got_in[d["in_ids"]] - win[d["in_ids"]], d["in_delta"],
                       atol=1e-6)
    assert np.allclose(got_out[d["out_ids"]], d["out_delta"], atol=1e-6)
    cat, dense, labels = gen.ctr_batches([100, 7, 3000], 13, 32, 4, 1.05, 9)
    assert cat.shape == (4, 32, 3) and dense.shape == (4, 32, 13)
    assert cat[..., 1].max() < 7 and cat[..., 2].max() < 3000
    assert np.array_equal(cat, gen.ctr_batches([100, 7, 3000], 13, 32, 4,
                                               1.05, 9)[0])


def test_bytes_by_hand():
    assert shapes.scatter_add_bytes(10, 300) == 3 * 10 * 1200
    assert shapes.dense_update_bytes(1000, 128, state_arrays=1) == 5 * 512000
    assert shapes.table_fill_bytes(1000, 128) + shapes.dense_update_bytes(
        1000, 128, 1) == 6 * 512000                 # the dlrm step's passes
    assert shapes.table_copy_bytes(7, 300) == 2 * 7 * 1200
    assert shapes.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        shapes.peak("TPU v9", "hbm_bytes_per_s")
