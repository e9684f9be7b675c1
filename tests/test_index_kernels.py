"""The two kernels of the indexer's term (``ops/index_kernels.py``:
``index_term_stats`` and ``index_term_grads``, which walk a chunk's key
tiles up to its diagonal) in Pallas's interpreter on the CPU: against the
XLA form of the same lines (``keye_moe._kl_chunks`` at ``attn="xla"``) to
float32 round-off, and against plain autodiff of the term's definition
over whole arrays."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from multiverso_tpu.models import keye_moe
from multiverso_tpu.ops import index_kernels

CFG = keye_moe.KeyeMoEConfig(
    dim=48, n_heads=4, n_kv_heads=2, head_dim=8, index_heads=3, index_dim=6,
    index_topk=8, index_chunk=16, attn="flash", compute_dtype=jnp.float32)
NAMES = ("term", "dqI", "dkI", "dw")


def _operands(cfg, batch=2, positions=64, seed=0, dtype=jnp.float32):
    """(qI, kI, w) as ``index_operands`` would give them, and the core's q
    and k; random."""
    hi, di = cfg.index_heads, cfg.index_dim
    keys = jax.random.split(jax.random.key(seed), 5)
    qi = jax.random.normal(keys[0], (batch, positions, hi, di))
    ki = jax.random.normal(keys[1], (batch, positions, di))
    w = jax.random.normal(keys[2], (batch, positions, hi)) * (hi * di) ** -0.5
    q = jax.random.normal(keys[3], (batch, cfg.n_heads, positions,
                                    cfg.head_dim), dtype)
    k = jax.random.normal(keys[4], (batch, cfg.n_kv_heads, positions,
                                    cfg.head_dim), dtype)
    return (qi, ki, w), q, k


def _term_and_grads(cfg, operands, q, k, chosen, tile=None, monkeypatch=None):
    """``index_loss`` and its gradients to (qI, kI, w) under ``cfg``, the
    kernels' key tile forced to ``tile`` where one is given."""
    if tile is not None:
        monkeypatch.setattr(index_kernels, "key_tile", lambda *_: tile)
        index_kernels._TRACED.clear()
    value, grads = jax.jit(jax.value_and_grad(
        lambda *a: keye_moe.index_loss(*a, q, k, chosen, cfg),
        (0, 1, 2)))(*operands)
    return (value,) + tuple(grads)


def _definition(operands, q, k, chosen, dtype=jnp.float32):
    """The term over whole arrays, and plain autodiff of it."""
    def term(qi, ki, w):
        b, h, s, hd = q.shape
        live = chosen != 0
        index = keye_moe._scores(qi, ki, w, dtype)
        dots = jnp.einsum(
            "bkgrd,bksd->bkgrs", q.astype(jnp.float32).reshape(
                b, k.shape[1], h // k.shape[1], s, hd),
            k.astype(jnp.float32)) / hd ** 0.5
        pbar = jnp.mean(jax.nn.softmax(jnp.where(
            live[:, None, None], dots, -jnp.inf), -1), (1, 2))
        logq = jax.nn.log_softmax(jnp.where(live, index, -jnp.inf), -1)
        return jnp.sum(jnp.where(
            live, jax.scipy.special.xlogy(pbar, pbar)
            - pbar * jnp.where(live, logq, 0.0), 0.0)) / (b * s)

    value, grads = jax.jit(jax.value_and_grad(term, (0, 1, 2)))(*operands)
    return (value,) + tuple(grads)


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _one_key(chosen):
    """The selection with the last sequence's last row cut to ONE key, its
    own."""
    s = chosen.shape[-1]
    return chosen.at[-1, -1].set(
        (jnp.arange(s) == s - 1).astype(chosen.dtype))


@pytest.mark.parametrize("shape", [
    # (batch, positions, chunk, key tile): several chunks, so that the
    # first (rows that select t + 1 < index_topk keys) and a last are met
    (2, 64, 16, None), (2, 64, 16, 8), (2, 64, 16, 4), (1, 64, 32, 16),
    (2, 64, 32, 32), (1, 128, 16, 16), (2, 32, 32, 8), (1, 64, 64, 16)])
def test_the_kernels_are_the_xla_form_to_round_off_and_the_definition(
        shape, monkeypatch):
    batch, positions, chunk, tile = shape
    cfg = CFG._replace(index_chunk=chunk)
    operands, q, k = _operands(cfg, batch, positions)
    chosen = _one_key(keye_moe.selection(*operands, cfg))
    want = _term_and_grads(cfg._replace(attn="xla"), operands, q, k, chosen)
    got = _term_and_grads(cfg, operands, q, k, chosen, tile, monkeypatch)
    plain = _definition(operands, q, k, chosen)
    for name, a, b, c in zip(NAMES, got, want, plain):
        assert a.shape == b.shape and a.dtype == jnp.float32, name
        assert _err(a, b) < 1e-5, (name, _err(a, b))
        assert _err(a, c) < 1e-5, (name, _err(a, c))
    # a row's selection of one key is a softmax of one: nothing to learn
    assert not np.any(np.asarray(got[1])[-1, -1])
    assert not np.any(np.asarray(got[3])[-1, -1])


@pytest.mark.parametrize("tile", [None, 8])
def test_bfloat16_operands_stay_within_the_flash_tests_tolerance(
        tile, monkeypatch):
    """The cell's precisions: the heads' dots and the transposed products
    with bfloat16 operands. Both forms sit as near the float32 definition
    as each other."""
    cfg = CFG._replace(compute_dtype=jnp.bfloat16)
    operands, q, k = _operands(cfg, dtype=jnp.bfloat16)
    chosen = keye_moe.selection(*operands, cfg)
    got = _term_and_grads(cfg, operands, q, k, chosen, tile, monkeypatch)
    xla = _term_and_grads(cfg._replace(attn="xla"), operands, q, k, chosen)
    plain = _definition(operands, q, k, chosen)
    for name, a, b, c in zip(NAMES, got, xla, plain):
        assert _err(a, c) < 3e-2, (name, _err(a, c))
        assert _err(a, b) < 3e-2, (name, _err(a, b))


def _planted(cfg, batch=1, positions=64):
    """Operands whose scores grow with the key's position from key 16 on
    and are lowest on keys 0 .. 15: every row from 24 on selects its own
    last 8 keys, so the key tile 0 .. 15 holds no selected key of the
    chunks under it."""
    operands, q, k = _operands(cfg, batch, positions)
    hi, di = cfg.index_heads, cfg.index_dim
    at = jnp.arange(positions, dtype=jnp.float32)
    ki = jnp.zeros((batch, positions, di)).at[..., 0].set(
        jnp.where(at < 16, 0.0, at))
    qi = jnp.zeros((batch, positions, hi, di)).at[..., 0].set(1.0)
    w = jnp.full((batch, positions, hi), 0.1)
    return (qi + 0.01 * operands[0], ki + 0.01 * operands[1], w), q, k


def test_a_key_tile_under_the_diagonal_may_hold_no_selected_key(monkeypatch):
    cfg = CFG
    operands, q, k = _planted(cfg)
    chosen = keye_moe.selection(*operands, cfg)
    assert not np.any(np.asarray(chosen)[:, 32:, :16])   # tile 0, chunks 2, 3
    assert np.all(np.asarray(chosen).sum(-1)[:, 7:] == cfg.index_topk)
    want = _term_and_grads(cfg._replace(attn="xla"), operands, q, k, chosen)
    got = _term_and_grads(cfg, operands, q, k, chosen, 16, monkeypatch)
    for name, a, b in zip(NAMES, got, want):
        assert np.all(np.isfinite(np.asarray(a))), name
        assert _err(a, b) < 1e-5, (name, _err(a, b))


def _chunk_call(cfg, operands, q, k, chosen, chunk, tile, ki=None,
                carry=None):
    """``index_kernels.term_chunk`` of chunk ``chunk`` alone, with the
    target ``pbar`` of the definition."""
    qi, ki_true, w = operands
    rows = cfg.index_chunk
    b, h, s, hd = q.shape
    live = chosen != 0
    dots = jnp.einsum("bkgrd,bksd->bkgrs", q.reshape(
        b, k.shape[1], h // k.shape[1], s, hd), k) / hd ** 0.5
    pbar = jnp.where(live, jnp.mean(jax.nn.softmax(jnp.where(
        live[:, None, None], dots, -jnp.inf), -1), (1, 2)), 0.0)
    cut = slice(chunk * rows, (chunk + 1) * rows)
    carry = jnp.zeros(ki_true.shape, jnp.float32) if carry is None else carry
    return index_kernels.term_chunk(
        jnp.int32(chunk), qi[:, cut], ki_true if ki is None else ki,
        w[:, cut], chosen[:, cut], pbar[:, cut], carry, tile=tile)


@pytest.mark.parametrize("chunk,tile", [(0, 16), (1, 8), (2, 16), (3, 4)])
def test_the_walk_is_causal_keys_past_the_diagonal_are_never_read(chunk,
                                                                  tile):
    """NaN in every key past the chunk's last row, and in the carry's rows
    there: the results are finite, equal to the clean call's, and the
    carry's rows past the walk are the carry's, untouched."""
    cfg = CFG
    operands, q, k = _operands(cfg)
    chosen = keye_moe.selection(*operands, cfg)
    past = jnp.arange(64) >= (chunk + 1) * cfg.index_chunk
    carry = jax.random.normal(jax.random.key(9), operands[1].shape)
    clean = _chunk_call(cfg, operands, q, k, chosen, chunk, tile, carry=carry)
    dirty = _chunk_call(
        cfg, operands, q, k, chosen, chunk, tile,
        ki=jnp.where(past[None, :, None], jnp.nan, operands[1]),
        carry=jnp.where(past[None, :, None], jnp.nan, carry))
    seen = ~np.asarray(past)
    for name, a, b in zip(("kl", "dqI", "dkI", "dw"), dirty, clean):
        a, b = np.asarray(a), np.asarray(b)
        if name == "dkI":
            assert np.all(np.isnan(a[:, ~seen]))        # the carry's own
            assert np.array_equal(b[:, ~seen], np.asarray(carry)[:, ~seen])
            a, b = a[:, seen], b[:, seen]
        assert np.all(np.isfinite(a)), name
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("shape", [(64, 16, 16), (64, 16, 4), (128, 32, 8),
                                   (64, 64, 16)])
def test_the_grids_counts_are_the_tiles_the_kernels_visit(shape, monkeypatch):
    """A visited key tile adds to its rows of the carry; one the walk never
    reaches leaves them as they were."""
    positions, chunk, tile = shape
    # every causal key selected: no visited tile without a live key
    cfg = CFG._replace(index_chunk=chunk, index_topk=positions)
    operands, q, k = _operands(cfg, 1, positions)
    chosen = keye_moe.selection(*operands, cfg)
    visited = 0
    for c in range(positions // chunk):
        dki = np.asarray(_chunk_call(cfg, operands, q, k, chosen, c, tile)[2])
        touched = np.any(dki.reshape(positions // tile, -1) != 0, -1)
        last = ((c + 1) * chunk) // tile
        assert np.all(touched[:last]) and not np.any(touched[last:])
        visited += int(touched.sum())
    monkeypatch.setattr(index_kernels, "key_tile", lambda *_: tile)
    grid = cfg.index_grid(positions)
    assert grid["index_tiles_walked"] == visited
    assert grid["index_tiles_whole"] == (positions // chunk) * (
        positions // tile)
    xla = cfg._replace(attn="xla").index_grid(positions)
    assert xla["index_tiles_walked"] == xla["index_tiles_whole"] == (
        grid["index_tiles_whole"])


def test_published_sizes_walk_half_the_rectangle_in_tiles_of_512():
    cfg = keye_moe.KeyeMoEConfig(
        dim=2048, n_heads=32, n_kv_heads=4, head_dim=128,
        mrope_section=(16, 24, 24), index_heads=16, index_dim=64,
        index_topk=2048, index_chunk=512, attn="flash")
    assert index_kernels.key_tile(512) == 512
    grid = cfg.index_grid(16384)
    assert (grid["index_tiles_walked"], grid["index_tiles_whole"]) == (
        528, 1024)


def test_tiles_divide_the_chunk_and_the_chunk_the_sequence():
    with pytest.raises(ValueError, match="do not divide"):
        index_kernels.walk_of(64, 16, tile=12)
    with pytest.raises(ValueError, match="do not divide"):
        index_kernels.walk_of(72, 16)
    assert index_kernels.walk_of(64, 16).tile == 16
    assert index_kernels.key_tile(2048) == 128
