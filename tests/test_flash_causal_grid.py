"""The flash kernels' causal grid (ops/attention_kernels.py): the table of
live (q block, k block) pairs a causal call walks, with no kernel at all,
and the three kernels over it in interpreter mode at small sizes, forward
and every gradient against plain XLA attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.mla_moe import _xla_attention
from multiverso_tpu.ops import attention_kernels as ak
from multiverso_tpu.parallel.ring import reference_attention

# (s, block_q, block_k): equal blocks, q over k, k over q, one block
# (s under a block), and the language-model cell's own
GRIDS = [(128, 32, 32), (256, 64, 32), (256, 32, 64), (64, 128, 128),
         (192, 64, 32), (8192, 512, 512)]


def _brute(s, bq, bk):
    """Every pair by its positions: live where some q >= some k, crossing
    where besides some q < some k."""
    bq, bk = min(bq, s), min(bk, s)
    live, crossing = set(), set()
    for i in range(s // bq):
        for j in range(s // bk):
            if j * bk <= i * bq + bq - 1:
                live.add((i, j))
                if i * bq < j * bk + bk - 1:
                    crossing.add((i, j))
    return live, crossing


@pytest.mark.parametrize("s,bq,bk", GRIDS)
def test_table_holds_every_live_pair_once_and_no_dead_one(s, bq, bk):
    live, _ = _brute(s, bq, bk)
    for q_inner in (False, True):
        qi, kj, _ = ak.live_pairs(s, bq, bk, q_inner)
        assert qi.dtype == kj.dtype == np.int32
        pairs = list(zip(qi.tolist(), kj.tolist()))
        assert len(pairs) == len(set(pairs)) and set(pairs) == live


@pytest.mark.parametrize("s,bq,bk", GRIDS)
def test_forward_walks_row_major_and_dkv_column_major(s, bq, bk):
    qi, kj, _ = ak.live_pairs(s, bq, bk)
    assert list(zip(qi.tolist(), kj.tolist())) == sorted(
        zip(qi.tolist(), kj.tolist()))
    qi, kj, _ = ak.live_pairs(s, bq, bk, q_inner=True)
    assert list(zip(kj.tolist(), qi.tolist())) == sorted(
        zip(kj.tolist(), qi.tolist()))


@pytest.mark.parametrize("s,bq,bk", GRIDS)
def test_masked_exactly_on_the_pairs_the_diagonal_crosses(s, bq, bk):
    _, crossing = _brute(s, bq, bk)
    for q_inner in (False, True):
        qi, kj, masked = ak.live_pairs(s, bq, bk, q_inner)
        got = {(i, j) for i, j, m in zip(qi.tolist(), kj.tolist(), masked)
               if m}
        assert got == crossing


@pytest.mark.parametrize("s,bq,bk", GRIDS)
def test_init_and_emit_fall_on_a_rows_first_and_last_pair(s, bq, bk):
    """The kernels' scalar arithmetic (``_Walk.enter``) names the first
    and last pair of each accumulator's run, as the table has them."""
    bq, bk = min(bq, s), min(bk, s)
    nq, nk = s // bq, s // bk
    qi, kj, _ = ak.live_pairs(s, bq, bk)
    for i in range(nq):                     # forward, dQ: a q block's row
        row = kj[qi == i]
        assert row[0] == 0 and row[-1] == min(nk - 1, (i * bq + bq - 1) // bk)
        assert np.all(np.diff(np.flatnonzero(qi == i)) == 1)   # one run
    qi, kj, _ = ak.live_pairs(s, bq, bk, q_inner=True)
    for j in range(nk):                     # dK with dV: a k block's column
        col = qi[kj == j]
        assert col[0] == (j * bk) // bq and col[-1] == nq - 1
        assert np.all(np.diff(np.flatnonzero(kj == j)) == 1)


@pytest.mark.parametrize("s,bq,bk,want", [
    (8192, 512, 512, (136, 136, 16)),       # glm47f-train-8k's call
    (8192, 1024, 512, (72, 72, 16)),
    (8192, 512, 1024, (72, 72, 16)),
    (64, 128, 128, (1, 1, 1)),
    (128, 32, 32, (10, 10, 4)),
])
def test_causal_pairs_counts_steps_live_and_masked(s, bq, bk, want):
    got = ak.causal_pairs(s, bq, bk)
    assert (got["grid_steps"], got["live"], got["masked"]) == want


def test_blocks_that_do_not_divide_the_sequence_are_refused():
    with pytest.raises(ValueError, match="not divisible"):
        ak.live_pairs(96, 64, 32)


def _inputs(shape, seed):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for _ in range(4))


def _out_and_grads(fn, q, k, v, g):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(g)


@pytest.fixture
def sub_tiles(monkeypatch):
    """Give every call of ``flash_attention`` sub-tiles of ``sub`` rows
    and columns, whatever its blocks and head size (``None``: the rule's
    own choice, which leaves blocks this small whole)."""
    def of(sub):
        if sub is not None:
            monkeypatch.setattr(ak, "sub_tile", lambda bq, bk, d: sub)
    return of


@pytest.mark.parametrize("s,bq,bk,causal,sub", [
    (128, 32, 32, True, None),  # equal blocks: 10 pairs, 4 crossing
    (256, 64, 32, True, None),  # block_q > block_k: rows wholly masked
    (256, 32, 64, True, None),  # block_q < block_k
    (192, 64, 32, True, None),  # three q blocks over six k blocks
    (64, 128, 128, True, None),     # s under a block: one pair, crossing
    (128, 32, 64, False, None),     # no mask at all: the rectangle
    (128, 64, 64, False, None),
    # the same with a crossed pair cut into sub-tiles
    (128, 32, 32, True, 8),     # equal blocks, 4 x 4 sub-tiles a pair
    (128, 32, 32, True, 16),
    (256, 64, 32, True, 16),    # block_q > block_k: sub-blocks of rows dead
    (256, 64, 32, True, 32),    # ... and only the rows cut
    (256, 32, 64, True, 16),    # block_q < block_k
    (256, 32, 64, True, 32),    # ... and only the columns cut
    (192, 64, 32, True, 8),
    (64, 128, 128, True, 16),   # s under a block: the one pair, cut
    (128, 32, 64, False, 16),   # no crossed pair: nothing to cut
])
def test_kernels_match_xla_attention_forward_and_three_gradients(
        s, bq, bk, causal, sub, sub_tiles):
    sub_tiles(sub)
    q, k, v, g = _inputs((1, 2, s, 32), seed=s + bq)
    oracle = (_xla_attention if causal else
              lambda q, k, v: reference_attention(q, k, v, causal=False))
    got = jax.jit(lambda *a: _out_and_grads(
        lambda q, k, v: ak.flash_attention(q, k, v, causal, bq, bk, True),
        *a))(q, k, v, g)
    want = jax.jit(lambda *a: _out_and_grads(oracle, *a))(q, k, v, g)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def test_inference_call_without_the_residual_matches_too():
    q, k, v, _ = _inputs((2, 1, 128, 32), seed=7)
    got = ak.flash_attention(q, k, v, True, 32, 64, True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_xla_attention(q, k, v)),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------- #
# the band: a window under the diagonal; grouped-query heads
# ---------------------------------------------------------------------- #
# (s, block_q, block_k, window): a window under a block, of a block, of
# several, one that cuts blocks unevenly, and of s or more (none at all)
BANDS = [(256, 32, 32, 8), (256, 32, 32, 32), (256, 32, 32, 96),
         (256, 64, 32, 48), (256, 32, 64, 100), (192, 64, 32, 1),
         (256, 32, 32, 256), (256, 32, 64, 1000), (8192, 512, 512, 1024),
         (8192, 512, 1024, 1024)]


def _brute_band(s, bq, bk, window):
    """Every pair by its positions: live where some (q, k) has
    ``0 <= q - k < window``, crossing where some other has not."""
    q, k = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (q >= k) & (q - k < window)
    tiles = seen.reshape(s // bq, bq, s // bk, bk)
    some, every = tiles.any((1, 3)), tiles.all((1, 3))
    live = {(int(i), int(j)) for i, j in zip(*np.nonzero(some))}
    crossing = {(int(i), int(j)) for i, j in zip(*np.nonzero(some & ~every))}
    return live, crossing


@pytest.mark.parametrize("q_inner", [False, True])
@pytest.mark.parametrize("s,bq,bk,window", BANDS)
def test_windowed_table_is_its_numpy_statement(s, bq, bk, window, q_inner):
    if s > 1024:        # the statement by positions is 67M booleans there
        live = {(i, j) for i in range(s // bq) for j in range(s // bk)
                if j * bk <= i * bq + bq - 1
                and i * bq - (j * bk + bk - 1) < window}
        crossing = None
    else:
        live, crossing = _brute_band(s, bq, bk, window)
    qi, kj, masked = ak.live_pairs(s, bq, bk, q_inner, window)
    pairs = list(zip(qi.tolist(), kj.tolist()))
    assert len(pairs) == len(set(pairs)) and set(pairs) == live
    key = (lambda p: (p[1], p[0])) if q_inner else (lambda p: p)
    assert pairs == sorted(pairs, key=key)
    if crossing is not None:
        assert {p for p, m in zip(pairs, masked) if m} == crossing
    if window >= s:     # no window at all: today's table, entry for entry
        for got, want in zip(ak.live_pairs(s, bq, bk, q_inner, window),
                             ak.live_pairs(s, bq, bk, q_inner)):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("s,bq,bk,window", BANDS)
def test_windowed_init_and_emit_fall_on_a_runs_first_and_last_pair(
        s, bq, bk, window):
    """``_Walk.enter``'s scalar arithmetic for a band: a q block's run of
    k blocks (forward, dQ) and a k block's run of q blocks (dK with dV)
    are contiguous in the table and start and end where it says."""
    w = ak._band(s, window)
    far = s if w is None else w
    qi, kj, _ = ak.live_pairs(s, bq, bk, window=window)
    for i in range(s // bq):
        run = np.flatnonzero(qi == i)
        assert np.all(np.diff(run) == 1)
        assert kj[run[0]] == max(0, (i * bq - far + 1) // bk)
        assert kj[run[-1]] == min(s // bk - 1, (i * bq + bq - 1) // bk)
    qi, kj, _ = ak.live_pairs(s, bq, bk, True, window)
    for j in range(s // bk):
        run = np.flatnonzero(kj == j)
        assert np.all(np.diff(run) == 1)
        assert qi[run[0]] == (j * bk) // bq
        assert qi[run[-1]] == min(s // bq - 1, (far + j * bk + bk - 2) // bq)


@pytest.mark.parametrize("s,bq,bk,window,want", [
    (8192, 512, 512, 1024, (45, 45, 30)),     # mellum2-train-8k's window
    (8192, 512, 1024, 1024, (30, 30, 30)),
    (8192, 512, 512, 8192, (136, 136, 16)),   # no window: the causal table
    (8192, 512, 512, None, (136, 136, 16)),
])
def test_causal_pairs_counts_a_bands_steps(s, bq, bk, window, want):
    got = ak.causal_pairs(s, bq, bk, window)
    assert (got["grid_steps"], got["live"], got["masked"]) == want


def _masked_attention(q, k, v, window):
    """Plain attention under a causal band, float32: q [B, H, S, D], k and
    v [B, Hkv, S, D], query head h reading key-value head h // group."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    i, j = jnp.arange(q.shape[2])[:, None], jnp.arange(q.shape[2])[None, :]
    seen = (i >= j) & ((i - j < window) if window else True)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v)


# sub-tiles of 8 and 16 under blocks of 32 and 64: a window under a
# sub-tile, of one, of several, and (48 over 16s and 32s, 100) no multiple
@pytest.mark.parametrize("sub", [None, 8, 16])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("s,bq,bk,window", [
    (256, 32, 32, 8),           # under a block
    (256, 32, 32, 32),          # of a block
    (256, 32, 32, 96),          # of several
    (256, 64, 32, 48),          # q block over k block: rows wholly masked
    (256, 32, 64, 100),         # k block over q block, an uneven edge
    (128, 32, 32, None),        # grouped heads under the plain diagonal
    (128, 32, 32, 4096),        # a window of s or more is none
])
def test_banded_grouped_kernels_match_plain_masked_attention(
        s, bq, bk, window, group, sub, sub_tiles):
    sub_tiles(sub)
    rng = np.random.default_rng(s + bq + group)
    draw = lambda h: jnp.asarray(rng.normal(size=(2, h, s, 32)), jnp.float32)
    q, k, v, g = draw(group), draw(1), draw(1), draw(group)
    got = jax.jit(lambda *a: _out_and_grads(
        lambda q, k, v: ak.flash_attention(q, k, v, True, bq, bk, True,
                                           window), *a))(q, k, v, g)
    want = jax.jit(lambda *a: _out_and_grads(
        lambda q, k, v: _masked_attention(q, k, v, window), *a))(q, k, v, g)
    assert got[2].shape == got[3].shape == k.shape      # never repeated
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=3e-5, err_msg=name)


def test_grouped_heads_without_a_causal_walk_are_refused():
    q, k, v, _ = _inputs((1, 2, 64, 32), seed=1)
    with pytest.raises(ValueError, match="causal"):
        ak.flash_attention(q, k[:, :1], v[:, :1], False, 32, 32, True)
    with pytest.raises(ValueError, match="do not divide"):
        ak.flash_attention(jnp.concatenate([q, q[:, :1]], 1), k, v, True,
                           32, 32, True)


# ---------------------------------------------------------------------- #
# sub-tiles: a crossed pair computes its live sub-tiles and masks only
# those an edge passes through
# ---------------------------------------------------------------------- #
def _pair_tiles(s, bq, bk, window, sub):
    """The sub-tiles of the crossed pairs that hold a live position,
    counted by positions, a kind of pair at a time (pairs of one
    ``i * bq - j * bk`` are alike): (those sub-tiles, a sub-tile's size,
    the interior pairs)."""
    sq, sk = min(sub or bq, bq), min(sub or bk, bk)
    qi, kj, crossing = ak.live_pairs(s, bq, bk, window=window)
    kinds, count = np.unique((qi * bq - kj * bk)[crossing],
                             return_counts=True)
    far = s if window is None else window
    a, b = np.arange(bq)[:, None], np.arange(bk)[None, :]
    computed = 0
    for kind, n in zip(kinds.tolist(), count.tolist()):
        seen = (kind + a - b >= 0) & (kind + a - b < far)
        tiles = seen.reshape(bq // sq, sq, bk // sk, sk)
        computed += n * int(tiles.any((1, 3)).sum())
    return computed, sq * sk, int((~crossing).sum())


@pytest.mark.parametrize("s,bq,bk,window,needed,whole,at_256", [
    # mellum2-train-8k's window layers: every pair crossed, twice the band
    (8192, 1024, 1024, 1024, 7_864_832, 15_728_640, 9_830_400),
    # trinity-train-16k's: 30 of 45 crossed
    (16384, 1024, 1024, 2048, 31_458_304, 47_185_920, 35_389_440),
    (8192, 1024, 1024, None, 33_558_528, 37_748_736, 34_603_008),
    (16384, 1024, 1024, None, 134_225_920, 142_606_336, 136_314_880),
    # glm47f-train-8k's call
    (8192, 512, 1024, None, 33_558_528, 37_748_736, 34_603_008),
])
def test_positions_computed_and_needed_are_their_numpy_statement(
        s, bq, bk, window, needed, whole, at_256):
    for sub, want in ((None, whole), (512, None), (256, at_256),
                      (128, None)):
        got = ak.causal_pairs(s, bq, bk, window, sub)
        tiles, size, interior = _pair_tiles(s, bq, bk, window, sub)
        assert got["computed"] == tiles * size + interior * bq * bk
        assert got["needed"] == needed == int(np.minimum(
            np.arange(s) + 1, window or s).sum())
        if want is not None:
            assert got["computed"] == want
    # what the issue's table says of sub-tiles of 128, to three places
    ratio = ak.causal_pairs(s, bq, bk, window, 128)["computed"] / needed
    assert round(ratio, 3) == {7_864_832: 1.125, 31_458_304: 1.062,
                               33_558_528: 1.016, 134_225_920: 1.008}[needed]


@pytest.mark.parametrize("q_inner", [False, True])
@pytest.mark.parametrize("sub", [8, 16, 32])
@pytest.mark.parametrize("s,bq,bk,window", BANDS[:8] + [(256, 64, 32, None)])
def test_pieces_cover_the_live_sub_tiles_and_mask_the_crossed_ones(
        s, bq, bk, window, sub, q_inner):
    """A kind's pieces by the dense mask written out: together they hold
    exactly the sub-tiles with a live position, each once, each piece one
    sub-block of rows (columns for dK with dV) against one contiguous run;
    and their edges are exactly the sub-tiles that also hold a dead one."""
    walk = ak._Walk(True, s, bq, bk, q_inner, ak._band(s, window), 1, sub)
    sq, sk = ak._sub_blocks(bq, bk, sub)
    far = s if walk.window is None else walk.window
    a, b = np.arange(bq)[:, None], np.arange(bk)[None, :]
    assert walk.kinds() == walk._replace(q_inner=not q_inner).kinds()
    for kind in walk.kinds():
        seen = (kind + a - b >= 0) & (kind + a - b < far)
        assert seen.any() and not seen.all()        # a crossed pair's
        tiles = seen.reshape(bq // sq, sq, bk // sk, sk)
        covered = np.zeros((bq // sq, bk // sk), int)
        crossed = np.zeros_like(covered)
        for p in walk.pieces(kind):
            rows = np.arange(bq)[p.rows][::sq] // sq
            cols = np.arange(bk)[p.cols][::sk] // sk
            assert (len(cols) if q_inner else len(rows)) == 1
            covered[np.ix_(rows, cols)] += 1
            for start, stop, corner in p.edges:
                if p.axis:      # along the columns
                    r, c = rows[0], (p.cols.start + start) // sk
                else:
                    r, c = (p.rows.start + start) // sq, cols[0]
                assert (stop - start, p.axis) == (
                    (sk, 1) if not q_inner else (sq, 0))
                assert corner == kind + r * sq - c * sk
                crossed[r, c] += 1
        np.testing.assert_array_equal(covered, tiles.any((1, 3)))
        np.testing.assert_array_equal(
            crossed, tiles.any((1, 3)) & ~tiles.all((1, 3)))


@pytest.mark.parametrize("s,bq,bk,window,sub,group", [
    (256, 32, 32, None, 8, 1), (256, 32, 32, 32, 16, 8),
    (256, 64, 32, 48, 16, 1), (256, 32, 64, 100, 16, 8),
    (256, 64, 64, 40, 16, 2), (256, 64, 64, 128, 32, 1),
])
def test_sub_tiled_kernels_agree_with_the_whole_tile_ones(
        s, bq, bk, window, sub, group):
    """Same inputs, ``sub`` under the block against ``sub`` of the block:
    the same positions computed and masked, sums associated otherwise."""
    rng = np.random.default_rng(s + bq + sub)
    draw = lambda h: jnp.asarray(rng.normal(size=(2, h, s, 32)), jnp.float32)
    q, k, v, g = draw(group), draw(1), draw(1), draw(group)
    cut, whole = (jax.jit(lambda *a, sub=sub: _out_and_grads(
        lambda q, k, v: ak._attention(q, k, v, True, bq, bk, True, window,
                                      sub), *a))(q, k, v, g)
        for sub in (sub, max(bq, bk)))
    for name, a, b in zip(("out", "dq", "dk", "dv"), cut, whole):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(
            jnp.linalg.norm(b)), name


def _kernel_jaxprs(sub, window=48):
    q, k, v, g = _inputs((1, 2, 128, 32), seed=3)
    return str(jax.make_jaxpr(lambda *a: _out_and_grads(
        lambda q, k, v: ak._attention(q, k, v, True, 64, 32, True, window,
                                      sub), *a))(q, k, v, g))


def test_sub_tiles_of_a_block_lower_to_the_whole_tile_kernels():
    """``sub`` of the block or more is no sub-tile at all: the program is
    the one without ``sub``, equation for equation, and each of its three
    kernels holds the four branches it held before sub-tiles (open the
    accumulator, a crossed pair under its mask, an interior pair, write),
    two products a tile forward, three for dQ, four for dK with dV."""
    whole = _kernel_jaxprs(None)
    assert _kernel_jaxprs(64) == whole == _kernel_jaxprs(1024)
    assert whole.count("pallas_call") == 3
    assert whole.count(" cond[") == 3 * 4
    assert whole.count("dot_general") == 2 * (2 + 3 + 4)
    assert whole.count("concatenate") == 0
    cut = _kernel_jaxprs(16)
    # a branch a kind of crossed pair in the interior pair's place
    kinds = len(ak._Walk(True, 128, 64, 32, False, 48, 1, 16).kinds())
    assert kinds > 2 and cut.count(" cond[") == 3 * (3 + kinds)


def test_sub_tiles_that_do_not_divide_a_block_are_refused():
    q, k, v, _ = _inputs((1, 1, 128, 32), seed=1)
    with pytest.raises(ValueError, match="sub-tiles"):
        ak._attention(q, k, v, True, 32, 32, True, None, 12)


@pytest.mark.parametrize("bq,bk,d,want", [
    (1024, 1024, 128, 256),     # mellum2-train-8k, trinity-train-16k
    (512, 1024, 256, 256),      # glm47f-train-8k
    (512, 512, 512, None),      # a head the chip has not read
    (1024, 256, 128, None),     # a block of two sub-tiles' rows at most
    (128, 128, 64, None),       # the dense model's blocks
    (32, 32, 32, None),         # a test's
])
def test_the_sub_tile_follows_the_blocks_and_the_head_size(bq, bk, d, want):
    assert ak.sub_tile(bq, bk, d) == want


def test_a_kernel_called_again_on_the_same_shapes_is_traced_once():
    """Two layers' calls (and a third on other blocks) in one program:
    the forward, dQ and dK with dV of the repeated call are traced once
    each, their equations carry the SAME kernel jaxpr (so a program lowers
    them once), and results are those of separate traces."""
    q, k, v, g = _inputs((1, 2, 128, 32), seed=11)
    ak._TRACED.clear()

    def two_layers(q, k, v):
        o = ak._attention(q, k, v, True, 32, 32, True, 48, 16)
        o = ak._attention(o, k, v, True, 32, 32, True, 48, 16)
        return ak._attention(o, k, v, True, 64, 32, True, 48, 16)

    jaxpr = jax.make_jaxpr(lambda *a: _out_and_grads(two_layers, *a))(
        q, k, v, g)
    assert len(ak._TRACED) == 2 * 3
    kernels = [e.params["jaxpr"] for e in jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 3 * 3 and len({id(j) for j in kernels}) == 2 * 3
    got = jax.jit(lambda *a: _out_and_grads(two_layers, *a))(q, k, v, g)
    ak._TRACED.clear()
    one = lambda bq: lambda q, k, v: ak._attention(q, k, v, True, bq, 32,
                                                   True, 48, 16)
    want = _out_and_grads(
        lambda q, k, v: one(64)(one(32)(one(32)(q, k, v), k, v), k, v),
        q, k, v, g)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6, err_msg=name)
