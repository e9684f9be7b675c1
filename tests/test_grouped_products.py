"""The held experts' grouped products end where the routed rows end: the
buffer's padding belongs to no group, the kernels' grid is the row tiles
that hold rows, and what the kernels leave unwritten past the last group
(NaN in Pallas's interpreter, anything on the chip) reaches no sum. The
passes round the kernels end there too: the gather into the buffer and
the scatter-add back walk the routed rows' chunks and give what the
whole-buffer passes gave."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from multiverso_tpu.models import mla_moe
from multiverso_tpu.parallel import moe

TILE = (8, 32, 32)
# loads of four groups in a 64-row buffer
LOADS = {"under_the_buffer": [10, 7, 5, 9],
         "one_expert_idle": [10, 0, 7, 9],
         "two_idle_at_the_end": [5, 3, 0, 0],
         "nothing_routed_here": [0, 0, 0, 0],
         "fills_the_buffer": [16, 20, 12, 16]}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_rows_past_the_groups_reach_nothing(load):
    """``grouped_matmul`` in the interpreter, ``lhs`` and the cotangent
    NaN past the last group: the live rows, their ``d_lhs`` and ``d_rhs``
    are finite and XLA's; the interpreter leaves the rows past the groups
    unwritten (NaN), as the chip leaves them whatever they were."""
    m, k, n = 64, 64, 32
    sizes = LOADS[load]
    groups = jnp.asarray(sizes, jnp.int32)
    live = np.arange(m) < sum(sizes)
    rng = np.random.default_rng(len(load))
    lhs, ct = rng.normal(size=(m, k)), rng.normal(size=(m, n))
    lhs[~live] = ct[~live] = np.nan
    lhs, ct = jnp.asarray(lhs, jnp.float32), jnp.asarray(ct, jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    got, got_vjp = jax.vjp(lambda a, b: moe.grouped_matmul(
        a, b, groups, TILE, True, jnp.float32), lhs, rhs)
    want, want_vjp = jax.vjp(lambda a, b: moe._grouped_matmul_xla(
        a, b, groups, jnp.float32), lhs, rhs)
    (d_lhs, d_rhs), (want_lhs, want_rhs) = got_vjp(ct), want_vjp(ct)
    for a, b in ((got, want), (d_lhs, want_lhs)):
        a, b = np.asarray(a)[live], np.asarray(b)[live]
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, atol=1e-4)
    assert np.isfinite(np.asarray(d_rhs)).all()
    np.testing.assert_allclose(np.asarray(d_rhs), np.asarray(want_rhs),
                               atol=1e-4)
    # an idle expert's matrix takes a zero gradient, not an unwritten one
    assert not np.asarray(d_rhs)[np.asarray(sizes) == 0].any()
    # XLA answers zero rows past the groups; the kernel never writes them
    assert not np.asarray(want)[~live].any()
    if (~live).any():
        assert np.isnan(np.asarray(got)[~live]).all()


@pytest.mark.parametrize("tile", [
    (16, 128, 256),     # k = 320: three tiles, the last half masked; n whole
    (16, 256, 128),     # k = 320: two tiles; n = 464: four, the last 80 wide
    (16, 256, 256),     # neither width in whole tiles
    (16, 320, 256),     # the whole of k in one tile: nothing to mask
])
def test_a_tile_that_divides_neither_width_gives_the_plain_products(tile):
    """``grouped_matmul`` at a tile that divides neither ``k`` (320) nor
    ``n`` (464), as ``moe.product_tile`` hands one over for a width like
    1,856: ``megablox`` masks the last ``k`` tile's columns past the width
    (whatever lies there, NaN in the interpreter, must reach no sum) and
    drops the last ``n`` tile's. Forward, the buffer's gradient (the tile
    turned round: ``n`` is contracted, so ITS last tile is the masked one)
    and the weights' float32 gradient against the plain product a group,
    the rows past the last group NaN coming in and unwritten going out."""
    m, k, n = 96, 320, 464
    sizes = [20, 0, 33, 11]
    groups = jnp.asarray(sizes, jnp.int32)
    ends = np.cumsum(sizes)
    live = np.arange(m) < ends[-1]
    rng = np.random.default_rng(sum(tile))
    lhs, ct = rng.normal(size=(m, k)), rng.normal(size=(m, n))
    lhs[~live] = ct[~live] = np.nan
    rhs = rng.normal(size=(len(sizes), k, n))
    got, vjp = jax.vjp(
        lambda a, b: moe.grouped_matmul(a, b, groups, tile, True,
                                        jnp.float32),
        jnp.asarray(lhs, jnp.float32), jnp.asarray(rhs, jnp.float32))
    d_lhs, d_rhs = vjp(jnp.asarray(ct, jnp.float32))
    assert d_rhs.dtype == jnp.float32 and d_rhs.shape == rhs.shape
    for g, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        np.testing.assert_allclose(
            np.asarray(got)[lo:hi], np.einsum(
                "rk,kn->rn", lhs[lo:hi], rhs[g]), rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(
            np.asarray(d_lhs)[lo:hi], np.einsum(
                "rn,kn->rk", ct[lo:hi], rhs[g]), rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(
            np.asarray(d_rhs)[g], np.einsum(
                "rk,rn->kn", lhs[lo:hi], ct[lo:hi]), rtol=1e-4, atol=1e-3)
    assert np.isnan(np.asarray(got)[~live]).all()
    assert np.isnan(np.asarray(d_lhs)[~live]).all()


def _layer(form: str, route: str):
    """A layer of 8 experts, 4 held from the third on, 48 tokens choosing
    2: about 48 rows here, in a buffer of 96 (12 row tiles of 8)."""
    t, d, f, e, held = 48, 64, 32, 8, 4
    rng = jax.random.split(jax.random.key(len(form) + len(route)), 5)
    u = jax.random.normal(rng[0], (t, d))
    params = {"router": 0.3 * jax.random.normal(rng[1], (e, d)),
              "w_up": 0.2 * jax.random.normal(rng[2], (held, d, f)),
              "w_down": 0.2 * jax.random.normal(rng[3], (held, f, d))}
    if form == "gated_silu":
        params["w_gate"] = 0.2 * jax.random.normal(rng[4], (held, d, f))
    cfg = moe.HeldExperts(num_experts=e, experts_held=held, expert_offset=2,
                          top_k=2, routed_scale=1.5, buffer_rows=96,
                          tile=TILE, dtype=jnp.float32, route=route,
                          form=form)
    return u, params, jnp.linspace(-0.02, 0.02, e), cfg


@pytest.mark.parametrize("route", ["sigmoid", "softmax"])
@pytest.mark.parametrize("form", ["gated_silu", "relu2"])
def test_the_layer_is_the_same_in_the_kernel_and_in_xla(form, route):
    """``held_expert_layer`` with its buffer half full, the kernel in the
    interpreter (where an unwritten row is NaN) against ``ragged_dot``:
    the value and the gradient of the input and of every parameter."""
    u, params, bias, cfg = _layer(form, route)
    weight = jax.random.normal(jax.random.key(9), u.shape)

    def loss(u, params, kernel):
        out, counts, overflow, balance = moe.held_expert_layer(
            u, params, bias, cfg, kernel)
        return jnp.sum(out * weight) + balance, (out, counts, overflow)

    results = {}
    for kernel in ("interpret", "xla"):
        (_, (out, counts, overflow)), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1), has_aux=True),
            static_argnums=2)(u, params, kernel)
        results[kernel] = (out, grads)
        here = int(counts[2:6].sum())
        assert int(overflow) == 0 and 24 <= here <= 72     # about half
    for got, want in zip(jax.tree.leaves(results["interpret"]),
                         jax.tree.leaves(results["xla"])):
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4 * float(jnp.abs(want).max())
                                   + 1e-6)
    assert float(jnp.abs(results["xla"][1][1]["w_down"]).max()) > 0


@pytest.mark.parametrize("buffer_rows,want_overflow", [(96, False),
                                                       (None, False),
                                                       (16, True)])
def test_the_groups_sum_to_the_held_rows(monkeypatch, buffer_rows,
                                         want_overflow):
    """Not to the buffer's length: the padding is in no group. A buffer
    under the load cuts the groups at its end."""
    u, params, bias, cfg = _layer("gated_silu", "sigmoid")
    cfg = cfg._replace(buffer_rows=buffer_rows)
    seen = []
    product = moe._grouped_matmul_xla

    def spy(lhs, rhs, group_sizes, dtype):
        seen.append((lhs.shape[0], np.asarray(group_sizes)))
        return product(lhs, rhs, group_sizes, dtype)

    monkeypatch.setattr(moe, "_grouped_matmul_xla", spy)
    _, counts, overflow, _ = moe.held_expert_layer(u, params, bias, cfg,
                                                   "xla")
    here = np.asarray(counts)[2:6]
    rows = moe.buffer_length(cfg, u.shape[0])
    assert rows == {96: 96, None: 96, 16: 16}[buffer_rows]
    assert len(seen) == 3       # gate, up, down: the same groups
    for length, groups in seen:
        assert length == rows
        assert groups.sum() == here.sum() - int(overflow) <= rows
        np.testing.assert_array_equal(
            groups, np.diff(np.minimum(np.cumsum(here), rows), prepend=0))
    assert (int(overflow) > 0) == want_overflow
    if not want_overflow:
        assert 0 < seen[0][1].sum() < rows


def _whole_buffer_layer(u, params, bias, cfg):
    """``held_expert_layer`` as it was before its passes walked chunks:
    one gather over the whole buffer, one scatter-add over all of it,
    selected by ``live``, and XLA's own transposes of both."""
    t, d = u.shape
    held, k = cfg.experts_held, cfg.top_k
    if cfg.route == "softmax":
        chosen, gates, counts, balance = moe.softmax_route(
            u, params["router"], cfg)
    else:
        chosen, gates, counts = moe.sigmoid_route(u, params["router"], bias,
                                                  cfg)
        balance = jnp.zeros((), jnp.float32)
    rows = moe.buffer_length(cfg, t)
    local = chosen.reshape(-1) - cfg.expert_offset
    here = (local >= 0) & (local < held)
    order = jnp.argsort(jnp.where(here, local, held), stable=True)
    take = (order[:rows] if rows <= t * k
            else jnp.pad(order, (0, rows - t * k)))
    sizes = counts[cfg.expert_offset:cfg.expert_offset + held]
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    held_rows = ends[-1]
    groups = jnp.diff(ends, prepend=0)
    live = jnp.arange(rows) < held_rows
    token = take // k
    x = jnp.where(live[:, None], u.astype(cfg.dtype)[token], 0)
    gate = jnp.where(live, gates.reshape(-1)[take], 0.0)
    y = moe.expert_products(x, params, groups, cfg, "xla")
    out = jnp.zeros((t, d), jnp.float32).at[token].add(
        jnp.where(live[:, None], y.astype(jnp.float32), 0.0)
        * gate[:, None])
    return out, counts, sizes.sum() - held_rows, balance


def _steered(u, params, held_first: bool):
    """``u`` and the router with one feature that decides every choice:
    the held experts (the third to the sixth) win it for every token, or
    lose it."""
    sign = jnp.where((jnp.arange(8) >= 2) & (jnp.arange(8) < 6),
                     1.0, -1.0) * (1 if held_first else -1)
    return (u.at[:, 0].set(10.0),
            dict(params, router=params["router"].at[:, 0].set(3 * sign)))


def _both_layers(u, params, bias, cfg):
    """(value, counts, overflow), gradients of ``u`` and of every
    parameter: of the layer and of its whole-buffer form."""
    weight = jax.random.normal(jax.random.key(9), u.shape)
    results = []
    for layer in (lambda *a: moe.held_expert_layer(*a, "xla"),
                  _whole_buffer_layer):
        def loss(u, params):
            out, counts, overflow, balance = layer(u, params, bias, cfg)
            return jnp.sum(out * weight) + balance, (out, counts, overflow)

        (_, aux), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(u, params)
        results.append((aux, grads))
    return results


def _assert_the_same(got, want):
    """The counts and every parameter's gradient bit for bit (the same
    slots are added in the same order, a chunk after another); the value
    and ``u``'s gradient to a last place: XLA:CPU contracts a chunk's
    ``sums + y * gate`` into one rounding where the chunk fuses, and adds
    the route's part of ``u``'s gradient to the buffer's in another
    place of the whole-buffer program."""
    (out, *counts), (du, dparams) = got
    (want_out, *want_counts), (want_du, want_dparams) = want
    for a, b in zip(jax.tree.leaves((counts, dparams)),
                    jax.tree.leaves((want_counts, want_dparams))):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in ((out, want_out), (du, want_du)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=4e-7 * float(jnp.abs(b).max()))


# the sorted buffer's states: (buffer_rows, rows a tile, how the choices
# are steered); 48 tokens choose 2 of 8 experts, 4 held
BUFFERS = {"half_full": (96, 8, None),
           "sized_for_the_most": (None, 8, None),
           "exactly_full": ("the_load", 1, None),
           "under_the_load": (16, 8, None),
           "every_choice_held": (64, 8, True),
           "no_held_row": (96, 8, False)}


@pytest.mark.parametrize("state", sorted(BUFFERS))
@pytest.mark.parametrize("route", ["sigmoid", "softmax"])
@pytest.mark.parametrize("form", ["gated_silu", "relu2"])
def test_the_chunked_passes_give_the_whole_buffers(form, route, state,
                                                   monkeypatch):
    """The layer against its whole-buffer form written out above, value
    and every gradient, whatever the buffer holds: chunks of 40 rows, so
    that a buffer of 96 is no multiple of them."""
    monkeypatch.setattr(moe, "CHUNK", 40)
    u, params, bias, cfg = _layer(form, route)
    buffer_rows, tm, held_first = BUFFERS[state]
    if held_first is not None:
        u, params = _steered(u, params, held_first)
    if buffer_rows == "the_load":
        counts = moe.held_expert_layer(u, params, bias, cfg, "xla")[1]
        buffer_rows = int(counts[2:6].sum())
    cfg = cfg._replace(buffer_rows=buffer_rows, tile=(tm,) + TILE[1:])
    got, want = _both_layers(u, params, bias, cfg)
    _assert_the_same(got, want)
    (out, counts, overflow), grads = got
    here, rows = int(counts[2:6].sum()), moe.buffer_length(cfg, u.shape[0])
    assert int(overflow) == max(here - rows, 0)
    assert {"half_full": 0 < here < rows, "sized_for_the_most": here < rows,
            "exactly_full": here == rows, "under_the_load": here > rows,
            "every_choice_held": here == 96 > rows,
            "no_held_row": here == 0}[state]
    experts = [n for n in params if n != "router"]
    if here:
        assert all(float(jnp.abs(grads[1][n]).max()) > 0 for n in experts)
    else:       # zero trips: a zero result, and zero gradients but the
        # ones the softmax route's balance term gives ``u`` and the router
        assert not np.asarray(out).any()
        assert not any(np.asarray(grads[1][n]).any() for n in experts)
        assert route == "softmax" or not any(
            np.asarray(g).any() for g in (grads[0], grads[1]["router"]))


@pytest.mark.parametrize("chunk", [1, 7, 32, 48, 95, 96, 2048])
def test_no_row_is_walked_twice_at_any_chunk(chunk, monkeypatch):
    """A last chunk that would run over the buffer's end starts early
    (``dynamic_slice`` clamps it there) and leaves what the chunk before
    it has added alone."""
    monkeypatch.setattr(moe, "CHUNK", chunk)
    u, params, bias, cfg = _layer("gated_silu", "sigmoid")
    u, params = _steered(u, params, True)       # 96 rows: the buffer's all
    got, want = _both_layers(u, params, bias, cfg)
    _assert_the_same(got, want)
    assert int(got[0][1][2:6].sum()) == 96 and int(got[0][2]) == 0


@pytest.mark.parametrize("head", [0, 16, 56, 96])
@pytest.mark.parametrize("held_rows", [0, 1, 16, 39, 40, 41, 56, 57, 80, 81,
                                       96])
def test_the_walk_stops_with_the_routed_rows(held_rows, head, monkeypatch):
    """The head at once, then ``ceil((held_rows - head) / chunk)`` trips,
    counted on the device; every live row is ``fresh`` in one stretch,
    and no other row in any. Chunks of 40 after a head of 16 or 56 leave
    a last chunk that starts early."""
    monkeypatch.setattr(moe, "CHUNK", 40)
    take = jnp.arange(96, dtype=jnp.int32)
    chunk = min(40, 96 - head) if head < 96 else 0

    def step(carry, start, idx, live, fresh):
        count, seen = carry
        assert idx.shape == live.shape == fresh.shape
        assert idx.shape[0] in (head, chunk)
        return count + 1, seen.at[idx].add(fresh.astype(jnp.int32))

    count, seen = jax.jit(lambda held: moe._walk(
        take, held, head, (0, jnp.zeros((96,), jnp.int32)), step))(
            jnp.int32(held_rows))
    trips = -(-max(held_rows - head, 0) // chunk) if chunk else 0
    assert int(count) == (head > 0) + trips
    np.testing.assert_array_equal(np.asarray(seen),
                                  np.arange(96) < held_rows)
    assert moe.rows_walked([held_rows], 96, head) == head + chunk * trips
    # a load over the buffer is cut at it, layers add up
    assert moe.rows_walked([[60, 60], [1, 0]], 96, 0) == 120 + 40
    assert moe.rows_walked([[60, 60], [1, 0]], 96, 16) == 96 + 16


def _loops(jaxpr) -> int:
    return sum((e.primitive.name == "while")
               + sum(_loops(sub)
                     for sub in jax.core.jaxprs_in_params(e.params))
               for e in jaxpr.eqns)


def test_every_pass_over_the_buffer_is_a_loop_of_chunks():
    """Two loops forward, four with the gradients, and beside them the
    gathers and scatters of the head that an even load fills (48 of the
    buffer's 96 rows): none of the buffer's length."""
    u, params, bias, cfg = _layer("gated_silu", "softmax")
    rows = moe.buffer_length(cfg, u.shape[0])

    def loss(u, params):
        return moe.held_expert_layer(u, params, bias, cfg, "xla")[0].sum()

    forward = jax.make_jaxpr(loss)(u, params).jaxpr
    both = jax.make_jaxpr(jax.grad(loss, (0, 1)))(u, params).jaxpr
    assert (_loops(forward), _loops(both)) == (2, 4)
    for e in both.eqns:     # the loops' bodies are not among these
        if e.primitive.name in ("gather", "scatter-add", "scatter_add"):
            assert e.invars[1].aval.shape[0] == 48      # its slots


def _num_tiles(sizes, rows, tm) -> int:
    groups = np.diff(np.minimum(np.cumsum(sizes), rows), prepend=0)
    _, tiles = make_group_metadata(
        group_sizes=jnp.asarray(groups, jnp.int32), m=rows, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=len(sizes),
        visit_empty_groups=False)
    return int(tiles)


def _random_loads(seed):
    rng = np.random.default_rng(seed)
    held, tm = int(rng.integers(1, 17)), int(rng.choice([8, 128, 512]))
    rows = tm * int(rng.integers(1, 33))
    return rng.integers(0, 2 * rows // held + 2, held).tolist(), rows, tm


@pytest.mark.parametrize("sizes,rows,tm", [
    ([100, 200, 50, 150], 1024, 128),       # ISSUE 48's reading: 7 of 8
    ([100, 0, 50, 150], 1024, 128),         # an empty group
    ([0, 0, 0, 0], 1024, 128),              # nothing routed here
    ([128, 256, 100, 28], 1024, 128),       # groups that end on an edge
    ([128, 0, 0, 128], 1024, 128),          # empty groups on an edge
    ([1, 1, 1, 1], 1024, 128),              # four groups in one tile
    ([600, 600, 600], 1024, 128),           # a load over the buffer
    ([1024, 1024, 1024, 1024, 1024, 1024, 1024, 1024], 16384, 512),
] + [_random_loads(seed) for seed in range(12)])
def test_product_tiles_is_the_kernels_own_count(sizes, rows, tm):
    got = moe.product_tiles(sizes, rows, tm)
    assert got == _num_tiles(sizes, rows, tm)
    filled = min(sum(sizes), rows)
    assert -(-filled // tm) <= got <= -(-filled // tm) + len(sizes) - 1
    # layers add up
    assert moe.product_tiles([sizes, sizes], rows, tm) == 2 * got


def test_issue_48s_reading_is_seven_tiles_of_eight():
    assert moe.product_tiles([100, 200, 50, 150], 1024, 128) == 7
    # the padding in the last group, as before this change: 11
    assert _num_tiles([100, 200, 50, 150 + 524], 1024, 128) == 11


@pytest.mark.parametrize("held_share", ["even", "none", "all"])
def test_routing_counts_say_the_tiles_visited_and_the_buffers(held_share):
    """A step's counts give ``product_tiles_visited`` over the layers and
    ``product_tiles_buffer``: at an even load a little over half."""
    cfg = mla_moe.MLAMoEConfig(dim=2048, moe_ffn=1536, n_experts=64,
                               experts_held=8, expert_offset=16, top_k=4,
                               n_moe_layers=2, n_mtp=1)
    tokens, layers = 16384, 3
    held = mla_moe.held(cfg, tokens)
    assert held.buffer_rows == 16384 and held.tile[0] == 512
    counts = np.zeros((layers, cfg.n_experts + 1), np.int64)
    if held_share == "even":
        counts[:, :64] = tokens * 4 // 64
    elif held_share == "none":
        counts[:, 0] = tokens * 4
    else:       # every token to four held experts: the buffer overflows
        counts[:, 16:20] = tokens
        counts[:, 64] = 4 * tokens - 16384
    said = mla_moe.routing_counts(counts, cfg)
    assert said["product_tiles_buffer"] == layers * 32
    assert said["product_tile"] == "512x512x512"    # the tile that runs
    assert said["product_tiles_visited"] == {
        "even": layers * 16, "none": 0, "all": layers * 32}[held_share]
    assert said["held_rows"] == {"even": layers * 8192, "none": 0,
                                 "all": layers * 4 * tokens}[held_share]
    assert said["buffer_rows"] == layers * 16384
    # the head of an even load is walked whatever it holds
    assert said["buffer_rows_walked"] == {
        "even": layers * 8192, "none": layers * 8192,
        "all": layers * 16384}[held_share]


@pytest.mark.parametrize("load,walked", [
    (8191, 8192),                           # ends inside the head
    (8192, 8192),                           # at its end
    (8193, 8192 + moe.CHUNK),               # a row past it
    (8192 + moe.CHUNK + 1, 8192 + 2 * moe.CHUNK),
    (3 * 16384, 16384)])                    # a buffer under the load
def test_routing_counts_say_the_rows_the_passes_walk(load, walked):
    """``buffer_rows_walked`` is a layer's head (the even load) and whole
    chunks from there to its last routed row, cut at the buffer, summed
    over the layers; beside it ``buffer_rows``."""
    cfg = mla_moe.MLAMoEConfig(dim=2048, moe_ffn=1536, n_experts=64,
                               experts_held=8, expert_offset=16, top_k=4,
                               n_moe_layers=2, n_mtp=1)
    tokens = 16384
    counts = np.zeros((2, cfg.n_experts + 1), np.int64)
    counts[0, 16], counts[0, 0] = load, tokens * 4 - load
    counts[1, 23], counts[1, 63] = 1, tokens * 4 - 1
    said = mla_moe.routing_counts(counts, cfg)
    assert said["buffer_rows"] == 2 * 16384
    assert said["buffer_rows_walked"] == walked + 8192
    assert said["held_rows"] == load + 1
