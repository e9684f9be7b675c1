"""The held experts' grouped products end where the routed rows end: the
buffer's padding belongs to no group, the kernels' grid is the row tiles
that hold rows, and what the kernels leave unwritten past the last group
(NaN in Pallas's interpreter, anything on the chip) reaches no sum."""

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
    assert said["product_tiles_visited"] == {
        "even": layers * 16, "none": 0, "all": layers * 32}[held_share]
    assert said["held_rows"] == {"even": layers * 8192, "none": 0,
                                 "all": layers * 4 * tokens}[held_share]
