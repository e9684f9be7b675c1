"""``ops/short_conv.py``: the mixers' short causal convolution as two Pallas
kernels, driven in the interpreter: against the plain form (values and every
gradient), against the convolution a position at a time, across the seams of
position tiles and of sequences, and the rule that picks a form."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.ops import short_conv

# two sequences, three position tiles of 32 (the halo at two seams a
# sequence, forward and reverse), two channel tiles of 128
B, S, C, TILE, ROWS = 2, 96, 256, (32, 128), 16


def _operands(taps, bias, seed=0, shape=(B, S, C)):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], shape),
            taps ** -0.5 * jax.random.normal(k[1], (taps, shape[-1])),
            jax.random.normal(k[2], shape[-1:]) if bias else None,
            jax.random.normal(k[3], shape))


def _kernels(x, w, bias, silu, **how):
    return short_conv.causal_taps(
        x, w, bias, silu, **{"tile": TILE, "rows": ROWS, "interpret": True,
                             **how})


def _with_grads(conv, silu, x, w, bias, weight):
    wrt = (0, 1) if bias is None else (0, 1, 2)
    y = conv(x, w, bias, silu)
    grads = jax.grad(lambda *t: jnp.sum(weight * conv(*t, silu)), wrt)(
        x, w, bias)
    return (y,) + tuple(grads)


def _err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _a_position_at_a_time(x, w, bias, silu):
    """The definition in NumPy float32: position ``t`` of a sequence reads
    its own and the ``taps - 1`` before it, nothing before position 0."""
    x, w = np.asarray(x), np.asarray(w)
    taps, out = w.shape[0], np.zeros_like(x)
    for t in range(x.shape[1]):
        pre = np.zeros_like(x[:, 0])
        for i in range(taps):
            at = t - (taps - 1) + i
            if at >= 0:
                pre = pre + w[i] * x[:, at]
        out[:, t] = pre if bias is None else np.asarray(bias) + pre
    return out / (1.0 + np.exp(-out)) if silu else out


CASES = list(itertools.product((3, 4), (False, True), (False, True)))
IDS = [f"taps{t}-{'bias' if b else 'nobias'}-{'silu' if s else 'linear'}"
       for t, b, s in CASES]


@pytest.mark.parametrize("taps,bias,silu", CASES, ids=IDS)
def test_the_kernels_are_the_plain_form(taps, bias, silu):
    """Values and every gradient (dx, dw, dbias) over two sequences, three
    position tiles and two channel tiles."""
    ops = _operands(taps, bias)
    got = _with_grads(_kernels, silu, *ops)
    want = _with_grads(short_conv.plain, silu, *ops)
    assert len(got) == len(want) == 3 + bias
    for name, a, b in zip(("y", "dx", "dw", "dbias"), got, want):
        assert a.shape == b.shape and a.dtype == jnp.float32, name
        assert _err(a, b) < 2e-6, (name, _err(a, b))


@pytest.mark.parametrize("taps,bias,silu", CASES, ids=IDS)
def test_the_kernels_are_the_convolution_a_position_at_a_time(
        taps, bias, silu):
    x, w, b, _ = _operands(taps, bias, seed=1)
    want = _a_position_at_a_time(x, w, b, silu)
    for conv in (_kernels, short_conv.plain):
        assert _err(conv(x, w, b, silu), want) < 2e-6


@pytest.mark.parametrize("tile,rows", [((32, 128), 8), ((32, 128), 32),
                                       ((96, 256), 16), ((16, 256), 16)],
                         ids=["rows8", "whole_tile", "one_tile", "six_tiles"])
def test_every_tiling_gives_the_same_numbers(tile, rows):
    ops = _operands(4, True, seed=2)
    want = _with_grads(short_conv.plain, True, *ops)
    got = _with_grads(
        lambda *t: _kernels(*t, tile=tile, rows=rows), True, *ops)
    for name, a, b in zip(("y", "dx", "dw", "dbias"), got, want):
        assert _err(a, b) < 2e-6, (name, _err(a, b))


def test_nothing_leaks_across_a_sequences_start_or_a_seam():
    """A sequence's first positions read zeros, not the sequence before it
    (nor, backward, does its last position's gradient reach the next
    one's); what a tile hands the next is its own last rows."""
    x, w, b, weight = _operands(4, True, seed=3)
    y = _kernels(x, w, b, True)
    # the second sequence alone gives what it gave beside the first
    alone = _kernels(x[1:], w, b, True)
    assert np.array_equal(np.asarray(y[1:]), np.asarray(alone))
    # position 0 sees its own tap alone
    pre = b + w[-1] * x[:, 0]
    assert _err(y[:, 0], pre * jax.nn.sigmoid(pre)) < 1e-6
    # a change at a seam's last row moves the next tile's first three
    # positions and no other, in its own sequence alone
    moved = _kernels(x.at[0, 31, :].add(1.0), w, b, True) - y
    rows = np.flatnonzero(np.abs(np.asarray(moved[0])).max(-1) > 0)
    assert rows.tolist() == [31, 32, 33, 34]
    assert not np.asarray(moved[1]).any()
    # and backward: dx of that row is the gradients of those four
    dx = jax.grad(lambda x: jnp.sum(weight * _kernels(x, w, b, True)))(x)
    only = jnp.zeros_like(weight).at[0, 31:35].set(weight[0, 31:35])
    part = jax.grad(lambda x: jnp.sum(only * _kernels(x, w, b, True)))(x)
    assert _err(part[0, 31], dx[0, 31]) < 1e-6
    last = jnp.zeros_like(weight).at[0, -1].set(weight[0, -1])
    reach = jax.grad(lambda x: jnp.sum(last * _kernels(x, w, b, True)))(x)
    assert not np.asarray(reach[1]).any()
    assert np.flatnonzero(np.abs(np.asarray(reach[0])).max(-1) > 0
                          ).tolist() == [92, 93, 94, 95]


def test_under_jit_and_a_rematerialised_caller():
    """As the mixers call it: jitted, under ``jax.checkpoint``; traced once
    a shape (the second call binds the first's jaxpr)."""
    from multiverso_tpu.ops import index_kernels

    x, w, b, weight = _operands(4, False, seed=4)
    index_kernels._TRACED.clear()

    @jax.jit
    def step(x, w):
        feed = jax.checkpoint(lambda x, w: _kernels(x, w, None, True) * 2.0)
        return jax.value_and_grad(
            lambda x, w: jnp.sum(weight * feed(feed(x, w), w)), (0, 1))(x, w)

    plain = lambda x, w: short_conv.plain(x, w, None, True) * 2.0
    want = jax.value_and_grad(
        lambda x, w: jnp.sum(weight * plain(plain(x, w), w)), (0, 1))(x, w)
    got = step(x, w)
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _err(a, b_) < 1e-5
    # four forward calls and two backward in the step: two traces
    assert sorted(k[0] for k in index_kernels._TRACED) == [
        short_conv.BWD, short_conv.FWD]


def test_what_the_rule_sends_to_the_plain_form(monkeypatch):
    """Off a TPU everything; on one, channels that are no whole lanes and
    positions that are no whole tiles. The cells' shapes take the kernels
    at tiles of 512 x 512."""
    x, w, b, _ = _operands(4, True, seed=5, shape=(1, 40, 96))
    assert short_conv.kernel_tiles(16384, 8192) is None     # the CPU's
    assert np.array_equal(np.asarray(short_conv.causal_taps(x, w, b, True)),
                          np.asarray(short_conv.plain(x, w, b, True)))

    class _Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    assert short_conv.kernel_tiles(16384, 8192) == (512, 512)
    assert short_conv.kernel_tiles(16384, 6144) == (512, 512)
    assert short_conv.kernel_tiles(8192, 384) == (512, 128)
    assert short_conv.kernel_tiles(16384, 8192, jnp.bfloat16) is None
    assert short_conv.kernel_tiles(16384, 6100) is None
    assert short_conv.kernel_tiles(1000, 8192) is None
    # a shape the rule refuses runs the plain form on the chip too
    text = jax.jit(lambda x, w, b: short_conv.causal_taps(
        x, w, b, True)).lower(x, w, b).as_text()
    assert "pad" in text and "custom_call" not in text
    with pytest.raises(ValueError, match="does not divide"):
        _kernels(x, w, b, True)


def test_the_kernels_names_are_no_flash_kernels():
    """``benchmark/layers/attn.kernels_in`` counts every custom call whose
    name holds ``mv.lm.attn``."""
    x, w, b, weight = _operands(4, True, seed=6)
    jaxpr = jax.make_jaxpr(lambda x, w, b: jax.grad(
        lambda *t: jnp.sum(weight * _kernels(*t, True)), (0, 1, 2))(x, w, b))(
            x, w, b)
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(names) == [short_conv.BWD, short_conv.FWD]
    assert names and not any("mv.lm.attn" in n for n in names)


def test_the_step_counts_the_layers_that_run_the_kernels(monkeypatch):
    """``lm.step``'s static counts: ``conv_kernel_layers`` (every mixer's
    convolution or none, by this device and the shape) and ``conv_bytes``
    (one read and one write of a mixer's float32 [s, channels])."""
    from multiverso_tpu.models import mla_moe, nemotron_h, qwen3_next

    delta = qwen3_next.Qwen3NextConfig(
        lin_key_heads=16, lin_value_heads=32, lin_key_dim=128,
        lin_value_dim=128)
    ssm = nemotron_h.NemotronHConfig(
        pattern="MEMEM*EME", ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
        ssm_state=128, chunk=128)
    grids = lambda: (mla_moe.mixer_grid(delta, 16384),
                     mla_moe.mixer_grid(ssm, 16384))
    for grid, channels in zip(grids(), (8192, 6144)):
        assert grid["conv_kernel_layers"] == 0          # the CPU's
        assert grid["conv_bytes"] == 2 * 4 * 16384 * channels

    class _Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    assert [g["conv_kernel_layers"] for g in grids()] == [3, 4]
    # the timeline's line for an operator
    from tools import dump_metrics
    lines = dump_metrics._mixer_lines([{"name": "lm.step",
                                        "args": grids()[1]}])
    assert lines[-2] == ("    short convolution: the kernels in 4 mixer(s) "
                         "(0: the plain form), 805 MB a mixer a pass at the "
                         "least")
    # a length of no whole tiles runs the plain form on the chip too
    assert mla_moe.mixer_grid(delta, 1000)["conv_kernel_layers"] == 0
    assert mla_moe.mixer_grid(nemotron_h.NemotronHConfig(), 512)[
        "conv_kernel_layers"] == 0                      # 96 channels
