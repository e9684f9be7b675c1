"""The chunked state-space scan (``ops/ssd.py``) against the recurrence
itself, a position at a time in float32: output and every gradient, across
chunk boundaries, with decays near 0 and near 1; the plain form, and the
two Pallas kernels under the interpreter at shapes their rule takes."""

import jax
import jax.numpy as jnp
import pytest

from multiverso_tpu.ops import ssd
from multiverso_tpu.ops.ssd import ssd_chunked

B, S, H, P, G, N = 2, 64, 4, 8, 2, 16
SMALL = (B, S, H, P, G, N)
# shapes the kernels take (a chunk of 128, heads of 64 in groups of 8, a
# state of 128): sequences, chunks and groups vary, the skip rides or not;
# ``head_blocks``: ONE group of 32 heads, walked as two blocks of 16 that
# read the one B and C (their dB and dC add up over the blocks)
KERNELS = {"two_groups": ((2, 256, 16, 64, 2, 128), False),
           "three_chunks": ((1, 384, 8, 64, 1, 128), True),
           "head_blocks": ((1, 256, 32, 64, 1, 128), True)}
# dt A over a position: heads that forget at once, heads that keep nearly
# everything, and a spread between
DECAYS = {"spread": (-3.0, 2.5), "near_0": (2.5, 3.5), "near_1": (-9.0, -7.0)}


def recurrence(x, dt, a, b, c):
    """H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t, y_t = H_t C_t."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    b, c = (jnp.repeat(t, h // g, axis=2) for t in (b, c))

    def step(state, each):
        xt, dtt, bt, ct = each
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return state, jnp.sum(state * ct[..., None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n)),
                        tuple(t.swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1)


def _inputs(decay: str, seed: int = 0, dims=SMALL):
    b, s, h, p, g, n = dims
    k = jax.random.split(jax.random.key(seed), 5)
    lo, hi = DECAYS[decay]
    return (jax.random.normal(k[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, h))),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=lo, maxval=hi)),
            jax.random.normal(k[3], (b, s, g, n)),
            jax.random.normal(k[4], (b, s, g, n)))


def _skip(args, on: bool, seed: int = 3):
    return jax.random.normal(jax.random.key(seed),
                             args[2].shape) if on else None


def _kernels(*args, dtype=jnp.float32, skip=None, whole=None):
    """The two kernels, interpreted."""
    return ssd_chunked(*args, ssd.CHUNK, dtype, skip=skip, whole=whole,
                       kernel=True, interpret=True)


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want))) / scale < tol


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_chunked_scan_is_the_recurrence(decay, chunk):
    args = _inputs(decay)
    want = recurrence(*args)
    got = jax.jit(lambda *t: ssd_chunked(*t, chunk, jnp.float32))(*args)
    assert _close(got, want, 2e-5)
    if chunk < S and decay != "near_0":
        # the carried state is no rounding: the last chunk's positions read
        # what the chunks before them left
        alone = ssd_chunked(*(t[:, -chunk:] if t.ndim > 1 else t
                              for t in args), chunk, jnp.float32)
        assert not _close(alone, want[:, -chunk:], 1e-2)


@pytest.mark.parametrize("case", sorted(KERNELS))
@pytest.mark.parametrize("decay", ["near_1", "spread"])
def test_the_kernels_scan_is_the_recurrence(decay, case):
    dims, rides = KERNELS[case]
    args = _inputs(decay, dims=dims)
    skip = _skip(args, rides)
    want = recurrence(*args)
    if rides:
        want = want + skip[:, None] * args[0]
    got = jax.jit(lambda *t: _kernels(*t, skip=skip))(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _close(got, want, 2e-5)
    assert _close(got, ssd_chunked(*args, ssd.CHUNK, jnp.float32, skip=skip),
                  2e-5)
    # the state rides on in VMEM from chunk to chunk, a sequence at a time
    alone = _kernels(*(t[:, -ssd.CHUNK:] if t.ndim > 1 else t for t in args),
                     skip=skip)
    assert not _close(alone, want[:, -ssd.CHUNK:], 1e-2)


@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_every_gradient_of_the_chunked_scan_is_the_recurrences(decay):
    args = _inputs(decay, seed=1)
    weight = jax.random.normal(jax.random.key(7), (B, S, H, P))
    want = jax.grad(lambda *t: jnp.sum(weight * recurrence(*t)),
                    range(5))(*args)
    got = jax.jit(jax.grad(
        lambda *t: jnp.sum(weight * ssd_chunked(*t, 16, jnp.float32)),
        range(5)))(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert _close(g, w, 5e-5), name


@pytest.mark.parametrize("case", sorted(KERNELS))
@pytest.mark.parametrize("decay", ["near_1", "spread"])
def test_every_gradient_of_the_kernels_is_the_recurrences(decay, case):
    dims, rides = KERNELS[case]
    args = _inputs(decay, seed=1, dims=dims)
    args += (_skip(args, True),) * rides
    weight = jax.random.normal(jax.random.key(7), args[0].shape)
    skipped = lambda y, t: y + t[5][:, None] * t[0] if rides else y
    want = jax.grad(lambda *t: jnp.sum(
        weight * skipped(recurrence(*t[:5]), t)), range(len(args)))(*args)
    got = jax.jit(jax.grad(lambda *t: jnp.sum(
        weight * _kernels(*t[:5], skip=t[5] if rides else None)),
        range(len(args))))(*args)
    for name, g, w in zip(("x", "dt", "a", "b", "c", "skip"), got, want):
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert g.shape == w.shape and _close(g, w, 5e-5), name


def test_bfloat16_operands_sum_in_float32():
    """The products' operands are rounded, nothing else: the result stays
    within bfloat16's rounding of the recurrence, forward and backward."""
    args = _inputs("spread", seed=2)
    want = recurrence(*args)
    got = ssd_chunked(*args, 16)
    assert got.dtype == jnp.float32 and _close(got, want, 3e-2)
    assert not _close(got, want, 1e-5)
    weight = jax.random.normal(jax.random.key(7), (B, S, H, P))
    grad = jax.grad(lambda x: jnp.sum(weight * ssd_chunked(x, *args[1:], 16)))
    want_grad = jax.grad(lambda x: jnp.sum(weight * recurrence(x, *args[1:])))
    assert _close(grad(args[0]), want_grad(args[0]), 3e-2)


def test_positions_must_divide_into_chunks():
    with pytest.raises(ValueError, match="chunks of 48"):
        ssd_chunked(*_inputs("spread"), 48)


def test_the_kernels_round_where_the_plain_form_rounds():
    """bfloat16 operands of the four products, float32 everything else:
    the kernels' output and gradients are the plain form's to far less
    than a rounding, read out of ONE array ``[x | B | C]`` or out of
    three."""
    dims, _ = KERNELS["two_groups"]
    b, s, h, p, g, n = dims
    args = _inputs("spread", seed=2, dims=dims)
    skip = _skip(args, True)
    whole = jnp.concatenate([t.reshape(b, s, -1)
                             for t in (args[0], args[3], args[4])], -1)
    weight = jax.random.normal(jax.random.key(7), args[0].shape)

    def of_whole(whole, dt, a, skip, kernel):
        x, bm, cm = jnp.split(whole, (h * p, h * p + g * n), -1)
        return jnp.sum(weight * ssd_chunked(
            x.reshape(b, s, h, p), dt, a, bm.reshape(b, s, g, n),
            cm.reshape(b, s, g, n), ssd.CHUNK, skip=skip, whole=whole,
            kernel=kernel, interpret=kernel))

    operands = (whole, args[1], args[2], skip)
    want = jax.value_and_grad(lambda *t: of_whole(*t, False), range(4))(
        *operands)
    got = jax.jit(jax.value_and_grad(lambda *t: of_whole(*t, True),
                                     range(4)))(*operands)
    for name, g_, w in zip(("y", "whole", "dt", "a", "skip"),
                           (got[0],) + got[1], (want[0],) + want[1]):
        assert _close(g_, w, 1e-3), name
    three = _kernels(*args, dtype=jnp.bfloat16, skip=skip)
    assert _close(three, ssd_chunked(*args, ssd.CHUNK, skip=skip), 1e-4)
    assert not _close(three, recurrence(*args) + skip[:, None] * args[0],
                      1e-5)


class _Chip:
    platform = "tpu"


def test_the_rule_takes_whole_tiles_on_a_tpu_and_nothing_else(monkeypatch):
    """``kernel_heads``: from the device and the shapes alone; a shape it
    refuses runs the plain form on a chip too."""
    cell = dict(s=16384, h=64, p=64, g=8, n=128, chunk=128)
    assert ssd.kernel_heads(**cell) is None                 # the CPU's
    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    assert ssd.kernel_heads(**cell) == 8
    assert ssd.kernel_heads(**dict(cell, p=128, h=32, g=4)) == 8
    # a group of more heads than a step holds is walked in blocks, and a
    # chunk of several lane tiles as chunks of one
    assert ssd.kernel_heads(**dict(cell, g=1)) == 16
    assert ssd.kernel_heads(**dict(cell, g=1, chunk=256, s=8192)) == 16
    assert ssd.kernel_heads(**dict(cell, p=128, h=32, g=1)) == 8
    assert ssd.kernel_refusal(**cell) is None
    assert ssd.step_counts(9, **dict(cell, g=1, chunk=256)) == {
        "ssd_kernel_layers": 9, "ssd_kernel_chunk": 128,
        "ssm_head_blocks": 4, "ssd_bytes": 4 * 16384 * (8192 + 256 + 64)}
    refused = [dict(cell, chunk=64), dict(cell, s=16384 + 64),
               dict(cell, p=32), dict(cell, p=96), dict(cell, n=64),
               dict(cell, g=16), dict(cell, h=60), dict(cell, h=20, g=1)]
    for shape in refused:
        assert ssd.kernel_heads(**shape) is None, shape
        assert ssd.kernel_refusal(**shape), shape
    assert "1,024 lanes" in ssd.step_counts(4, **refused[-1])["ssd_kernel_why"]
    assert ssd.step_counts(4, **refused[-1])["ssd_kernel_layers"] == 0

    def traced(s, h, p, g, n, chunk):
        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
        return str(jax.make_jaxpr(lambda *t: ssd_chunked(*t, chunk))(
            f32(1, s, h, p), f32(1, s, h), f32(h), f32(1, s, g, n),
            f32(1, s, g, n)))

    assert "pallas_call" in traced(**dict(cell, s=256))
    for shape in refused[:1] + refused[2:5]:
        assert "pallas_call" not in traced(**dict(shape, s=256)), shape
    with pytest.raises(ValueError, match="chunks of 128"):
        traced(**dict(cell, s=256 + 64))


def test_the_kernels_names_are_no_flash_kernels():
    """``benchmark/layers/attn.kernels_in`` counts every custom call whose
    name holds ``mv.lm.attn``; the scope map files these under
    ``mv.lm.ssm.scan:kernel``."""
    args = _inputs("spread", dims=KERNELS["three_chunks"][0])
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *t: jnp.sum(_kernels(*t)), range(5)))(*args)
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(names) == [ssd.BWD, ssd.FWD] == ["ssd_chunk_bwd",
                                                  "ssd_chunk_fwd"]
    assert not any("mv.lm.attn" in name for name in names)


def test_off_a_tpu_the_scan_lowers_to_the_plain_forms_text():
    """No flag and no option: what the CPU runs is ``plain``, line for
    line (the parent's ``ssd_chunked``; ``tests/test_qwen3_next.py`` holds
    the whole step's text to the parent's by hash)."""
    args = _inputs("spread")
    text = lambda fn: jax.jit(jax.grad(
        lambda *t: jnp.sum(fn(*t, 16)), range(5))).lower(*args).as_text()
    assert text(ssd_chunked) == text(ssd.plain)
    assert "custom_call" not in text(ssd_chunked)
