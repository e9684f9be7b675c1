"""The chunked state-space scan (``ops/ssd.py``) against the recurrence
itself, a position at a time in float32: output and every gradient, across
chunk boundaries, with decays near 0 and near 1."""

import jax
import jax.numpy as jnp
import pytest

from multiverso_tpu.ops.ssd import ssd_chunked

B, S, H, P, G, N = 2, 64, 4, 8, 2, 16
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


def _inputs(decay: str, seed: int = 0):
    k = jax.random.split(jax.random.key(seed), 5)
    lo, hi = DECAYS[decay]
    return (jax.random.normal(k[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, S, H))),
            -jnp.exp(jax.random.uniform(k[2], (H,), minval=lo, maxval=hi)),
            jax.random.normal(k[3], (B, S, G, N)),
            jax.random.normal(k[4], (B, S, G, N)))


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
