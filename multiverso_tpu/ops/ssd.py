"""The state-space scan of a Mamba-2 mixer (SSD) in its chunked form, in
plain ``jax.numpy``.

A head ``h`` of width P carries a state ``H`` [P, N] over the positions:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t        y_t = H_t C_t

with ``A < 0`` one number a head, ``dt_t > 0`` one a head and position,
``x_t`` [P], and ``B_t``, ``C_t`` [N] shared by the heads of a group. Over
chunks of Q positions, with ``La`` the running sum of ``dt A`` inside a
chunk (so ``La_i <= 0`` and falling), ``Xd = dt x``:

    Y   = ((C B^T) o L) Xd + exp(La) C H_c      L_ij = exp(La_i - La_j), i >= j
    S_c = sum_j exp(La_end - La_j) B_j (x) Xd_j
    H_{c+1} = exp(La_end) H_c + S_c             H_0 = 0

The chunk-local work is four batched matrix products (``C B^T`` once a
group, the masked product with ``Xd``, the chunk's state ``S_c``, and ``C
H_c``); the recurrence over the chunks' states is a ``lax.scan``; the
groups are walked by a ``lax.map``. Running
sums, exponentials, the carried state and the recurrence are float32; the
four products take operands in ``dtype`` (bfloat16) and sum in float32,
forward and backward (:func:`_ein`). Every exponent is of a number that
is at most 0, so nothing overflows however long the sequence.

``ops/delta_rule.py`` (a gated delta rule's chunked form) shares
:func:`_ein`, the walk over groups of heads by a ``lax.map`` under
``jax.checkpoint`` and the rule that no exponent is positive, and nothing
else: there the state's update is a correction by what the state itself
answers, so a chunk's contribution ``V' = U - W S_c`` needs the state the
chunk starts from, and the scan over chunks carries two matrix products a
step where this one carries a multiply-add of a ``left`` that is made for
all chunks at once. Do not merge the two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _ein(spec: str, a, b, dtype):
    """``einsum(spec, a, b)`` with operands in ``dtype`` and a float32
    sum, forward and backward. ``spec`` is ``"x,y->z"`` with every index of
    an operand in the other operand or in the result (so each gradient is
    one einsum of the cotangent with the other operand)."""
    return _ein_fwd(spec, a, b, dtype)[0]


def _ein_fwd(spec, a, b, dtype):
    ac, bc = a.astype(dtype), b.astype(dtype)
    return (jnp.einsum(spec, ac, bc, preferred_element_type=jnp.float32),
            (ac, bc))


def _ein_bwd(spec, dtype, res, g):
    ac, bc = res
    ins, out = spec.split("->")
    x, y = ins.split(",")
    g = g.astype(dtype)
    da = jnp.einsum(f"{out},{y}->{x}", g, bc,
                    preferred_element_type=jnp.float32)
    db = jnp.einsum(f"{x},{out}->{y}", ac, g,
                    preferred_element_type=jnp.float32)
    return da, db


_ein.defvjp(_ein_fwd, _ein_bwd)


def _group(xs, dts, a, bs, cs, dtype):
    """One group's heads: ``xs`` [B, C, K, Q, P], ``dts`` [B, C, K, Q],
    ``a`` [K], ``bs``, ``cs`` [B, C, Q, N] -> y [B, C, K, Q, P]."""
    f32 = jnp.float32
    chunk = xs.shape[3]
    la = jnp.cumsum(dts * a[:, None], -1)                   # [B,C,K,Q]
    xd = xs.astype(f32) * dts[..., None]
    # inside a chunk: ((C B^T) o L) Xd
    cb = _ein("zcin,zcjn->zcij", cs, bs, dtype)
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              la[..., :, None] - la[..., None, :], -jnp.inf))
    y = _ein("zckij,zckjp->zckip", cb[:, :, None] * decay, xd, dtype)
    # the chunk's own state, what it leaves behind it, and the state every
    # chunk starts from
    end = la[..., -1]                                       # [B,C,K]
    left = _ein("zcjn,zckjp->zckpn", bs,
                xd * jnp.exp(end[..., None] - la)[..., None], dtype)

    def step(state, each):
        keep, add = each
        return keep[..., None, None] * state + add, state

    _, starts = jax.lax.scan(
        step, jnp.zeros(left.shape[:1] + left.shape[2:], f32),
        (jnp.exp(end).swapaxes(0, 1), left.swapaxes(0, 1)))
    return y + (_ein("zcin,zckpn->zckip", cs, starts.swapaxes(0, 1), dtype)
                * jnp.exp(la)[..., None])


def ssd_chunked(x, dt, a, b, c, chunk: int, dtype=jnp.bfloat16):
    """``y`` [B, S, H, P] float32 of the recurrence above, without the
    skip ``D x``.

    ``x`` [B, S, H, P]; ``dt`` [B, S, H] (positive: after the softplus);
    ``a`` [H] (negative); ``b``, ``c`` [B, S, G, N], head ``h`` reading
    group ``h // (H / G)``; ``chunk`` divides S. The groups are computed
    one after another, each rematerialised in the backward pass: a group's
    ``L`` over 16,384 positions and 8 heads is 67 MB of float32, all 64
    heads' 537 MB, several times over in a backward pass."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if s % chunk or h % g:
        raise ValueError(f"{s} positions do not divide into chunks of "
                         f"{chunk}, or {h} heads into {g} groups")
    nc, k = s // chunk, h // g
    # group-major, then chunk-major: [G, B, C, (K,) Q, ...]
    xs = x.reshape(bsz, nc, chunk, g, k, p).transpose(3, 0, 1, 4, 2, 5)
    dts = dt.astype(jnp.float32).reshape(bsz, nc, chunk, g, k).transpose(
        3, 0, 1, 4, 2)
    bs = b.reshape(bsz, nc, chunk, g, n).transpose(3, 0, 1, 2, 4)
    cs = c.reshape(bsz, nc, chunk, g, n).transpose(3, 0, 1, 2, 4)
    one = jax.checkpoint(lambda t: _group(*t, dtype))
    y = jax.lax.map(one, (xs, dts, a.astype(jnp.float32).reshape(g, k),
                          bs, cs))
    return y.transpose(1, 2, 4, 0, 3, 5).reshape(bsz, s, h, p)
