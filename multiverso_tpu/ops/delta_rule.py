"""The gated delta rule of a linear-attention mixer in its chunked form, in
plain ``jax.numpy``.

A value head of width P reads a key head of width D and carries a state
``S`` [D, P] over the positions, ``S_0 = 0``:

    S <- exp(g_t) S        d_t = beta_t (v_t - S^T k_t)
    S <- S + k_t (x) d_t   o_t = S^T q_t

with ``g_t <= 0`` and ``beta_t`` in (0, 1), one each a value head and
position; ``q_t``, ``k_t`` arrive normed and scaled. The update is a
rank-one CORRECTION: what the state already answers for ``k_t`` is taken
off ``v_t`` before it is written. Over chunks of Q positions, with ``G`` the
running sum of ``g`` inside a chunk (falling, at most 0), ``Kb = beta k``,
``Vb = beta v`` and ``M = strict_lower((Kb K^T) o exp(G_i - G_j))``:

    T = (I + M)^-1              U = T Vb        W = T (Kb o exp(G))
    V' = U - W S_c
    O  = (q o exp(G)) S_c + lower((q K^T) o exp(G_i - G_j)) V'
    S_{c+1} = exp(G_end) S_c + (k o exp(G_end - G))^T V'

``T`` is the inverse of a unit lower-triangular matrix a chunk and value
head (:func:`unit_lower_inverse`: forward substitution). The chunk-local work is batched matrix
products (``K K^T`` and ``q K^T`` once a KEY head: its value heads differ in
``beta`` and ``G`` alone; ``T``'s two applications; the masked product with
``V'``; ``q S_c``); the recurrence over the chunks is a ``lax.scan`` that
carries TWO products a step (``W S_c``, then ``K~^T V'``), which is what
``ops/ssd.py``'s scan cannot be bent to: there a chunk leaves the same
thing behind it whatever state it started from. Running sums, exponentials,
``T``, the carried state and the recurrence are float32; the products take
operands in ``dtype`` (bfloat16) and sum in float32, forward and backward
(``ssd._ein``). Every exponent is of a number that is at most 0, so nothing
overflows however long the sequence. The backward pass is plain autodiff.

Readings on a v5e at (1 x 16,384, 16 key and 32 value heads of 128),
bfloat16 operands, ms a call forward / forward with every gradient (my chip
runs, PR 56, ``chip_smoke.py`` stage ``delta``; chunk 64 and 4 key heads a
group unless said): ``T`` by XLA's triangular solve **17.7 / 75.3**, by the
doubling product ``(I - M)(I + M^2)(I + M^4)...`` 20.9 / 96.7, by forward
substitution in halves (``log2 Q`` rounds of two whole [Q, Q] products at
the highest precision) 22.7 / 100.3; with the halves, chunk 128 reads 22.9 /
107.4 (27.2 / 114.2 at 8 key heads a group), and 2 / 8 / 16 key heads a
group 22.0 / 100.8, 25.2 / 106.0, 23.9 / 104.5: the group's size hardly
moves the time (the scan's 256 steps a group are not what it waits for),
and a v5e's compiler gives the rule's backward pass 1.23 / 1.79 / 2.65 /
4.22 / 6.55 GB of temporaries at 1 / 2 / 4 / 8 / 16 key heads a group. So:
the solve, chunk 64, two key heads a group. Against the recurrence a
position at a time in float32, over 2,048 positions of one key head (the
largest of o, dq, dk, dv, dg, dbeta over the reference's largest): 2.9e-4
with float32 operands at the highest precision and 5.2e-3 with bfloat16
ones, the same with ``T`` by the solve and by halves (a later call, two key
heads a group: the solve 16.9 / 82.1 ms, the halves 26.6 / 109.6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from multiverso_tpu.ops.ssd import _ein


def unit_lower_inverse(m):
    """``T = (I + M)^-1`` for ``m`` [..., Q, Q] strictly lower triangular,
    float32: XLA's triangular solve against the identity (on a TPU a
    routine of its own for a diagonal block's inverse, then a product at
    the highest precision; forward substitution, so no entry is larger
    than the inverse's own). Of the three ways read on the chip it is the
    fastest (the module's readings); ``chip_smoke.py`` keeps the other
    two, forward substitution by halves and the doubling product."""
    eye = jnp.eye(m.shape[-1], dtype=m.dtype)
    return jax.scipy.linalg.solve_triangular(
        m + eye, jnp.broadcast_to(eye, m.shape), lower=True,
        unit_diagonal=True)


def _group(qs, ks, vs, gs, betas, dtype, inverse):
    """One group of K key heads, R value heads each: ``qs``, ``ks`` [B, C,
    K, Q, D], ``vs`` [B, C, K, R, Q, P], ``gs``, ``betas`` [B, C, K, R, Q]
    -> o [B, C, K, R, Q, P]."""
    f32 = jnp.float32
    chunk = qs.shape[3]
    run = jnp.cumsum(gs, -1)                                # G
    i = jnp.arange(chunk)
    seen = i[:, None] >= i[None, :]
    decay = jnp.exp(jnp.where(seen, run[..., :, None] - run[..., None, :],
                              -jnp.inf))                    # [B,C,K,R,Q,Q]
    kk = _ein("zckid,zckjd->zckij", ks, ks, dtype)
    qk = _ein("zckid,zckjd->zckij", qs, ks, dtype)
    t = inverse(jnp.where(i[:, None] > i[None, :],
                          betas[..., None] * kk[:, :, :, None] * decay, 0.0))
    into = jnp.exp(run)                                     # exp(G)
    end = run[..., -1]                                      # [B,C,K,R]
    k_r = ks.astype(f32)[:, :, :, None]                     # [B,C,K,1,Q,D]
    u = _ein("zckrij,zckrjp->zckrip", t,
             vs.astype(f32) * betas[..., None], dtype)
    w = _ein("zckrij,zckrjd->zckrid", t,
             k_r * (betas * into)[..., None], dtype)
    left = k_r * jnp.exp(end[..., None] - run)[..., None]   # k o exp(G_end - G)

    def step(state, each):
        u_c, w_c, left_c, keep = each
        fresh = u_c - _ein("zkrid,zkrdp->zkrip", w_c, state, dtype)   # V'
        return (keep[..., None, None] * state
                + _ein("zkrid,zkrip->zkrdp", left_c, fresh, dtype),
                (state, fresh))

    first = jnp.zeros(w.shape[:1] + w.shape[2:4] + (w.shape[-1], u.shape[-1]),
                      f32)
    _, (starts, fresh) = jax.lax.scan(
        step, first, tuple(x.swapaxes(0, 1)
                           for x in (u, w, left, jnp.exp(end))))
    starts, fresh = starts.swapaxes(0, 1), fresh.swapaxes(0, 1)
    q_r = qs.astype(f32)[:, :, :, None] * into[..., None]   # q o exp(G)
    return (_ein("zckrid,zckrdp->zckrip", q_r, starts, dtype)
            + _ein("zckrij,zckrjp->zckrip", qk[:, :, :, None] * decay, fresh,
                   dtype))


def gated_delta_chunked(q, k, v, g, beta, chunk: int, dtype=jnp.bfloat16,
                        key_heads_a_group: int = 2,
                        inverse=unit_lower_inverse):
    """``o`` [B, S, Hv, P] float32 of the recurrence above.

    ``q``, ``k`` [B, S, Hk, D], normed and scaled, a KEY head each (value
    heads ``h R .. h R + R - 1`` read key head ``h``, ``R = Hv / Hk``: the
    repeat is made here, after ``K K^T`` and ``q K^T``); ``v`` [B, S, Hv,
    P]; ``g`` [B, S, Hv] (at most 0), ``beta`` [B, S, Hv] (in (0, 1));
    ``chunk`` divides S. The key heads are computed ``key_heads_a_group``
    at a time, each group rematerialised in the backward pass: a group's
    decays, ``M`` and ``T`` over 16,384 positions are 17 MB of float32 each
    at 2 key heads and 134 MB at all 16, its stacked start states 67 and
    537 MB, several times over in a backward pass (the module's readings
    have the compiler's count); a group is ``S / chunk`` DEPENDENT steps of
    its scan, and the chip read no gain from fewer, larger groups.
    ``inverse`` makes ``T`` from ``M`` (``chip_smoke.py`` stage ``delta``
    reads others)."""
    bsz, s, hk, d = q.shape
    hv, p = v.shape[2], v.shape[3]
    per = min(key_heads_a_group, hk)
    if s % chunk or hv % hk or hk % per:
        raise ValueError(
            f"{s} positions do not divide into chunks of {chunk}, {hv} value "
            f"heads over {hk} key heads, or {hk} key heads into groups of "
            f"{per}")
    nc, r, groups = s // chunk, hv // hk, hk // per
    f32 = jnp.float32
    # group-major, then chunk-major: [G, B, C, K, (R,) Q, ...]
    keys = lambda t: t.reshape(bsz, nc, chunk, groups, per, d).transpose(
        3, 0, 1, 4, 2, 5)
    gates = lambda t: t.astype(f32).reshape(
        bsz, nc, chunk, groups, per, r).transpose(3, 0, 1, 4, 5, 2)
    vs = v.reshape(bsz, nc, chunk, groups, per, r, p).transpose(
        3, 0, 1, 4, 5, 2, 6)
    one = jax.checkpoint(lambda t: _group(*t, dtype, inverse))
    o = jax.lax.map(one, (keys(q), keys(k), vs, gates(g), gates(beta)))
    # [G, B, C, K, R, Q, P] -> [B, C, Q, G, K, R, P]
    return o.transpose(1, 2, 5, 0, 3, 4, 6).reshape(bsz, s, hv, p)
