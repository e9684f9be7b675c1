"""Pallas TPU flash attention: fused blockwise softmax-attention kernel.

Single-chip counterpart of the cross-chip schemes in parallel/ring.py (the
reference framework predates attention entirely — SURVEY §5 "long-context:
absent"). The kernel never materializes the [S, S] score matrix: the grid
walks (batch*heads, then the (q block, k block) pairs with the k block
innermost and sequential), carrying the online-softmax state (running max
``m``, denominator ``l``, f32 accumulator) in VMEM scratch that persists
across the k steps — the same math as ``ring._ring_attention_local`` with
ppermute hops replaced by grid steps over HBM-resident K/V blocks.

MXU/VPU notes: both matmuls (q@k^T, p@v) run on the MXU in the input dtype
with f32 accumulation (``preferred_element_type``); masking, exp and the
rescale are VPU elementwise ops on (block_q, block_k) tiles. The causal
structure is known before the kernel runs, and the grid uses it: a causal
call walks a table of the live pairs only (:func:`live_pairs`, read by the
index maps as scalar-prefetch operands), so a block strictly above the
diagonal costs no grid step and no K/V copy, and only a pair the diagonal
crosses builds and applies the mask; a pair under it runs the same tile
function without. A non-causal call walks the whole rectangle, unmasked.

The backward pass is Pallas too (FlashAttention-2 style): the forward
additionally emits the per-row logsumexp, and two blockwise kernels
recompute ``p = exp(s - lse)`` tile by tile — one walking k-blocks
innermost to accumulate dQ, one walking q-blocks innermost to accumulate
dK/dV (its table is column-major) — so the [S, S] score matrix is never
materialized in either direction.

Measured on one v5e chip at (B x H, S, D) = (40, 8192, 256), bfloat16,
causal, milliseconds a call, forward / dQ / dK with dV (builder's chip
run, PR 34; PERF.md section 6): the whole rectangle with dead steps
skipped by ``pl.when`` and every live pair masked (the parent) 17.0 /
17.7 / 24.1 at 512 x 512 blocks; this table of live pairs 13.3 / 13.9 /
17.9 at 512 x 512 (bit for bit the parent's results), 12.0 / 13.4 / 17.7
at 1,024 x 512 and 10.9 / 13.4 / 17.6 at 512 x 1,024; the rectangle with
a dead step's index maps clamped to the resident block 14.6 / 15.4 / 17.9.
1,024 x 1,024 and 512 x 2,048 do not fit the kernels' VMEM.

A causal call may carry a ``window`` (position ``i`` sees ``j`` only where
``i - j < window``): the pair table then holds the band alone, so a pair
under the band costs no grid step and no K/V copy either, in all three
walks, and a pair is masked only where the diagonal or the band's far edge
crosses it. And it may have fewer key-value heads than query heads: K and
V come in and dK and dV go out at the key-value heads' shape, never
repeated; forward and dQ read block ``b // group`` of them, and dK with dV
walks the key-value heads with the group's query heads innermost in its
table, so one accumulator sums the group. Measured on one v5e chip at q
(2, 32, 8192, 128) over k, v (2, 4, 8192, 128), bfloat16, ms a call forward
(with the residual) / dQ / dK with dV (builder's chip run, PR 35; PERF.md
section 6), causal: 20.5 / 12.9 / 16.8 at 512 x 512, 11.9 / 12.0 / 15.3 at
512 x 1,024, **10.3 / 11.1 / 14.9 at 1,024 x 1,024**, which fits at this head
size, 11.5 / 12.3 / 16.0 at 512 x 2,048; under a window of 1,024 (23% of
the triangle's positions): 7.5 / 4.8 / 6.1 at 512 x 512 (45 pairs a head),
5.7 / 5.8 / 6.9 at 512 x 1,024 (30), **5.1 / 5.4 / 6.8 at 1,024 x 1,024**
(15, every one masked, twice the band's area), 13.8 / 7.6 / 9.5 at 256 x
256 (150). Against plain float32 attention on the chip the outputs and
the three gradients differ by 0.0020 to 0.0027 of their norm, grouped or
not, banded or not.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_RES_LANES = 8    # lse residual lane width (smallest legal TPU tile)
_NEG_INF = -1e30


def _blocks(s: int, block_q: int, block_k: int) -> Tuple[int, int]:
    """The block sizes a call over ``s`` positions runs with: clamped to
    ``s``, and dividing it."""
    block_q, block_k = min(block_q, s), min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} not divisible by blocks "
                         f"({block_q}, {block_k})")
    return block_q, block_k


def _band(s: int, window: Optional[int]) -> Optional[int]:
    """``window`` as the walks use it: ``None`` where it hides nothing
    (none given, or one of ``s`` positions or more)."""
    return None if window is None or window >= s else int(window)


def live_pairs(s: int, block_q: int, block_k: int, q_inner: bool = False,
               window: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The (q block, k block) pairs of a causal call that hold a live
    position, in the order a kernel walks them: row-major (k innermost:
    forward and dQ) or, with ``q_inner``, column-major (dK with dV).
    Position ``i`` sees ``j`` where ``0 <= i - j``, and with a ``window``
    also ``i - j < window`` (a band under the diagonal; a window of ``s``
    or more is none, and gives the causal table entry for entry). Returns
    ``(qi, kj, crossing)``; a pair is *crossing* when the diagonal or the
    band's far edge passes through it, and *interior* (no mask needed)
    when every one of its positions is live."""
    block_q, block_k = _blocks(s, block_q, block_k)
    window = _band(s, window)
    i, j = np.indices((s // block_q, s // block_k), dtype=np.int32)
    if q_inner:
        i, j = i.T, j.T
    live = j * block_k <= i * block_q + block_q - 1
    crossing = j * block_k + block_k - 1 > i * block_q
    if window is not None:
        # the nearest (q, k) of the pair is inside the band; the farthest
        # is outside it
        live &= i * block_q - (j * block_k + block_k - 1) < window
        crossing |= i * block_q + block_q - 1 - j * block_k >= window
    return i[live], j[live], crossing[live]


def causal_pairs(s: int, block_q: int, block_k: int,
                 window: Optional[int] = None) -> Dict[str, int]:
    """What ONE causal kernel call does for one (batch x head): the grid
    steps it takes, the pairs among them that compute, and those of them
    that take the masked path."""
    _, _, crossing = live_pairs(s, block_q, block_k, window=window)
    return {"grid_steps": int(crossing.size), "live": int(crossing.size),
            "masked": int(crossing.sum())}


class _Walk(NamedTuple):
    """How a kernel's grid visits the (q block i, k block j) pairs. A
    causal call walks the table of :func:`live_pairs`, handed to the
    kernel as scalar-prefetch operands (grid ``(bh, pairs)``): no grid
    step and no block copy for a pair above the diagonal or, with a
    ``window``, under the band. Any other call walks the rectangle.
    Either way the inner index is the accumulator's: k for forward and
    dQ, q (``q_inner``) for dK with dV.

    ``group`` query heads read one key-value head (K and V arrive with a
    ``group``-th of q's heads and are never repeated): a q-shaped operand
    of (batch x head) ``b`` goes with the k-shaped one of ``b // group``.
    Forward and dQ walk the query heads. dK with dV walks the KEY-VALUE
    heads, and each step of its table names one of the group's query
    heads as well (``g`` innermost), so the accumulators of a k block sum
    over the group before they are written once."""
    causal: bool
    s: int
    block_q: int
    block_k: int
    q_inner: bool
    window: Optional[int] = None
    group: int = 1

    @property
    def nq(self) -> int:
        return self.s // self.block_q

    @property
    def nk(self) -> int:
        return self.s // self.block_k

    @property
    def _group_walk(self) -> bool:
        return self.q_inner and self.group > 1

    def table(self) -> Tuple[np.ndarray, ...]:
        """The scalar-prefetch operands: (qi, kj) of the live pairs, and
        for the grouped dK with dV walk each pair once a query head of
        the group, with that head's number as the third."""
        if not self.causal:
            return ()
        qi, kj, _ = live_pairs(self.s, self.block_q, self.block_k,
                               self.q_inner, self.window)
        if not self._group_walk:
            return qi, kj
        g = np.tile(np.arange(self.group, dtype=np.int32), qi.size)
        return np.repeat(qi, self.group), np.repeat(kj, self.group), g

    def specs(self, d: int) -> Tuple[pl.BlockSpec, ...]:
        """The block specs of a q-shaped operand, a k-shaped one and the
        logsumexp residual, at this walk's (q block, k block)."""
        group = self.group

        def spec(rows, lanes, at):      # at(b, i, j, g) -> block index
            if self._group_walk:
                index = lambda b, t, qi, kj, g: at(b, qi[t], kj[t], g[t])
            elif self.causal:
                index = lambda b, t, qi, kj: at(b, qi[t], kj[t], 0)
            elif self.q_inner:
                index = lambda b, j, i: at(b, i, j, 0)
            else:
                index = lambda b, i, j: at(b, i, j, 0)
            return pl.BlockSpec((1, rows, lanes), index)

        if self.q_inner:        # the grid's b is a key-value head
            of_q = lambda b, i, j, g: (b * group + g, i, 0)
            of_k = lambda b, i, j, g: (b, j, 0)
        else:                   # the grid's b is a query head
            of_q = lambda b, i, j, g: (b, i, 0)
            of_k = lambda b, i, j, g: (b // group, j, 0)
        return (spec(self.block_q, d, of_q), spec(self.block_k, d, of_k),
                spec(self.block_q, _RES_LANES, of_q))

    def call(self, kernel, bh: int, operands, *, interpret: bool,
             out_shape, **specs):
        """``pl.pallas_call`` of ``kernel`` over ``bh`` (batch x head)s of
        this walk (key-value heads for ``q_inner``, else query heads);
        ``specs`` are the grid spec's in, out and scratch."""
        table = self.table()
        if self.causal:
            grid, inner = (bh, len(table[0])), ("arbitrary",)
        else:
            grid = ((bh, self.nk, self.nq) if self.q_inner
                    else (bh, self.nq, self.nk))
            inner = ("parallel", "arbitrary")
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(table), grid=grid, **specs),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) + inner),
            interpret=interpret,
        )(*table, *operands)

    def enter(self, refs):
        """In the kernel: ``(i, j, first, last, crossing, refs)`` of this
        grid step, ``refs`` without the table's. ``first`` / ``last`` say
        whether the step opens / closes its accumulator's row (column for
        ``q_inner``): scalar arithmetic on the pair. ``crossing`` is
        ``None`` where nothing is masked at all."""
        bq, bk, w = self.block_q, self.block_k, self.window
        n = 2 + self._group_walk if self.causal else 0     # the table's refs
        if self.causal:
            t = pl.program_id(1)
            i, j = refs[0][t], refs[1][t]
            crossing = j * bk + bk - 1 > i * bq
            if w is not None:
                crossing |= i * bq + bq - 1 - j * bk >= w
        else:
            x, y = pl.program_id(1), pl.program_id(2)
            (i, j), crossing = ((y, x) if self.q_inner else (x, y)), None
        if self.q_inner:
            lo, hi = 0, self.nq - 1
            if self.causal:
                lo = jax.lax.div(j * bk, bq)
            if w is not None:
                hi = jnp.minimum(hi, jax.lax.div(w + j * bk + bk - 2, bq))
            first, last = i == lo, i == hi
            if self._group_walk:
                g = refs[2][t]
                first, last = first & (g == 0), last & (g == self.group - 1)
            return i, j, first, last, crossing, refs[n:]
        lo, hi = 0, self.nk - 1
        if self.causal:
            hi = jnp.minimum(hi, jax.lax.div(i * bq + bq - 1, bk))
        if w is not None:
            lo = jnp.maximum(lo, jax.lax.div(i * bq - w + 1, bk))
        return i, j, j == lo, j == hi, crossing, refs[n:]


def _masked_or_not(crossing, tile: Callable[[bool], None]) -> None:
    """Run ``tile(masked)``: masked on a pair the diagonal crosses, plain
    on one under it."""
    if crossing is None:
        tile(False)
        return
    pl.when(crossing)(lambda: tile(True))
    pl.when(jnp.logical_not(crossing))(lambda: tile(False))


def _causal_mask(s, i, j, walk: _Walk):
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * walk.block_q
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * walk.block_k
    seen = qpos >= kpos
    if walk.window is not None:
        seen &= qpos - kpos < walk.window
    return jnp.where(seen, s, _NEG_INF)


def _flash_kernel(*refs, walk: _Walk, scale: float, emit_lse: bool):
    i, j, first, last, crossing, refs = walk.enter(refs)
    q_ref, k_ref, v_ref = refs[:3]
    if emit_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[3:]
    else:   # inference-only call: skip the residual's VPU work + HBM write
        (o_ref, m_ref, l_ref, acc_ref), lse_ref = refs[3:], None

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked: bool):
        qb = q_ref[0]                                     # (bq, d)
        kb = k_ref[0]                                     # (bk, d)
        vb = v_ref[0]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if masked:
            s = _causal_mask(s, i, j, walk)
        m_prev = m_ref[...][:, :1]                        # (bq, 1)
        l_prev = l_ref[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        if masked:
            # rows whose every position is masked would get exp(-inf-(-inf))
            p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        l_next = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, d)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)

    _masked_or_not(crossing, tile)

    @pl.when(last)
    def _emit():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row logsumexp, the backward's softmax residual (stored
            # with a tiny 8-lane trailing dim — TPU blocks need their last
            # dim to match the array dim or divide 128)
            lse_ref[0] = jnp.broadcast_to(m_ref[...][:, :1] + jnp.log(l),
                                          lse_ref.shape[1:])


def _heads(q, k, causal: bool) -> Tuple[int, int]:
    """(batch x key-value heads, query heads a key-value head) of a call
    on q [B, H, S, D] and k [B, Hkv, S, D]."""
    b, h, hkv = q.shape[0], q.shape[1], k.shape[1]
    if h % hkv:
        raise ValueError(f"{h} query heads do not divide over {hkv} "
                         "key-value heads")
    if h != hkv and not causal:
        raise ValueError("grouped-query heads take a causal call")
    return b * hkv, h // hkv


def _walk_of(q, k, causal: bool, block_q: int, block_k: int,
             window: Optional[int]) -> Tuple[_Walk, int]:
    """(the forward's and dQ's walk, batch x key-value heads) of a call."""
    s = q.shape[2]
    block_q, block_k = _blocks(s, block_q, block_k)
    bkv, group = _heads(q, k, causal)
    return _Walk(causal, s, block_q, block_k, False,
                 _band(s, window) if causal else None, group), bkv


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool, with_lse: bool,
                   window: Optional[int] = None):
    b, h, s, d = q.shape
    walk, bkv = _walk_of(q, k, causal, block_q, block_k, window)
    block_q, bh = walk.block_q, b * h
    qspec, kspec, lspec = walk.specs(d)
    oshape = jax.ShapeDtypeStruct((bh, s, d), q.dtype)
    lshape = jax.ShapeDtypeStruct((bh, s, _RES_LANES), jnp.float32)
    res = walk.call(
        functools.partial(_flash_kernel, walk=walk, scale=1.0 / (d ** 0.5),
                          emit_lse=with_lse),
        bh, (q.reshape(bh, s, d), k.reshape(bkv, s, d),
             v.reshape(bkv, s, d)), interpret=interpret,
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, lspec] if with_lse else [qspec],
        out_shape=[oshape, lshape] if with_lse else [oshape],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # denominator
            pltpu.VMEM((block_q, d), jnp.float32),        # output acc
        ])
    out = res[0].reshape(b, h, s, d)
    return (out, res[1]) if with_lse else (out, None)


def _bwd_p_ds(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, i, j, *,
              scale: float, masked: bool, walk: _Walk):
    """Shared backward recompute for ONE (q-block i, k-block j) tile:
    returns (p, ds) with ds already scale-folded — the one definition of
    the tile math, so the dQ and dK/dV kernels cannot desynchronize.
    D_i = rowsum(dO * O) is recomputed per tile in VPU registers:
    trivially cheap next to the three matmuls, and it saves materializing
    a lane-padded delta array in HBM."""
    qb, kb, vb, dob = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    lse = lse_ref[0][:, :1]
    delta = jnp.sum(dob.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)
    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale           # (bq, bk)
    if masked:
        s = _causal_mask(s, i, j, walk)
    p = jnp.exp(s - lse)               # masked entries: exp(-inf-..) = 0
    dp = jax.lax.dot_general(
        dob, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (bq, bk)
    ds = (p * (dp - delta) * scale).astype(qb.dtype)
    return p, ds


def _bwd_dq_kernel(*refs, walk: _Walk, scale: float):
    i, j, first, last, crossing, refs = walk.enter(refs)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, acc_ref = refs

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked: bool):
        _, ds = _bwd_p_ds(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          i, j, scale=scale, masked=masked, walk=walk)
        acc_ref[...] += jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, d)

    _masked_or_not(crossing, tile)

    @pl.when(last)
    def _emit():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, walk: _Walk, scale: float):
    i, j, first, last, crossing, refs = walk.enter(refs)
    (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
     dk_ref, dv_ref, dk_acc, dv_acc) = refs

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked: bool):
        p, ds = _bwd_p_ds(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          i, j, scale=scale, masked=masked, walk=walk)
        dob = do_ref[0]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, d)
        dk_acc[...] += jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, d)

    _masked_or_not(crossing, tile)

    @pl.when(last)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, do, causal: bool, block_q: int,
                    block_k: int, interpret: bool,
                    window: Optional[int] = None):
    b, h, s, d = q.shape
    walk, bkv = _walk_of(q, k, causal, block_q, block_k, window)
    block_q, block_k, bh = walk.block_q, walk.block_k, b * h
    scale = 1.0 / (d ** 0.5)
    operands = (q.reshape(bh, s, d), k.reshape(bkv, s, d),
                v.reshape(bkv, s, d), out.reshape(bh, s, d),
                do.reshape(bh, s, d), lse)
    qspec, kspec, rspec = walk.specs(d)
    dq = walk.call(
        functools.partial(_bwd_dq_kernel, walk=walk, scale=scale),
        bh, operands, interpret=interpret,
        in_specs=[qspec, kspec, kspec, qspec, qspec, rspec],
        out_specs=qspec, out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)])

    # dK/dV walk the key-value heads, q-blocks (and the group) innermost
    walk = walk._replace(q_inner=True)
    qspec, kspec, rspec = walk.specs(d)
    dk, dv = walk.call(
        functools.partial(_bwd_dkv_kernel, walk=walk, scale=scale),
        bkv, operands, interpret=interpret,
        in_specs=[qspec, kspec, kspec, qspec, qspec, rspec],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bkv, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bkv, s, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)])
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = interpreter mode off-TPU (tests); one rule for fwd AND bwd
    (a drift between them would run half the op interpreted)."""
    return (jax.devices()[0].platform != "tpu" if interpret is None
            else interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Fused attention over q [B, H, S, D] and k, v [B, Hkv, S, D]; S must
    divide by the block sizes (blocks auto-clamp to S when S < 128). With
    ``Hkv < H`` (a causal call) query head ``h`` reads key-value head
    ``h // (H / Hkv)``, and dK and dV come out at k's shape. ``window``
    (a causal call's): position ``i`` sees ``j`` only where ``i - j <
    window``. ``interpret=None`` auto-selects interpreter mode off-TPU
    (tests); pass False to force the compiled path.
    """
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k,
                            _resolve_interpret(interpret), False, window)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k,
                              _resolve_interpret(interpret), True, window)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k,
                           _resolve_interpret(interpret), window)


flash_attention.defvjp(_fwd, _bwd)
