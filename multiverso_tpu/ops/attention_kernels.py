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
crosses builds and applies a mask; a pair under it runs the same tile
function without. A non-causal call walks the whole rectangle, unmasked.

A crossed pair is not one tile under one mask either, where
:func:`sub_tile` gives the blocks sub-tiles: what is live in a pair
follows from ``i * block_q - j * block_k`` alone (its *kind*: 0 on the
diagonal, the window at the band's far edge where the blocks are equal),
so each kind is one ``pl.when`` branch of static slices
(:meth:`_Walk.pieces`). A sub-block of rows has ONE contiguous run of live
columns (up to the diagonal, from the far edge on): the forward is one
``q[rows] @ k[run]^T``, one max, one rescale and one ``p @ v[run]`` for it,
no more rescales a pair than the whole tile's; dQ likewise; dK with dV
takes a sub-block of columns with its run of live rows. The mask (two
iotas, a compare for each edge that is there, the select, the forward's
second select) is built and applied on the sub-tiles an edge passes
through and nowhere else. An interior pair runs the whole tile as before,
and the pair table, the grid, the block specs and the number of kernels
are as they were.

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

The same calls with a crossed pair in sub-tiles (builder's chip runs,
PR 44, ``chip_smoke.py`` stage ``flash``; PERF.md section 6; 1,024 x 1,024
blocks but GLM's; whole tile / sub-tiles of 512 / 256 / 128):

  window 1,024, q (2, 32, 8192, 128) over 4 key-value heads (15 pairs,
  all crossed; 2.00 / 1.50 / 1.25 / 1.125 of the band computed):
    forward 4.91 / - / **3.34** / 3.66, dQ 5.24 / 3.93 / **3.49** / 3.39,
    dK with dV 6.64 / 4.98 / **4.68** / 5.06
  window 2,048, q (1, 32, 16384, 128) (45 pairs, 30 crossed):
    forward 6.80 / - / **5.22** / 5.55, dQ 7.39 / 6.08 / **5.65** / 5.55,
    dK with dV 9.59 / 7.92 / **7.63** / 8.00
  causal, q (2, 32, 8192, 128) (36 pairs, 8 crossed):
    forward 10.09 / - / **9.27** / 9.43, dQ 10.97 / 10.38 / **10.14** /
    10.13, dK with dV 14.74 / 13.91 / **13.73** / 14.06
  causal, q (2, 20, 8192, 256), 512 x 1,024 blocks (72 pairs, 16 crossed):
    forward 10.56 / - / **9.81** / 9.82, dQ 13.41 / 13.05 / **12.89** /
    12.81, dK with dV 17.52 / 16.69 / **16.44** / 16.46

A kernel compiles in 1.0 to 2.6 s with sub-tiles of 256 and 0.9 to 2.3 s
without; the results are the whole tiles' bit for bit in bfloat16 (a dead
position added 0.0 to a sum; the live ones keep their order). The forward
gains only because a row's running max and denominator are kept and
combined in every lane of their scratch: taken at [rows, 1], as they
were, the forward of the first call read 5.08 in sub-tiles of 256 for
4.94 whole, while the same statistics cost a whole tile nothing (4.91).
The forward's time follows its row updates (rows x pairs), not its area:
what the sub-tiles save there is the products, the exps and the masks of
the area they skip, at the same number of rescales.

A kernel with sub-tiles is four to nine tile bodies where it was two, and
tracing and lowering it costs in proportion (a model calls it once a
layer, and again under ``jax.checkpoint``: 62 traces and 56 lowerings in
``glm47f-train-8k``'s set-up, 6.8 s more than the parent's on a warm
compile cache). So :meth:`_Walk.call` traces a call once for its kernel
body, static arguments and operand types and binds it again from the
jaxpr; equations with one jaxpr also lower once a program.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

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
                 window: Optional[int] = None,
                 sub: Optional[int] = None) -> Dict[str, int]:
    """What ONE causal kernel call does for one (batch x head): the grid
    steps it takes, the pairs among them that compute, those of them the
    diagonal or the band's far edge crosses, the positions it computes (a
    whole tile for an interior pair; of a crossed one the sub-tiles of
    ``sub`` rows and columns that hold a live position, or the whole tile
    without ``sub``) and the positions that are live."""
    block_q, block_k = _blocks(s, block_q, block_k)
    _, _, crossing = live_pairs(s, block_q, block_k, window=window)
    # an interior pair's sub-tiles are all live: the call computes the live
    # tiles of the sub-tiles' own grid
    sub_q, sub_k = _sub_blocks(block_q, block_k, sub)
    tiles = live_pairs(s, sub_q, sub_k, window=window)[0].size
    w = _band(s, window) or s
    return {"grid_steps": int(crossing.size), "live": int(crossing.size),
            "masked": int(crossing.sum()), "computed": tiles * sub_q * sub_k,
            "needed": w * (w + 1) // 2 + (s - w) * w}


def sub_tile(block_q: int, block_k: int, d: int) -> Optional[int]:
    """The rows and columns of the sub-tiles a crossed pair of ``block_q``
    x ``block_k`` is cut into at head size ``d``; ``None`` leaves it one
    tile under one mask. 256 where the blocks are of 512 rows or more
    (what the chip read at head sizes 128 and 256, the module's table, and
    at 64, half a lane tile, where 1,024 x 1,024 blocks read 9.9 / 11.6 /
    14.9 ms forward / dQ / dK with dV in sub-tiles of 256 for 10.7 / 12.6 /
    15.9 whole: ``mla_moe.attn_blocks``, PR 50; and at 192 for queries and
    keys with values of 128, a lane tile and a half, where 1,024 x 1,024
    blocks over 4,096 positions read 1.96 / 2.66 / 3.15 for 2.24 / 2.99 /
    3.51 whole: PR 60)."""
    if d <= 256 and block_q % 512 == 0 and block_k % 512 == 0:
        return 256
    return None


def _sub_blocks(block_q: int, block_k: int,
                sub: Optional[int]) -> Tuple[int, int]:
    """A sub-tile's (rows, columns): ``sub`` of each, at most the block."""
    if sub is None:
        return block_q, block_k
    sub_q, sub_k = min(sub, block_q), min(sub, block_k)
    if block_q % sub_q or block_k % sub_k:
        raise ValueError(f"blocks ({block_q}, {block_k}) not divisible by "
                         f"sub-tiles of {sub}")
    return sub_q, sub_k


class _Piece(NamedTuple):
    """One product's worth of a crossed pair: a sub-block of rows with its
    run of live columns (``axis`` 1: forward and dQ) or a sub-block of
    columns with its run of live rows (``axis`` 0: dK with dV). ``edges``
    are the sub-tiles of it that the diagonal or the band's far edge passes
    through, each ``(start, stop, corner)``: where along ``axis`` it lies
    in the piece, and ``qpos - kpos`` at its first row and column."""
    rows: slice
    cols: slice
    axis: int
    edges: Tuple[Tuple[int, int, int], ...]


# the kernel calls traced so far, by kernel body, static arguments and
# operand types (:meth:`_Walk.call`)
_TRACED: Dict[tuple, Any] = {}


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
    over the group before they are written once.

    With ``sub`` a crossed pair is no longer one tile under one mask: the
    kernel cuts it into sub-tiles of ``sub`` rows and columns, computes
    only those that hold a live position and masks only those an edge
    passes through (:meth:`pieces`). What is live in a pair follows from
    ``i * block_q - j * block_k`` alone, its *kind*, and a call's crossed
    pairs are of a few kinds (:meth:`kinds`): each is one branch of static
    slices in the kernel.

    ``heads`` (the query heads of a batch element; 0 without) says that
    the call carries a *selection*: one more operand [B, S, S], nonzero
    where query ``i`` may see key ``j``, shared by a batch element's
    heads. Its (q block, k block) tile rides the same pair table
    (:meth:`select_spec`) and is applied in EVERY live pair, an interior
    one too, after the diagonal's own mask; a selection takes whole tiles
    (``sub`` is ``None``)."""
    causal: bool
    s: int
    block_q: int
    block_k: int
    q_inner: bool
    window: Optional[int] = None
    group: int = 1
    sub: Optional[int] = None
    heads: int = 0

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

    def kinds(self) -> Tuple[int, ...]:
        """``i * block_q - j * block_k`` of the crossed pairs, each value
        once; none where a crossed pair stays one tile."""
        if not self.causal or self.sub is None:
            return ()
        qi, kj, crossing = live_pairs(self.s, self.block_q, self.block_k,
                                      window=self.window)
        return tuple(int(x) for x in np.unique(
            (qi * self.block_q - kj * self.block_k)[crossing]))

    def pieces(self, kind: int) -> Tuple[_Piece, ...]:
        """A crossed pair of ``kind`` as the kernel computes it: for each
        sub-block of rows that has one, its run of live columns (one
        contiguous run of sub-tiles: up to the diagonal, from the far edge
        on), or with ``q_inner`` for each sub-block of columns its run of
        live rows. A sub-tile holds ``qpos - kpos`` from ``lo`` to ``hi``;
        a position is live where that is at least 0 and under the window."""
        sub_q, sub_k = _sub_blocks(self.block_q, self.block_k, self.sub)
        r, c = np.indices((self.block_q // sub_q, self.block_k // sub_k))
        corner = kind + r * sub_q - c * sub_k
        lo, hi = corner - (sub_k - 1), corner + (sub_q - 1)
        far = np.inf if self.window is None else self.window
        live, whole = (hi >= 0) & (lo < far), (lo >= 0) & (hi < far)
        own, run = sub_q, sub_k
        if self.q_inner:
            live, whole, corner = live.T, whole.T, corner.T
            own, run = sub_k, sub_q
        out = []
        for a in range(live.shape[0]):
            along = np.flatnonzero(live[a])
            if not along.size:
                continue
            first, last = int(along[0]), int(along[-1])
            assert along.size == last - first + 1       # one run
            edges = tuple(((b - first) * run, (b - first + 1) * run,
                           int(corner[a, b]))
                          for b in along.tolist() if not whole[a, b])
            mine = slice(a * own, (a + 1) * own)
            theirs = slice(first * run, (last + 1) * run)
            out.append(_Piece(theirs, mine, 0, edges) if self.q_inner
                       else _Piece(mine, theirs, 1, edges))
        return tuple(out)

    def _spec(self, rows: int, lanes: int, at) -> pl.BlockSpec:
        """A block of ``rows`` x ``lanes`` at ``at(b, i, j, g)`` of this
        walk's grid step."""
        if self._group_walk:
            index = lambda b, t, qi, kj, g: at(b, qi[t], kj[t], g[t])
        elif self.causal:
            index = lambda b, t, qi, kj: at(b, qi[t], kj[t], 0)
        elif self.q_inner:
            index = lambda b, j, i: at(b, i, j, 0)
        else:
            index = lambda b, i, j: at(b, i, j, 0)
        return pl.BlockSpec((1, rows, lanes), index)

    def select_spec(self) -> pl.BlockSpec:
        """The block spec of the selection [B, S, S]: the pair's (q block,
        k block) tile of the grid step's batch element (the grid's ``b``
        counts key-value heads for ``q_inner``, else query heads)."""
        per = self.heads // self.group if self.q_inner else self.heads
        return self._spec(self.block_q, self.block_k,
                          lambda b, i, j, g: (b // per, i, j))

    def specs(self, d: int) -> Tuple[pl.BlockSpec, ...]:
        """The block specs of a q-shaped operand, a k-shaped one and the
        logsumexp residual, at this walk's (q block, k block)."""
        group, spec = self.group, self._spec

        if self.q_inner:       # the grid's b is a key-value head
            of_q = lambda b, i, j, g: (b * group + g, i, 0)
            of_k = lambda b, i, j, g: (b, j, 0)
        else:                   # the grid's b is a query head
            of_q = lambda b, i, j, g: (b, i, 0)
            of_k = lambda b, i, j, g: (b // group, j, 0)
        return (spec(self.block_q, d, of_q), spec(self.block_k, d, of_k),
                spec(self.block_q, _RES_LANES, of_q))

    def call(self, kernel, bh: int, operands, *, interpret: bool,
             out_shape, **specs):
        """``pl.pallas_call`` of ``kernel`` (a partial of a kernel body
        over its static arguments) over ``bh`` (batch x head)s of this
        walk (key-value heads for ``q_inner``, else query heads);
        ``specs`` are the grid spec's in, out and scratch. A model calls
        the same kernel on the same shapes once a layer, and again under
        ``jax.checkpoint``: the call is traced once (:data:`_TRACED`) and
        bound again from its jaxpr, so a program also lowers it once
        (equations with the same parameters share a lowering)."""
        key = (kernel.func, tuple(sorted(kernel.keywords.items())), bh,
               interpret, tuple(jax.typeof(o) for o in operands))
        traced = _TRACED.get(key)
        if traced is None:
            table = self.table()
            if self.causal:
                grid, inner = (bh, len(table[0])), ("arbitrary",)
            else:
                grid = ((bh, self.nk, self.nq) if self.q_inner
                        else (bh, self.nq, self.nk))
                inner = ("parallel", "arbitrary")
            call = pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=len(table), grid=grid, **specs),
                out_shape=out_shape,
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel",) + inner),
                interpret=interpret)
            if len(_TRACED) >= 64:      # a test suite's worth of shapes
                _TRACED.clear()
            traced = _TRACED[key] = jax.make_jaxpr(
                lambda *operands: call(*table, *operands))(*operands)
        out = jax.core.eval_jaxpr(traced.jaxpr, traced.consts, *operands)
        return out if isinstance(out_shape, (list, tuple)) else out[0]

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


_WHOLE = slice(None)


class _Mask(NamedTuple):
    """A tile's dead positions, as the kernels apply them: ``scores(s)``
    is ``s`` with -1e30 there, ``probs(p, s)`` the forward's ``p`` with 0
    there (a row with no live position at all would keep
    ``exp(-1e30 - -1e30)``)."""
    scores: Callable
    probs: Callable


def _masked_or_not(i, j, crossing, walk: _Walk,
                   tile: Callable[[slice, slice, Optional[_Mask]], None],
                   select_ref=None) -> None:
    """Run the pair's ``tile(rows, cols, mask)``: the whole tile unmasked
    on an interior pair; on a crossed one the whole tile under
    :func:`_causal_mask` or, where the walk has sub-tiles, the pieces of
    the pair's kind, each under the mask of its edges. With a selection
    (``select_ref``: its tile of the pair) every pair is masked: the whole
    tile under the selection, on a crossed pair after the diagonal's own
    mask."""
    if select_ref is not None:
        def chosen(s, crossed: bool):
            if crossed:
                s = _causal_mask(s, i, j, walk)
            return jnp.where(select_ref[0].astype(jnp.int32) != 0, s,
                             _NEG_INF)

        live = lambda p, s: jnp.where(s > _NEG_INF / 2, p, 0.0)
        for crossed in (True, False):
            pl.when(crossing if crossed else jnp.logical_not(crossing))(
                lambda crossed=crossed: tile(_WHOLE, _WHOLE, _Mask(
                    lambda s: chosen(s, crossed), live)))
        return
    if crossing is None:
        tile(_WHOLE, _WHOLE, None)
        return
    kinds = walk.kinds()
    if kinds:
        kind = i * walk.block_q - j * walk.block_k
        for k in kinds:
            @pl.when(kind == k)
            def _pieces(k=k):
                for piece in walk.pieces(k):
                    tile(piece.rows, piece.cols,
                         _edge_mask(piece, walk.window))
    else:
        pl.when(crossing)(lambda: tile(_WHOLE, _WHOLE, _Mask(
            lambda s: _causal_mask(s, i, j, walk),
            lambda p, s: jnp.where(s > _NEG_INF / 2, p, 0.0))))
    pl.when(jnp.logical_not(crossing))(lambda: tile(_WHOLE, _WHOLE, None))


def _causal_mask(s, i, j, walk: _Walk):
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * walk.block_q
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * walk.block_k
    seen = qpos >= kpos
    if walk.window is not None:
        seen &= qpos - kpos < walk.window
    return jnp.where(seen, s, _NEG_INF)


def _edge_mask(piece: _Piece, window: Optional[int]) -> _Mask:
    """The mask of a piece of a crossed pair: built and applied on the
    sub-tiles an edge passes through, and there by the edges that do."""

    def on_edges(fn, x, *like):
        # x with fn(corner, its sub-tile, like's) in each edge's place
        cut = lambda a, lo, hi: a[:, lo:hi] if piece.axis else a[lo:hi]
        parts, at = [], 0
        for start, stop, corner in piece.edges:
            if start > at:
                parts.append(cut(x, at, start))
            parts.append(fn(corner, *(cut(a, start, stop)
                                      for a in (x,) + like)))
            at = stop
        if at < x.shape[piece.axis]:
            parts.append(cut(x, at, x.shape[piece.axis]))
        return (parts[0] if len(parts) == 1
                else jnp.concatenate(parts, piece.axis))

    def dead_scores(corner, s):
        # qpos - kpos is corner + t
        t = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        seen = None
        if corner - (s.shape[1] - 1) < 0:           # the diagonal is here
            seen = t >= -corner
        if window is not None and corner + s.shape[0] - 1 >= window:
            far = t < window - corner               # the far edge is here
            seen = far if seen is None else seen & far
        return jnp.where(seen, s, _NEG_INF)

    return _Mask(
        lambda s: on_edges(dead_scores, s),
        lambda p, s: on_edges(
            lambda _, p, s: jnp.where(s > _NEG_INF / 2, p, 0.0), p, s))


def _across(x, width: int):
    """``x`` [rows, _LANES], a row's statistic in every lane, at ``width``
    lanes."""
    copies = -(-width // x.shape[1])
    wide = x if copies == 1 else jnp.concatenate([x] * copies, axis=1)
    return wide if wide.shape[1] == width else wide[:, :width]


def _select_of(refs, at: int, walk: _Walk):
    """(the selection's ref or ``None``, the kernel's other refs): a call
    with a selection hands it over as its LAST input, ``refs[at]``."""
    if not walk.heads:
        return None, refs
    return refs[at], refs[:at] + refs[at + 1:]


def _flash_kernel(*refs, walk: _Walk, scale: float, emit_lse: bool):
    i, j, first, last, crossing, refs = walk.enter(refs)
    q_ref, k_ref, v_ref = refs[:3]
    select_ref, refs = _select_of(refs, 3, walk)
    if emit_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[3:]
    else:   # inference-only call: skip the residual's VPU work + HBM write
        (o_ref, m_ref, l_ref, acc_ref), lse_ref = refs[3:], None

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(rows, cols, mask):
        qb = q_ref[0, rows]                               # (bq, d)
        kb = k_ref[0, cols]                               # (bk, d)
        vb = v_ref[0, cols]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if mask is not None:
            s = mask.scores(s)
        # a row's running max and denominator sit in every lane of their
        # scratch and are combined there: at [rows, 1] a piece of a
        # crossed pair paid more for them than its area saved
        m_prev, l_prev = m_ref[rows], l_ref[rows]         # (bq, _LANES)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _across(m_next, s.shape[1]))
        if mask is not None:
            p = mask.probs(p, s)
        l_next = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, d)
        acc_ref[rows] = acc_ref[rows] * _across(corr, pv.shape[1]) + pv
        m_ref[rows] = m_next
        l_ref[rows] = l_next

    _masked_or_not(i, j, crossing, walk, tile, select_ref)

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
             window: Optional[int], sub: Optional[int],
             select=None) -> Tuple[_Walk, int]:
    """(the forward's and dQ's walk, batch x key-value heads) of a call."""
    s = q.shape[2]
    block_q, block_k = _blocks(s, block_q, block_k)
    bkv, group = _heads(q, k, causal)
    if not causal or _sub_blocks(block_q, block_k, sub) == (block_q, block_k):
        sub = None      # no crossed pair, or none to cut: whole tiles
    walk = _Walk(causal, s, block_q, block_k, False,
                 _band(s, window) if causal else None, group, sub)
    if select is None:
        return walk, bkv
    if not causal or select.shape != (q.shape[0], s, s):
        raise ValueError("a selection [B, S, S] goes with a causal call; "
                         f"got {select.shape} for q {q.shape}")
    # every pair is masked by the selection's own tile: whole tiles
    return walk._replace(sub=None, heads=q.shape[1]), bkv


def _scale_of(q, scale: Optional[float]) -> float:
    """The scores' multiplier: ``scale``, or ``1 / sqrt(D)`` of q's heads."""
    return 1.0 / (q.shape[3] ** 0.5) if scale is None else float(scale)


def _specs(walk: _Walk, d: int, dv: int):
    """A walk's block specs for a call at head sizes ``d`` (q, k) and
    ``dv`` (v, the output): (q's, k's, the output's, v's, lse's)."""
    qspec, kspec, lspec = walk.specs(d)
    ospec, vspec, _ = walk.specs(dv)
    return qspec, kspec, ospec, vspec, lspec


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool, with_lse: bool,
                   window: Optional[int] = None, sub: Optional[int] = None,
                   select=None, scale: Optional[float] = None):
    b, h, s, d = q.shape
    dv = v.shape[3]             # the values' head size, and the output's
    walk, bkv = _walk_of(q, k, causal, block_q, block_k, window, sub, select)
    block_q, bh = walk.block_q, b * h
    qspec, kspec, ospec, vspec, lspec = _specs(walk, d, dv)
    chosen = ((select,), [walk.select_spec()]) if walk.heads else ((), [])
    oshape = jax.ShapeDtypeStruct((bh, s, dv), q.dtype)
    lshape = jax.ShapeDtypeStruct((bh, s, _RES_LANES), jnp.float32)
    res = walk.call(
        functools.partial(_flash_kernel, walk=walk, scale=_scale_of(q, scale),
                          emit_lse=with_lse),
        bh, (q.reshape(bh, s, d), k.reshape(bkv, s, d),
             v.reshape(bkv, s, dv)) + chosen[0], interpret=interpret,
        in_specs=[qspec, kspec, vspec] + chosen[1],
        out_specs=[ospec, lspec] if with_lse else [ospec],
        out_shape=[oshape, lshape] if with_lse else [oshape],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # denominator
            pltpu.VMEM((block_q, dv), jnp.float32),       # output acc
        ])
    out = res[0].reshape(b, h, s, dv)
    return (out, res[1]) if with_lse else (out, None)


def _bwd_p_ds(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, rows, cols,
              mask: Optional[_Mask], *, scale: float):
    """Shared backward recompute for ONE tile, ``rows`` of the q block
    against ``cols`` of the k block (the whole pair, or a piece of a
    crossed one): returns (p, ds) with ds already scale-folded, the one
    definition of the tile math, so the dQ and dK/dV kernels cannot
    desynchronize. D_i = rowsum(dO * O) is recomputed per tile in VPU
    registers: trivially cheap next to the three matmuls, and it saves
    materializing a lane-padded delta array in HBM."""
    qb, kb, vb, dob = q_ref[0, rows], k_ref[0, cols], v_ref[0, cols], \
        do_ref[0, rows]
    lse = lse_ref[0, rows][:, :1]
    delta = jnp.sum(dob.astype(jnp.float32)
                    * o_ref[0, rows].astype(jnp.float32),
                    axis=-1, keepdims=True)
    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale           # (bq, bk)
    if mask is not None:
        s = mask.scores(s)
    p = jnp.exp(s - lse)               # masked entries: exp(-inf-..) = 0
    dp = jax.lax.dot_general(
        dob, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (bq, bk)
    ds = (p * (dp - delta) * scale).astype(qb.dtype)
    return p, ds


def _bwd_dq_kernel(*refs, walk: _Walk, scale: float):
    i, j, first, last, crossing, refs = walk.enter(refs)
    select_ref, refs = _select_of(refs, 6, walk)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, acc_ref = refs

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(rows, cols, mask):
        _, ds = _bwd_p_ds(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          rows, cols, mask, scale=scale)
        acc_ref[rows] += jax.lax.dot_general(
            ds, k_ref[0, cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, d)

    _masked_or_not(i, j, crossing, walk, tile, select_ref)

    @pl.when(last)
    def _emit():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, walk: _Walk, scale: float):
    i, j, first, last, crossing, refs = walk.enter(refs)
    select_ref, refs = _select_of(refs, 6, walk)
    (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
     dk_ref, dv_ref, dk_acc, dv_acc) = refs

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(rows, cols, mask):
        p, ds = _bwd_p_ds(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          rows, cols, mask, scale=scale)
        dob = do_ref[0, rows]
        dv_acc[cols] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, d)
        dk_acc[cols] += jax.lax.dot_general(
            ds, q_ref[0, rows], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, d)

    _masked_or_not(i, j, crossing, walk, tile, select_ref)

    @pl.when(last)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, do, causal: bool, block_q: int,
                    block_k: int, interpret: bool,
                    window: Optional[int] = None, sub: Optional[int] = None,
                    select=None, scale: Optional[float] = None):
    b, h, s, d = q.shape
    dv = v.shape[3]
    walk, bkv = _walk_of(q, k, causal, block_q, block_k, window, sub, select)
    chosen = (select,) if walk.heads else ()
    block_q, block_k, bh = walk.block_q, walk.block_k, b * h
    scale = _scale_of(q, scale)
    operands = (q.reshape(bh, s, d), k.reshape(bkv, s, d),
                v.reshape(bkv, s, dv), out.reshape(bh, s, dv),
                do.reshape(bh, s, dv), lse) + chosen
    qspec, kspec, ospec, vspec, rspec = _specs(walk, d, dv)
    dq = walk.call(
        functools.partial(_bwd_dq_kernel, walk=walk, scale=scale),
        bh, operands, interpret=interpret,
        in_specs=([qspec, kspec, vspec, ospec, ospec, rspec]
                  + [walk.select_spec() for _ in chosen]),
        out_specs=qspec, out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)])

    # dK/dV walk the key-value heads, q-blocks (and the group) innermost
    walk = walk._replace(q_inner=True)
    qspec, kspec, ospec, vspec, rspec = _specs(walk, d, dv)
    dk, dv_ = walk.call(
        functools.partial(_bwd_dkv_kernel, walk=walk, scale=scale),
        bkv, operands, interpret=interpret,
        in_specs=([qspec, kspec, vspec, ospec, ospec, rspec]
                  + [walk.select_spec() for _ in chosen]),
        out_specs=[kspec, vspec],
        out_shape=[jax.ShapeDtypeStruct((bkv, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bkv, s, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)])
    return dq.reshape(q.shape), dk.reshape(k.shape), dv_.reshape(v.shape)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = interpreter mode off-TPU (tests); one rule for fwd AND bwd
    (a drift between them would run half the op interpreted)."""
    return (jax.devices()[0].platform != "tpu" if interpret is None
            else interpret)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None, select=None,
                    with_lse: bool = False, scale: Optional[float] = None):
    """Fused attention over q [B, H, S, D] and k, v [B, Hkv, S, D]; S must
    divide by the block sizes (blocks auto-clamp to S when S < 128). With
    ``Hkv < H`` (a causal call) query head ``h`` reads key-value head
    ``h // (H / Hkv)``, and dK and dV come out at k's shape. ``window``
    (a causal call's): position ``i`` sees ``j`` only where ``i - j <
    window``. ``interpret=None`` auto-selects interpreter mode off-TPU
    (tests); pass False to force the compiled path. A causal call's
    crossed pairs are cut into the sub-tiles :func:`sub_tile` gives the
    blocks and the head size. ``select`` (a causal call's, int8 [B, S, S],
    shared by a batch element's heads): position ``i`` sees ``j`` only
    where ``select[b, i, j]`` is nonzero as well; the three kernels read
    its tile of every live pair and mask by it, in whole tiles, and it
    takes no gradient; ``with_lse`` (a selection's call) hands back the
    rows' log-sum-exp over their live keys as well, float32 [B, H, S], a
    constant to the gradient: ``exp(q . k / sqrt(D) - lse)`` is a live
    key's probability. Without a selection the call is what it was, trace
    for trace. ``v`` may have a head size of its own, [B, Hkv, S, Dv]: the
    output and dV are then [.., Dv], the second product, its accumulator
    and its blocks are Dv wide and nothing is padded to D. ``scale`` (a
    call's without a selection) multiplies the scores in ``1 / sqrt(D)``'s
    place, in all three kernels; without either the call is what it was.
    """
    if select is not None:
        if window is not None or scale is not None:
            raise ValueError("a selection goes with no window and no scale")
        out, lse = _attention_selected(q, k, v, select, block_q, block_k,
                                       interpret)
        return (out, lse) if with_lse else out
    if with_lse:
        raise ValueError("the log-sum-exp comes with a selection's call")
    return _attention(q, k, v, causal, block_q, block_k, interpret, window,
                      sub_tile(*_blocks(q.shape[2], block_q, block_k),
                               q.shape[3]), scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _attention(q, k, v, causal, block_q, block_k, interpret, window, sub,
               scale=None):
    """:func:`flash_attention` at a given ``sub`` (``None``: whole tiles)."""
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k,
                            _resolve_interpret(interpret), False, window,
                            sub, scale=scale)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret, window, sub, scale):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k,
                              _resolve_interpret(interpret), True, window,
                              sub, scale=scale)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, window, sub, scale, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k,
                           _resolve_interpret(interpret), window, sub,
                           scale=scale)


_attention.defvjp(_fwd, _bwd)


def _rows_lse(lse, q):
    """The forward's residual [B x H, S, lanes] as [B, H, S]."""
    return lse[..., 0].reshape(q.shape[:3])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attention_selected(q, k, v, select, block_q, block_k, interpret):
    """:func:`flash_attention` under a selection: causal, whole tiles;
    (the output, the rows' log-sum-exp [B, H, S])."""
    return _fwd_selected(q, k, v, select, block_q, block_k, interpret)[0]


def _fwd_selected(q, k, v, select, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, True, block_q, block_k,
                              _resolve_interpret(interpret), True,
                              select=select)
    return (out, _rows_lse(lse, q)), (q, k, v, out, lse, select)


def _bwd_selected(block_q, block_k, interpret, res, g):
    q, k, v, out, lse, select = res
    # the log-sum-exp is a constant to the gradient: g[1] is dropped
    return _flash_backward(q, k, v, out, lse, g[0], True, block_q, block_k,
                           _resolve_interpret(interpret),
                           select=select) + (None,)


_attention_selected.defvjp(_fwd_selected, _bwd_selected)
