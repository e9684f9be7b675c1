"""The state-space scan of a Mamba-2 mixer (SSD) in its chunked form:
:func:`plain`, the definition in ``jax.numpy``, and the same arithmetic as
two Pallas kernels under a ``jax.custom_vjp`` (:func:`ssd_chunked` chooses).

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
groups are walked by a ``lax.map``, a group of many heads in blocks of
them (:func:`head_block`). Running
sums, exponentials, the carried state and the recurrence are float32; the
four products take operands in ``dtype`` (bfloat16) and sum in float32,
forward and backward (:func:`_ein`). Every exponent is of a number that
is at most 0, so nothing overflows however long the sequence.

:func:`plain` is the definition. On a TPU, at shapes :func:`kernel_heads`
takes (a chunk of whole lane tiles, heads of 64 or of whole lane tiles, a
state of whole lane tiles, ANY number of groups: a group's heads in blocks
of whole sublane tiles on at most 1,024 lanes, the group itself where it
fits; :func:`kernel_refusal` names what a shape fails), :func:`ssd_chunked`
runs the same arithmetic, rounded where :func:`_group` rounds it, as two
Pallas kernels under ONE ``jax.custom_vjp`` (:func:`_scan`), and nothing
chunk-local reaches HBM. The kernels walk chunks of ONE lane tile whatever
the configuration's chunk (:func:`kernel_chunk`: the recurrence does not
depend on how it is chunked). A "unit" below is what a grid step holds: a
block of ONE group's heads (8 groups of 8 heads are 8 units, the groups
themselves; 1 group of 64 heads is 4 units of 16), and every unit of a
group reads that group's ``B`` and ``C``:

1. :func:`_fwd_kernel` (``ssd_chunk_fwd``): a grid over (sequence, unit,
   chunk), the chunks innermost and in order. A step reads the chunk's
   ``x`` as it lies, [128 positions, a unit's heads side by side on the
   lanes] out of ``[S, H P]``, its group's ``B`` and ``C`` tiles out of
   ``[S, G N]`` (or all three out of a mixer's one ``[x | B | C]``:
   ``whole``) and the
   chunk's rows of ``dt`` and ``La`` out of ``[H, S]``; ``C B^T`` once, a
   head's ``L`` and its masked product, ``C H_c`` for all the group's heads
   in one product against the state, which rides TRANSPOSED in a VMEM
   scratch (``H^T`` [N, heads x P] float32: the heads side by side, so the
   chunk's state ``B^T (Xd w)`` is one product too); the skip ``D x`` rides.
   It writes ``y`` into ``[S, H P]`` and, under differentiation, the state
   each chunk starts from (float32, 268 MB a mixer at the cell's shapes,
   alive until that mixer's backward pass).
2. :func:`_bwd_kernel` (``ssd_chunk_bwd``): the chunks in REVERSE and the
   units innermost, every unit's ``dH`` carried in VMEM; the chunk's
   products made again, every product's two gradients with the cotangent
   rounded to ``dtype`` (as :func:`_ein` does); ``dx`` a tile of ``[S, H
   P]``, ``dB`` and ``dC`` (sums over the group's heads: the products over
   a unit's lanes make a unit's part, and where a group is several units
   their parts add up in the group's tile while it stays in VMEM, the
   group's first unit writing and the others adding) a tile each of ``[S,
   G N]``, or the three as ONE block [128, H P + 2 G N] of the gradient of
   ``whole``, written once all its units are in; ``ddt`` and ``dLa`` rows
   of ``[H, S]``, the
   sums over a head's lanes made on the matrix unit (:func:`_of_heads`);
   the skip's gradient added up over the walk.

What is NOT in the kernels is small and XLA's: ``La``, the running sum of
``dt A`` inside a chunk, on ``[B, H, S]`` float32 (Mosaic has no cumulative
sum), and so ``dA`` and the part of ``ddt`` that comes through ``La``, by
plain autodiff of those lines. A chunk's small vectors arrive a head a ROW
and turn into columns in one [128, 128] transpose a chunk
(:func:`_columns`); a head's number is spread over its lanes by a
broadcast (:func:`_spread`). Neither kernel takes a DMA or a semaphore of
its own: blocks and scratch alone. Their names hold no ``mv.lm.attn``.

``ops/delta_rule.py`` (a gated delta rule's chunked form) shares
:func:`_ein`, the plain form's walk over groups of heads by a ``lax.map``
under ``jax.checkpoint`` and the rule that no exponent is positive, and nothing
else: there the state's update is a correction by what the state itself
answers, so a chunk's contribution ``V' = U - W S_c`` needs the state the
chunk starts from, and the scan over chunks carries two matrix products a
step where this one carries a multiply-add of a ``left`` that is made for
all chunks at once. Do not merge the two.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multiverso_tpu.ops.index_kernels import _bind

FWD, BWD = "ssd_chunk_fwd", "ssd_chunk_bwd"
# the positions a chunk of the kernels' walk holds: a lane tile, so that a
# head's ``L`` is [128, 128] and a chunk's small rows turn in one transpose
CHUNK = LANES = 128


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


def plain(x, dt, a, b, c, chunk: int, dtype=jnp.bfloat16):
    """``y`` [B, S, H, P] float32 of the recurrence above, without the
    skip ``D x``.

    ``x`` [B, S, H, P]; ``dt`` [B, S, H] (positive: after the softplus);
    ``a`` [H] (negative); ``b``, ``c`` [B, S, G, N], head ``h`` reading
    group ``h // (H / G)``; ``chunk`` divides S. A group's heads are
    walked in blocks of :func:`head_block` (a whole group where it is
    small, as the kernels do), one block after another, each
    rematerialised in the backward pass and each reading its group's ``B``
    and ``C``: a block's ``L`` over 16,384 positions and 8 heads is 67 MB
    of float32, all 64 heads' 537 MB, several times over in a backward
    pass."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if s % chunk or h % g:
        raise ValueError(f"{s} positions do not divide into chunks of "
                         f"{chunk}, or {h} heads into {g} groups")
    k = head_block(h // g, p)
    nc, units = s // chunk, h // k
    # block-major (a group's blocks together), then chunk-major: [G x
    # blocks, B, C, (K,) Q, ...]
    xs = x.reshape(bsz, nc, chunk, units, k, p).transpose(3, 0, 1, 4, 2, 5)
    dts = dt.astype(jnp.float32).reshape(bsz, nc, chunk, units, k).transpose(
        3, 0, 1, 4, 2)
    bs = b.reshape(bsz, nc, chunk, g, n).transpose(3, 0, 1, 2, 4)
    cs = c.reshape(bsz, nc, chunk, g, n).transpose(3, 0, 1, 2, 4)
    if units > g:
        # every block of a group reads that group's B and C; their
        # gradients add up over its blocks
        bs, cs = (jnp.repeat(t, units // g, axis=0) for t in (bs, cs))
    one = jax.checkpoint(lambda t: _group(*t, dtype))
    y = jax.lax.map(one, (xs, dts, a.astype(jnp.float32).reshape(units, k),
                          bs, cs))
    return y.transpose(1, 2, 4, 0, 3, 5).reshape(bsz, s, h, p)


class Walk(NamedTuple):
    """What a call of the kernels is, from its shapes: ``b`` sequences of
    ``s`` positions, ``groups`` groups each of ``blocks`` blocks of
    ``heads`` heads of ``p`` with a state of ``n`` (a grid step holds ONE
    block of one group's heads; ``blocks`` is 1 where a group's heads fit a
    step); ``b_at`` / ``c_at``: where ``B`` and ``C`` start in their
    operand, in blocks of ``n`` columns (``x`` starts at 0: a mixer hands
    ONE array ``[x | B | C]`` for all three, :func:`ssd_chunked`'s
    ``whole``). A chunk of the walk holds :data:`CHUNK` positions."""
    b: int
    s: int
    groups: int
    blocks: int
    heads: int
    p: int
    n: int
    b_at: int
    c_at: int
    dtype: Any
    interpret: bool

    @property
    def chunks(self) -> int:
        return self.s // CHUNK

    @property
    def units(self) -> int:          # the blocks of heads of all groups
        return self.groups * self.blocks

    @property
    def lanes(self) -> int:          # a block's heads side by side
        return self.heads * self.p

    @property
    def whole(self) -> bool:         # ONE operand holds [x | B | C]
        return self.b_at > 0


def head_block(k: int, p: int) -> int:
    """The heads of ONE group that a step of a walk takes together (the
    kernels' grid step, :func:`plain`'s ``lax.map`` step): all ``k`` where
    they lie on at most 1,024 lanes side by side, else the larger of 16 and
    8 that divides ``k`` and fits; a group that neither divides is walked
    whole (and the kernels refuse it)."""
    if k * p <= 1024:
        return k
    return next((each for each in (16, 8)
                 if k % each == 0 and each * p <= 1024), k)


def kernel_chunk(chunk: int) -> int:
    """The positions a chunk of the KERNELS' walk holds under a
    configuration's ``chunk``: one lane tile, whatever whole number of
    them the configuration's is (the recurrence does not depend on how it
    is chunked). On the chip, 1 group x 64 heads over 8,192 positions, the
    mixer's call read 0.68 ms forward and 2.98 with every gradient at 128
    and, with the walk's chunk made a parameter for the reading, 0.68 and
    2.83 at 256 (PERF.md section 6, PR 66): 0.3% of the step, for running
    sums twice as long (their differences' rounding doubles) and a second
    shape of both kernels to guard; not taken, and the parameter went."""
    return CHUNK


def kernel_refusal(s: int, h: int, p: int, g: int, n: int, chunk: int
                   ) -> Optional[str]:
    """Why :func:`plain` runs at these shapes on this process's device
    (``lm.step``'s ``ssd_kernel_why``), or ``None`` where the kernels do."""
    if jax.devices()[0].platform != "tpu":
        return "no TPU"
    if chunk % CHUNK or s % chunk:
        return (f"a chunk of {chunk} is no whole lane tiles, or {s} "
                f"positions no whole chunks")
    if h % g:
        return f"{h} heads do not divide into {g} groups"
    if n % LANES:
        return f"a state of {n} is no whole lane tiles"
    if p != 64 and p % LANES:
        return f"a head of {p} is neither 64 nor whole lane tiles"
    k = head_block(h // g, p)
    if k % 8 or k * p > 1024:
        return (f"{h // g} heads of {p} a group divide into no blocks of "
                f"whole sublane tiles on at most 1,024 lanes")
    return None


def kernel_heads(s: int, h: int, p: int, g: int, n: int, chunk: int
                 ) -> Optional[int]:
    """The heads a grid step of the kernels holds on this process's
    device (:func:`head_block` of a group's: the group whole, or a block of
    it), or ``None`` where :func:`plain` runs (:func:`kernel_refusal` says
    why: off a TPU, at a chunk or a state that is no whole lane tiles, at
    positions that are no whole chunks, at a head that is neither 64 nor
    whole lane tiles, at groups whose heads make no blocks of whole
    sublane tiles)."""
    if kernel_refusal(s, h, p, g, n, chunk) is not None:
        return None
    return head_block(h // g, p)


def step_counts(mixers: int, s: int, h: int, p: int, g: int, n: int,
                chunk: int) -> dict:
    """What ``lm.step`` spans say of ``mixers`` layers whose scan runs
    over ``s`` positions: ``ssd_kernel_layers``, those that run the kernels
    on this device (all of them or none); where none does,
    ``ssd_kernel_why`` (:func:`kernel_refusal`), and where they do
    ``ssd_kernel_chunk``, the positions a chunk of their walk holds;
    ``ssm_head_blocks``, the blocks a group's heads are walked in
    (:func:`head_block`); and ``ssd_bytes``, what ONE mixer's scan must
    move a forward pass of a sequence: ``x``, ``B``, ``C`` and ``dt`` read
    and ``y`` written once, float32 as the mixer holds them."""
    why = kernel_refusal(s, h, p, g, n, chunk)
    return {"ssd_kernel_layers": mixers * (why is None),
            **({"ssd_kernel_chunk": kernel_chunk(chunk)} if why is None
               else {"ssd_kernel_why": why}),
            "ssm_head_blocks": h // g // head_block(h // g, p),
            "ssd_bytes": 4 * s * (2 * h * p + 2 * g * n + h)}


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _columns(rows):
    """Rows [k, 128] each (a head a row, a position a lane) -> [128, 128]
    with a position a row and the rows' entries side by side in the first
    lanes: ONE transpose for all a chunk's small vectors."""
    held = sum(r.shape[0] for r in rows)
    return jnp.concatenate(
        rows + [jnp.zeros((LANES - held, CHUNK), jnp.float32)], 0).T


def _beside(cols):
    """Pieces [128, 1] side by side in the first lanes of a [128, 128] of
    zeros, as :func:`_of_heads` lays its sums."""
    return jnp.concatenate(
        cols + [jnp.zeros((CHUNK, LANES - len(cols)), jnp.float32)], 1)


def _spread(col, at: int, heads: int, p: int):
    """``col`` [Q, 128] holds a number a position and head in lanes ``at
    .. at + heads``: -> [Q, heads x p], each head's number over its ``p``
    lanes."""
    q = col.shape[0]
    one = lambda k, width: jnp.broadcast_to(col[:, at + k:at + k + 1],
                                            (q, width))
    if p % LANES == 0:
        return jnp.concatenate([one(k, p) for k in range(heads)], 1)
    per = LANES // p                 # heads a lane tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    tiles = []
    for first in range(0, heads, per):
        tile = one(first, LANES)
        for j in range(1, per):
            tile = jnp.where(lane >= j * p, one(first + j, LANES), tile)
        tiles.append(tile)
    return jnp.concatenate(tiles, 1)


def _of_heads(p: int, *wides):
    """The float32 sums over each head's ``p`` lanes of every ``wide``
    [rows, heads x p] -> [rows, 128] each, head ``k``'s in lane ``k``: on
    the matrix unit against ONE 0/1 matrix, a ``wide`` as three bfloat16
    parts (they add up to the float32 number exactly, and a part's
    products with 1 are exact in the unit's float32 sums). A lane
    reduction a head and sum took 1.6 of the backward kernel's 4.4 ms
    (PERF.md section 6, PR 65)."""
    lanes = wides[0].shape[1]
    head = jax.lax.broadcasted_iota(jnp.int32, (lanes, LANES), 0) // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (lanes, LANES), 1)
    pick = (head == lane).astype(jnp.bfloat16)
    totals = []
    for rest in wides:
        total = None
        for _ in range(3):
            part = rest.astype(jnp.bfloat16)
            rest = rest - part.astype(jnp.float32)
            piece = _dot(part, pick, _NN)
            total = piece if total is None else total + piece
        totals.append(total)
    return totals


def _chunk(x_ref, b_ref, c_ref, dt_ref, la_ref, walk: Walk):
    """What both kernels make of a chunk's operands first: ``x`` [Q,
    lanes], ``B`` and ``C`` [Q, N] in the products' dtype, ``dt`` and ``La``
    over each head's lanes, ``La``'s last row, ``Xd``, ``C B^T`` and the
    heads' ``L``."""
    k, p, dtype = walk.heads, walk.p, walk.dtype
    x = x_ref[0]
    bm, cm = b_ref[0].astype(dtype), c_ref[0].astype(dtype)
    la_r = la_ref[0]                                # [K, Q]
    col = _columns([dt_ref[0], la_r])
    dt_x, la_x = _spread(col, 0, k, p), _spread(col, k, k, p)
    i = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)

    def decay(head):       # L of one head: exp(La_i - La_j) under i >= j
        return jnp.exp(jnp.where(
            i >= j, col[:, k + head:k + head + 1] - la_r[head:head + 1],
            -jnp.inf))

    return (x, bm, cm, dt_x, la_x, la_x[CHUNK - 1:], x * dt_x,
            _dot(cm, bm, _NT), decay)


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, la_ref, d_ref, y_ref, *rest,
                walk: Walk):
    h_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = jnp.zeros_like(h_ref)

    k, p, dtype = walk.heads, walk.p, walk.dtype
    x, bm, cm, _, la_x, end_x, xd, cb, decay = _chunk(
        x_ref, b_ref, c_ref, dt_ref, la_ref, walk)
    start = h_ref[...]                              # H_c^T [N, lanes]
    if len(rest) == 2:          # the backward kernel reads it again
        rest[0][0, 0, 0] = start
    inside = [_dot((cb * decay(head)).astype(dtype),
                   xd[:, head * p:(head + 1) * p].astype(dtype), _NN)
              for head in range(k)]
    y_ref[0] = (jnp.concatenate(inside, 1)
                + _dot(cm, start.astype(dtype), _NN) * jnp.exp(la_x)
                + x * d_ref[...])
    left = _dot(bm, (xd * jnp.exp(end_x - la_x)).astype(dtype), _TN)
    h_ref[...] = jnp.exp(end_x) * start + left


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, la_ref, d_ref, g_ref, start_ref,
                *rest, walk: Walk):
    *wide_refs, ddt_ref, dla_ref, dd_ref, dh_ref, acc_ref = rest
    # ``group``: the block of heads this step holds, of all the groups'
    # (a group itself where its heads are one block)
    t, group = pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _start():       # a sequence's LAST chunk: nothing follows it
        dh_ref[group] = jnp.zeros(dh_ref.shape[1:], jnp.float32)
        acc_ref[group] = jnp.zeros(acc_ref.shape[1:], jnp.float32)

    k, p, n, dtype = walk.heads, walk.p, walk.n, walk.dtype
    x, bm, cm, dt_x, la_x, end_x, xd, cb, decay = _chunk(
        x_ref, b_ref, c_ref, dt_ref, la_ref, walk)
    gy, start, after = g_ref[0], start_ref[0, 0, 0], dh_ref[group]
    ela_x, w_x = jnp.exp(la_x), jnp.exp(end_x - la_x)
    # what the chunk was handed: y += (C H_c) exp(La)
    held = start.astype(dtype)
    handed = _dot(cm, held, _NN)
    g = (gy * ela_x).astype(dtype)
    dc = _dot(g, held, _NT)
    dstart = _dot(cm, g, _TN)
    # what it leaves: H_{c+1} = exp(end) H_c + B^T (Xd exp(end - La))
    left = xd * w_x
    g = after.astype(dtype)
    dleft = _dot(bm, g, _NN)
    db = _dot(left.astype(dtype), g, _NT)
    dh_ref[group] = jnp.exp(end_x) * after + dstart
    dxd = dleft * w_x
    # the exponents': the rows' La_i less the columns', and the end's
    dla_x = gy * handed * ela_x - dleft * left
    dend_x = (jnp.sum(dleft * left, 0, keepdims=True)
              + jnp.exp(end_x) * jnp.sum(after * start, 0, keepdims=True))
    # inside the chunk: y += ((C B^T) o L) Xd
    gyb, dcb, inside, rows, cols = gy.astype(dtype), None, [], [], []
    for head in range(k):
        at = slice(head * p, (head + 1) * p)
        l = decay(head)
        m = cb * l
        dm = _dot(gyb[:, at], xd[:, at].astype(dtype), _NT)
        inside.append(_dot(m.astype(dtype), gyb[:, at], _TN))
        e = dm * m
        dcb = dm * l if dcb is None else dcb + dm * l
        rows.append(jnp.sum(e, 1, keepdims=True))
        cols.append(jnp.sum(e, 0, keepdims=True))
    dxd = dxd + jnp.concatenate(inside, 1)
    g = dcb.astype(dtype)
    dx = dxd * dt_x + gy * d_ref[...]
    dc, db = dc + _dot(g, bm, _NN), db + _dot(g, cm, _TN)
    # dB and dC are sums over ALL the group's heads: where a group is
    # several blocks they are consecutive steps, the group's tile stays in
    # VMEM over them, and the first of them writes where the others add
    of = group if walk.blocks == 1 else group // walk.blocks
    if walk.whole:      # [dx | dB | dC], as the operand lies
        dw_ref, = wide_refs

        def put(at, width, grad, add=False):    # ``width`` lanes from ``at``
            where = (0, slice(None),            # blocks
                     pl.ds(pl.multiple_of(at * width, width), width))
            dw_ref[where] = dw_ref[where] + grad if add else grad

        put(group, walk.lanes, dx)

        def shared(add):
            put(walk.b_at + of, n, db, add)
            put(walk.c_at + of, n, dc, add)
    else:
        dx_ref, db_ref, dc_ref = wide_refs
        dx_ref[0] = dx

        def shared(add):
            db_ref[0] = db_ref[0] + db if add else db
            dc_ref[0] = dc_ref[0] + dc if add else dc

    if walk.blocks == 1:
        shared(False)
    else:
        first = group % walk.blocks == 0
        pl.when(first)(lambda: shared(False))
        pl.when(jnp.logical_not(first))(lambda: shared(True))
    acc_ref[group] += jnp.sum(gy * x, 0, keepdims=True)
    dd_ref[0] = acc_ref[group]      # the walk's last chunk writes last
    last = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0) == CHUNK - 1
    ddt, dla, dend = _of_heads(p, dxd * x, dla_x, dend_x)
    ddt_ref[0] = ddt.T[:k]
    dla_ref[0] = (dla + _beside(rows) + jnp.where(last, dend, 0.0)).T[:k] - (
        jnp.concatenate(cols, 0))


def _calls(walk: Walk):
    """(the forward ``pallas_call`` without the chunks' states, with them,
    the backward): ``(x, B, C, dt^T, La^T, D) -> y [, H]`` and ``(x, B, C,
    dt^T, La^T, D, dy, H) -> (dx, dB, dC, ddt^T, dLa^T, dD)``, or ``(d[x | B
    | C], ...)`` where one operand holds the three; ``dt^T``, ``La^T`` [B,
    heads, S], ``D`` [1, heads x p], ``H`` [B, units, chunks, N, lanes]
    float32, a unit a block of one group's heads (``walk.units``: the
    groups themselves where a group's heads are one block). The forward
    walks a unit's chunks in order, every block of a group reading that
    group's ``B`` / ``C`` tile; the backward walks the chunks in reverse
    with the UNITS innermost, every unit's ``dH`` in VMEM, so that a
    chunk's ``[dx | dB | dC]`` block of all the groups is written as one,
    a group's ``dB`` and ``dC`` summed over its blocks in it."""
    b, s, k, n = walk.b, walk.s, walk.heads, walk.n
    g, units, lanes, steps = walk.groups, walk.units, walk.lanes, walk.chunks
    group = (lambda u: u) if walk.blocks == 1 else (
        lambda u: u // walk.blocks)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    params = lambda *order: pltpu.CompilerParams(
        dimension_semantics=("parallel",) + order,
        vmem_limit_bytes=64 << 20)

    def specs(of):      # of(*grid indices) -> (sequence, unit, chunk)
        def spec(block, at):
            return pl.BlockSpec(block, lambda *ids: at(*of(*ids)))

        wide = spec((1, CHUNK, lanes), lambda b, u, c: (b, c, u))
        state = lambda first: spec((1, CHUNK, n),
                                   lambda b, u, c: (b, c, first + group(u)))
        small = spec((1, k, CHUNK), lambda b, u, c: (b, u, c))
        skip = spec((1, lanes), lambda b, u, c: (0, u))
        start = spec((1, 1, 1, n, lanes), lambda b, u, c: (b, u, c, 0, 0))
        return wide, state, small, skip, start, spec

    wide, state, small, skip, start, _ = specs(lambda b, u, t: (b, u, t))
    ins = [wide, state(walk.b_at), state(walk.c_at), small, small, skip]
    fwd = [pl.pallas_call(
        functools.partial(_fwd_kernel, walk=walk), grid=(b, units, steps),
        in_specs=ins, out_specs=[wide, start][:1 + keep],
        out_shape=[f32(b, s, units * lanes),
                   f32(b, units, steps, n, lanes)][:1 + keep],
        scratch_shapes=[pltpu.VMEM((n, lanes), jnp.float32)],
        compiler_params=params("parallel", "arbitrary"), name=FWD,
        interpret=walk.interpret) for keep in (False, True)]
    wide, state, small, skip, start, spec = specs(
        lambda b, t, u: (b, u, steps - 1 - t))
    width = units * lanes + 2 * g * n
    grads = ([spec((1, CHUNK, width), lambda b, u, c: (b, c, 0))],
             [f32(b, s, width)]) if walk.whole else (
        [wide, state(0), state(0)],
        [f32(b, s, units * lanes), f32(b, s, g * n), f32(b, s, g * n)])
    bwd = pl.pallas_call(
        functools.partial(_bwd_kernel, walk=walk), grid=(b, steps, units),
        in_specs=[wide, state(walk.b_at), state(walk.c_at), small, small,
                  skip, wide, start],
        out_specs=grads[0] + [small, small,
                              spec((1, 1, lanes), lambda b, u, c: (b, 0, u))],
        out_shape=grads[1] + [f32(b, units * k, s), f32(b, units * k, s),
                              f32(b, 1, units * lanes)],
        scratch_shapes=[pltpu.VMEM((units, n, lanes), jnp.float32),
                        pltpu.VMEM((units, 1, lanes), jnp.float32)],
        compiler_params=params("arbitrary", "arbitrary"), name=BWD,
        interpret=walk.interpret)
    return fwd[0], fwd[1], bwd


def _call(which: int, walk: Walk, operands):
    """Kernel ``which`` of :func:`_calls`, traced once a process for its
    walk and operands (``index_kernels._bind``): a step calls it once a
    mixer, and again under ``jax.checkpoint``."""
    return _bind(((FWD, FWD, BWD)[which], which, walk),
                 lambda *ops: _calls(walk)[which](*ops), operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(walk: Walk, wide, *small):
    """``wide``: ``(x, B, C)`` [B, S, ...] float32 each, or the ONE array
    that holds the three side by side (a kernel takes it three times);
    ``small``: ``dt^T``, ``La^T``, ``D`` (:func:`_calls`)."""
    return _call(0, walk, (*wide * (3 // len(wide)), *small))[0]


def _scan_fwd(walk, wide, *small):
    y, starts = _call(1, walk, (*wide * (3 // len(wide)), *small))
    return y, (wide, small, starts)


def _scan_bwd(walk, res, g):
    wide, small, starts = res
    *dwide, ddt, dla, dd = _call(
        2, walk, (*wide * (3 // len(wide)), *small, g, starts))
    return tuple(dwide), ddt, dla, dd.sum(0)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _kernels(wide, dt, a, skip, heads: int, p: int, groups: int, n: int,
             dtype, interpret: bool):
    """The kernels on ``wide`` (:func:`_scan`): ``La`` and what follows
    from its gradient (``ddt``'s second part, ``dA``) are XLA's, on [B, H,
    S] arrays; the skip's ``D`` is spread over each head's lanes."""
    b, s = dt.shape[:2]
    k = head_block(heads // groups, p)
    walk = Walk(b, s, groups, heads // groups // k, k, p, n,
                *((0, 0) if len(wide) == 3 else
                  (heads * p // n, heads * p // n + groups)),
                jnp.dtype(dtype), interpret)
    dt_r = dt.astype(jnp.float32).transpose(0, 2, 1)
    la_r = jnp.cumsum(
        (dt_r * a.astype(jnp.float32)[:, None]).reshape(
            b, heads, s // CHUNK, CHUNK), -1).reshape(b, heads, s)
    lanes = (jnp.zeros((1, heads * p), jnp.float32) if skip is None
             else jnp.repeat(skip.astype(jnp.float32), p)[None])
    return _scan(walk, wide, dt_r, la_r, lanes)


def ssd_chunked(x, dt, a, b, c, chunk: int, dtype=jnp.bfloat16, *,
                skip=None, whole=None, kernel: Optional[bool] = None,
                interpret: bool = False):
    """:func:`plain`'s ``y`` [B, S, H, P] float32, plus ``skip`` [H] times
    ``x`` where one is handed: the two kernels where :func:`kernel_heads`
    finds them a group on this device (or a test says ``kernel``, with the
    interpreter), :func:`plain` anywhere else: one function either way.

    ``whole``: the float32 array ``[x | B | C]`` [B, S, H P + 2 G N] that
    ``x``, ``b`` and ``c`` are the column windows of, where the caller
    holds it (a mixer does: its convolution's result). The kernels then
    read the three out of it, and the windows, which would be copied out
    for a custom call, are never made."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    if kernel is None:
        kernel = kernel_heads(s, h, p, g, n, chunk) is not None
    if not kernel:
        y = plain(x, dt, a, b, c, chunk, dtype)
        return y if skip is None else y + skip[:, None] * x
    f32 = jnp.float32
    if whole is None or whole.dtype != f32 or (h * p) % n:
        wide = (x.astype(f32).reshape(bsz, s, h * p),
                b.astype(f32).reshape(bsz, s, g * n),
                c.astype(f32).reshape(bsz, s, g * n))
    else:
        wide = (whole,)
    return _kernels(wide, dt, a, skip, h, p, g, n, dtype,
                    interpret).reshape(x.shape)
