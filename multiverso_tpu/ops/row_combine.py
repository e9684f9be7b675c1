"""Scatter-add of a minibatch's update rows with its duplicates combined
first: the table sees each distinct row once. And, on a row-sharded table,
the read of those rows by the shards that own them.

XLA's scatter-add into a large table costs the same for every update row
it is handed (0.10 us on a v5e into ``f32[1800001,300]``), a repeat of a
row it has just written included, and a slot whose id it drops included.
A word2vec minibatch repeats a third of its rows (5,349 distinct of 8,192
on the benchmark's stream), and all of a row's updates are computed at one
stale value anyway, so summing them first is the same float32 sum in
another order. The sum into a ``[B, D]`` buffer runs eight times faster a
row than the scatter into the table.

* :func:`plan_rows` — from ids ``[..., B]``: the run (distinct id) each
  update row belongs to, the distinct ids ascending and padded to ``B``
  with distinct ids past the table's last row, their number, and for each
  row shard of the table where its rows past its head start and end among
  them. Leading axes are walked a minibatch at a time, so an epoch makes
  its plans before its scan, off the minibatch's path.
* :func:`combine_rows` — the float32 sum of each run, ``[B, D]``.
* :func:`add_rows` — the sums, then the table write over the distinct
  rows alone: one dense add for the head, and for the others the table
  scatter, a chunk of slots at a time from the first id past the head up
  to the last distinct row. Pad slots of the last chunk are dropped by the
  scatter (``mode="drop"``): they write nothing, so ``unique_indices`` is
  a true promise. Where the table is float32, whole lanes wide (a width
  that is a multiple of 128) and not row-sharded, on a TPU, the walk is
  not XLA's scatter but a Pallas read-modify-write of 8-row tiles that
  pays by the row (:func:`tile_walk`, :func:`_walk_tiles`; PERF.md,
  PR 45): the array decides, no caller says which.
* :func:`take_rows` — ``jnp.take`` of the ids' rows, which on a
  row-sharded table every shard makes of its own rows alone (below).

Row shards (PERF.md, PR 36). A scatter slot costs a chip the same whether
it keeps or drops the row, so a chip that is handed the whole plan pays
for every other chip's rows. On a row-sharded table :func:`add_rows` is
one ``shard_map``: a chip adds its own part of the head (the first
``HEAD // shards`` rows of EVERY shard) and walks its own range of the
plan, nothing else. That shares the work out only if the rows a minibatch
names are shared out, and ids that are frequency ranks, split into
contiguous ranges, all lie in the first: :func:`striped_row` deals the
ranks round the shards, and the app that owns the table applies it where
a word becomes a row (``apps/word_embedding``). On one shard all of this
is the identity and the program is the one it was.

Reads on row shards (PERF.md, PR 38). Left to the partitioner, a
``jnp.take`` on a table sharded by rows is a gather of all ``B`` slots on
every chip (zeros for the rows it does not own) and an all-reduce of ``S``
copies of which ``S - 1`` are zeros: on four v5e chips 0.61 of a 1.18 ms
minibatch. The plan already holds a minibatch's distinct rows sorted by
row id, so a shard's rows are ONE range of ``uniq``, from the end of the
shard before it to its own ``end``. :func:`take_rows` is one
``shard_map``: a chip reads :func:`gather_cap` slots of its range from its
own shard, casts them to the compute type, ``lax.all_gather`` hands every
chip every chip's ``[S * cap, D]`` (half an all-reduce's bytes on the
links, one phase and not two), and one gather from that buffer by
``plan.place`` (made in :func:`plan_rows`, off the minibatch's path: for
each update row its round, its owner and its offset in the owner's range)
gives the ``[B, D]`` rows in the ids' order. ``cap`` is static; a
minibatch whose busiest shard owns more distinct rows takes more rounds, a
``while`` whose trip count every chip reckons alike from the replicated
plan, so any ids are read exactly, all in one shard included. The rows
are the table's own bits cast once, where the partitioner summed a row
and three zeros (which differs only for a ``-0.0``, handed on as it is
here and as ``+0.0`` there).

What the chip said about the promises (PERF.md, PR 28): ``unique_indices``
changes nothing in the v5e's scatter today, and ``indices_are_sorted``
selects a program that streams the whole table (11 times slower at 1.8M
rows), so the ids are sorted and only the first promise is made.

``HEAD`` (PERF.md, PR 31; one shard's figures). Where ids are frequency
ranks (the word2vec dictionary numbers words by falling count) a
minibatch's hot rows are the first slots of its plan and the first rows
of the table: on the
benchmark's stream 43% of a minibatch's 5,319 distinct rows lie below
8,192. The head's delta is gathered from the combined sums by the plan
(``head_run``: a head row's run; ``-0.0`` where the minibatch has none),
and ``table[:HEAD] += delta`` is one contiguous read-modify-write in
place, no scatter slot; the walk keeps 12 chunks of 21. Read on one v5e
inside the epoch program, ms a minibatch (two tables) at ``HEAD`` 0 /
4,096 / 6,144 / 8,192 / 12,288 / 16,384: 1.545 / 1.259 / 1.241 / 1.227 /
1.238 / 1.267. The head is paid whatever it holds: ``add_rows`` alone
takes 0.631 ms without a head, 0.472 with it on that stream, and 0.694 on
a minibatch none of whose rows lies below ``HEAD`` (ids that are no
ranks: 0.063 ms a table for nothing). A ``lax.cond`` around the dense add
is no way out: it cost the epoch program 0.065 ms a minibatch and saved
such a minibatch nothing. The sums stay a buffer of ``B + CHUNK`` rows on
purpose: summing the head's updates straight onto their row ids, in one
buffer of ``HEAD + B + CHUNK`` rows, needs no gather but read 1.317,
because a sum into more than 8,192 rows of 300 takes 0.18 ms where this
one takes 0.11; laying the runs out by a scatter read 1.419 (``B``
slots) and 1.341 (a walk of the head's slots).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec

# Slots a table scatter takes at a time: the scatter's cost follows the
# slots it is handed, so the walk stops within one chunk of the last
# distinct row. Read on the chip for 5,343 distinct rows of 8,192 (PERF.md,
# PR 28): 0.67 / 0.62 / 0.59 / 0.54 / 0.57 / 0.61 ms a minibatch at chunks
# of 32 / 64 / 128 / 256 / 512 / 1,024 (one scatter of 8,192: 0.84).
CHUNK = 256

# Rows at the head of a table that take one dense add a minibatch and no
# scatter slot (the whole table where it has no more rows than this).
HEAD = 8192
_NONE = 2 ** 31 - 1      # a head row without a run: past any buffer of sums

# A float32 array on the chip is tiles of 8 rows by 128 lanes. The tile
# kernel (:func:`_walk_tiles`) reaches a row through its aligned 8-row tile,
# and this Mosaic slices an HBM array only where its width is whole lanes.
LANES, TILE = 128, 8
# The kernel takes the tiles a call names in rounds of ROUND, each in a
# bank of ROUND buffers of a ring of BANKS banks, and starts a round's
# reads ROUNDS_AHEAD rounds before it adds to them.
ROUND, BANKS, ROUNDS_AHEAD = 8, 4, 2
LISTED = 8      # slots the kernel's listing of the tiles takes in a line


class RowPlan(NamedTuple):
    """How the update rows ``ids [..., B]`` of a table reach it combined.
    ``S`` is the table's row shards (1 for a whole table)."""
    run: jax.Array       # [..., B] int32: the run of each update row
    uniq: jax.Array      # [..., B] int32: run -> row id ascending, then pads
    count: jax.Array     # [...] int32: runs, i.e. distinct ids
    head: jax.Array      # [..., S] int32: the slot of uniq where a shard's
    #                      rows past its head start: its walk starts here
    end: jax.Array       # [..., S] int32: and the slot where its rows end
    head_run: jax.Array  # [..., S * (head rows a shard)] int32: head row ->
    #                      its run, or _NONE
    place: jax.Array     # [..., B] int32: where take_rows finds each update
    #                      row among the rows the shards hand round; [..., 0]
    #                      on one shard, which hands nothing round


def striped_row(word, shards: int, per: int):
    """The row of word (frequency rank) ``word`` in a table of ``shards``
    row shards of ``per`` rows each: ranks are dealt round the shards, so
    every shard owns one in ``shards`` of the hot rows (split into
    contiguous ranges of ranks, the first shard owns them all). The
    identity on one shard. Integers or integer arrays, NumPy's or JAX's."""
    return word if shards == 1 else (word % shards) * per + word // shards


def striped_word(row, shards: int, per: int):
    """:func:`striped_row`'s inverse: the word that lives in row ``row``
    (for a row that holds none, a number past the last word)."""
    return row if shards == 1 else (row % per) * shards + row // per


def striped_table_rows(words: int, shards: int) -> int:
    """Rows a table needs so that every :func:`striped_row` of ``words``
    words is one of its own rows, given that a table of ``n`` rows holds
    ``ceil((n + 1) / shards)`` a shard: on one shard ``words``."""
    return words if shards == 1 else shards * (-(-words // shards) + 1) - 1


def row_shards(sharding) -> Tuple[Optional[str], int]:
    """The mesh axis a table's rows are sharded over and how many shards
    that makes, from the table's sharding: ``(None, 1)`` for a whole
    table (or no sharding at all)."""
    axis = (sharding.spec[0] if isinstance(sharding, NamedSharding)
            and len(sharding.spec) else None)
    return axis, (1 if axis is None else sharding.mesh.shape[axis])


def gather_cap(b: int, shards: int) -> int:
    """Slots a row shard reads for a minibatch of ``b`` update rows in one
    round of :func:`take_rows`: a static size (it shapes the all-gather),
    from ``b`` and the shard count alone, three quarters of the shard's
    share of the update rows. A shard that owns more distinct rows takes
    another round, so the size decides speed and never the result.

    What the size counts on: that the busiest shard of a minibatch owns no
    more than ``3 * b / (4 * shards)`` DISTINCT rows, which with rows
    dealt evenly (:func:`striped_row`) says a quarter or more of the
    minibatch's update rows are repeats. Word pairs' are: on the benchmark's stream
    68% of a minibatch's update rows are distinct (5,600 of 8,192;
    ``counts.unique_share.we``) and the busiest of four shards owns 1,435
    in the mean and 1,505 to 1,555 at the most over eight seeds, so about
    one minibatch in 3,500 takes a second round. The read's cost follows
    the slots: on four v5e chips the epoch runs at 1,841,700 to 1,844,000
    words/s with 1,536 slots and 1,704,400 to 1,705,100 with ``b //
    shards`` = 2,048 (two seeds each). Where the property fails, ids
    without a duplicate, every minibatch takes two rounds, at ``b //
    shards`` too (a busiest shard of 2,055 to 2,111): a read of 0.246 ms
    where the one round takes 0.173 and the partitioner's gather and
    all-reduce 0.439, so 0.07 ms a table slower and still ahead (PERF.md,
    PR 38; ``chip_smoke.py`` stage ``rows``). The size cannot follow the
    ids: it is a shape of the compiled epoch."""
    return b if shards == 1 else max(3 * b // (4 * shards), 1)


def plan_rows(ids: jax.Array, table_rows: int, shards: int = 1) -> RowPlan:
    """The plan for update-row ids ``[..., B]`` into a table of
    ``table_rows`` rows in ``shards`` equal contiguous row shards.
    ``uniq[..., u]`` is the id of run ``u``; slots past the last run hold
    ``table_rows + j`` for distinct ``j``: out of range, ascending, never
    equal, so a ``mode="drop"`` scatter skips them and the whole of
    ``uniq`` is sorted and unique, and a shard's rows are one contiguous
    range of it. The head is the first ``HEAD // shards`` rows of EVERY
    shard (all of a shard that has no more): ``head_run`` finds the run of
    each of them, shard after shard, and ``head[..., k]`` to
    ``end[..., k]`` are the slots of shard ``k``'s rows past its head."""
    if ids.ndim > 1:
        # a row at a time: a batched sort of [439, 8192] takes the v5e's
        # compiler 9.5 s and 7 ms to run, this loop 1.8 s and 14 ms
        lead = ids.shape[:-1]
        plan = lax.map(lambda row: plan_rows(row, table_rows, shards),
                       ids.reshape(-1, ids.shape[-1]))
        return RowPlan(*(a.reshape(lead + a.shape[1:]) for a in plan))
    ids = ids.astype(jnp.int32)
    b = ids.shape[0]
    # three sorts and no gather from the ids: an element gather of an
    # epoch's ids takes a v5e five times what a sort of them does
    slots = jnp.arange(b, dtype=jnp.int32)
    srt, perm = lax.sort((ids, slots), num_keys=1)
    first = jnp.concatenate([jnp.ones(1, bool), srt[1:] != srt[:-1]])
    run_sorted = jnp.cumsum(first.astype(jnp.int32)) - 1
    uniq = jnp.sort(jnp.where(first, srt, table_rows + slots))
    per = table_rows // shards
    part = min(HEAD // shards, per)          # head rows a shard
    first_row = jnp.arange(shards, dtype=jnp.int32) * per
    end = jnp.searchsorted(uniq, first_row + per).astype(jnp.int32)
    if shards == 1:
        # back to the ids' own order: update row perm[i] lies in
        # run_sorted[i]
        _, run = lax.sort((perm, run_sorted), num_keys=1)
        place = slots[:0]
    else:
        # and its place among the rows take_rows hands round: round after
        # round, shard after shard, a shard's own distinct rows in order,
        # gather_cap(b, shards) of them a round
        cap, owner = gather_cap(b, shards), srt // per
        at = run_sorted - jnp.take(_begin(end), owner)
        _, run, place = lax.sort(
            (perm, run_sorted, (at // cap * shards + owner) * cap + at % cap),
            num_keys=1)
    owner, local = uniq // per, uniq % per   # a pad's owner is no shard
    head_run = jnp.full(shards * part, _NONE, jnp.int32).at[
        jnp.where(local < part, owner * part + local, _NONE)].set(
            slots, mode="drop")
    return RowPlan(run, uniq, run_sorted[-1] + 1,
                   jnp.searchsorted(uniq, first_row + part).astype(jnp.int32),
                   end, head_run, place)


def _begin(end: jax.Array) -> jax.Array:
    """Where each shard's rows start among a plan's distinct rows, from
    where they end (``RowPlan.end``): where the shard before it ends."""
    return jnp.concatenate(
        [jnp.zeros_like(end[..., :1]), end[..., :-1]], axis=-1)


def _rounds(owned: jax.Array, cap: int) -> jax.Array:
    """Rounds of ``cap`` slots a shard that :func:`take_rows` reads the
    rows of a minibatch in, where its shards own ``owned [..., S]`` of
    them: as many as the busiest shard needs, the same on every shard,
    and one where none owns a row."""
    return jnp.maximum((jnp.max(owned, axis=-1) + cap - 1) // cap, 1)


def plan_counts(plan: RowPlan) -> jax.Array:
    """What the table writes and reads of the plans ``plan`` (any leading
    axes) are handed, ``int32[3 + S]``, one array for one read-back: the
    distinct rows; those of them in the shards' heads, which the dense
    adds take; for each shard the slots its walks are handed, the pads of
    a last chunk included; and the rounds past the first that the reads
    take (:func:`take_rows`; 0 says one round of :func:`gather_cap` slots
    a shard held every plan's busiest shard, and on one shard there are
    no rounds). Every shard reads ``cap`` slots a round whatever it owns,
    so its slots are the plans and these rounds times ``cap``."""
    b, shards = plan.run.shape[-1], plan.head.shape[-1]
    chunk = min(CHUNK, b)
    begin = _begin(plan.end)
    walks = (plan.end - plan.head + chunk - 1) // chunk * chunk
    rounds = _rounds(plan.end - begin, gather_cap(b, shards))
    return jnp.concatenate([
        jnp.stack([jnp.sum(plan.count), jnp.sum(plan.head - begin)]),
        jnp.sum(walks.reshape(-1, shards), axis=0),
        jnp.sum(rounds - 1)[None]]).astype(jnp.int32)


def combine_rows(updates: jax.Array, plan: RowPlan,
                 pad: int = 0) -> jax.Array:
    """``[B + pad, D]`` float32: row ``u`` is the sum of the updates of
    run ``u``, rows past the last run are zero. Whatever the updates'
    type, the sum is taken in float32."""
    return jax.ops.segment_sum(updates.astype(jnp.float32), plan.run,
                               num_segments=updates.shape[0] + pad)


def take_rows(table: jax.Array, ids: jax.Array,
              plan: Optional[RowPlan] = None, sharding=None,
              dtype=None) -> jax.Array:
    """``jnp.take(table, ids, axis=0).astype(dtype)`` for ids ``[B]``, and
    on a whole table (``sharding`` names no row axis, or one of one
    shard) that very program.
    On ``S`` row shards every shard reads the rows it owns and no others,
    and the shards hand them round, in one ``shard_map``: a shard's
    distinct rows are one range of the plan's ``uniq``; it reads
    :func:`gather_cap` slots of it from its own rows (slots past its range
    read a row it clips to, which nobody looks at), casts them, and an
    all-gather gives every shard every shard's ``[S * cap, D]``; one
    gather from that by ``plan.place`` lays the rows out in the ids' own
    order, duplicates and all. A minibatch whose busiest shard owns more
    than ``cap`` distinct rows takes more rounds: every shard reckons
    their number alike from the replicated plan, so the all-gathers stay
    in step. Every row is the table's own bits (cast), whatever the ids.

    Left to the partitioner, ``jnp.take`` on a row-sharded table is a
    gather of all ``B`` slots on every shard, zeros for rows it does not
    own, and an all-reduce of ``S`` copies of which ``S - 1`` are zeros
    (PERF.md, PR 38)."""
    axis, shards = row_shards(sharding)
    dtype = dtype or table.dtype
    if shards == 1:
        return jnp.take(table, ids, axis=0).astype(dtype)
    if plan is None:
        plan = plan_rows(ids, table.shape[0], shards)
    cap = gather_cap(ids.shape[0], shards)
    begin = _begin(plan.end)
    # a last round may run past the last slot: pad slots, as far away
    uniq = jnp.concatenate(
        [plan.uniq, jnp.full(cap, _NONE, jnp.int32)])

    def local(tab, uniq, begin, place, rounds):
        shard = lax.axis_index(axis)
        start, first_row = begin[shard], shard * tab.shape[0]

        def round_(r, out=None):
            """The update rows whose place lies in round ``r``, over
            ``out``; the first round (no ``out``) lays all ``[B, D]``
            out, and later rounds overwrite what it clipped."""
            mine = lax.dynamic_slice(uniq, (start + r * cap,), (cap,))
            rows = jnp.take(tab, mine - first_row, axis=0, mode="clip")
            if out is not None:
                # times one, which the compiler cannot know: else it casts
                # before it reads, and so casts the WHOLE shard ahead of
                # the loop of later rounds, every minibatch (2.3 GB of
                # temporaries at 3,000,001 x 300 on a v5e; an
                # optimization_barrier does not stop it)
                rows = rows * jnp.minimum(r, 1).astype(rows.dtype)
            handed = lax.all_gather(rows.astype(dtype), axis, tiled=True)
            at = place - r * shards * cap
            got = jnp.take(handed, at, axis=0, mode="clip")
            if out is None:
                return got
            return jnp.where(((at >= 0) & (at < shards * cap))[:, None],
                             got, out)

        return lax.while_loop(
            lambda c: c[0] < rounds, lambda c: (c[0] + 1, round_(*c)),
            (jnp.int32(1), round_(0)))[1]

    # every shard ends with the same rows, which the checker cannot know:
    # to it what lax.all_gather hands out differs by shard. The collective
    # that says otherwise is private in jax 0.9.0
    # (jax._src.lax.parallel.all_gather_invariant): once jax.lax exports
    # it, use it above and drop check_vma=False, which turns the check off
    # for the whole body
    return jax.shard_map(
        local, mesh=sharding.mesh,
        in_specs=(PartitionSpec(axis, None),) + (PartitionSpec(),) * 4,
        out_specs=PartitionSpec(), check_vma=False)(
            table, uniq, begin, plan.place,
            _rounds(plan.end - begin, cap))


def lane_wide(width: int) -> int:
    """``width`` rounded up to whole lanes: the width at which an array
    of a program's own (a PS block's bucket) meets :func:`tile_walk`. A
    float32 row of 300 occupies 384 lanes on the chip either way."""
    return -(-width // LANES) * LANES


def _kernel_interpret() -> Optional[bool]:
    """How the tile kernel runs here: compiled on a TPU, and off it not
    at all (``None``: XLA's walk). A test that drives the kernel in the
    interpreter, or compiles it for a described chip, patches this."""
    return False if jax.devices()[0].platform == "tpu" else None


def tile_walk(table, axis=None) -> Optional[bool]:
    """Whether :func:`add_rows` walks ``table`` (anything with a shape
    and a dtype; ``axis`` the mesh axis its rows are sharded over) with
    the tile kernel, by what the array is: float32, whole lanes wide,
    not row-sharded, on a TPU. ``None`` says XLA's scatter walks it;
    else the kernel's ``interpret``."""
    if (axis is not None or table.dtype != jnp.float32
            or len(table.shape) != 2 or table.shape[1] % LANES):
        return None
    return _kernel_interpret()


def kernel_rows(table, sharding, unique_rows, head_rows) -> int:
    """Of the counts of plans whose rows :func:`add_rows` wrote into
    ``table`` (:func:`plan_counts`' first two), the rows the tile kernel
    was handed: every distinct row past the heads where
    :func:`tile_walk` says it walks, none where XLA's scatter does."""
    walks = tile_walk(table, row_shards(sharding)[0]) is not None
    return int(unique_rows - head_rows) if walks else 0


def _walk_tiles(tab: jax.Array, uniq: jax.Array, sums: jax.Array,
                start: jax.Array, end: jax.Array,
                interpret: bool) -> jax.Array:
    """The walk as a Pallas kernel: ``tab[uniq[j]] += sums[j]`` for the
    slots ``start <= j < end`` of ascending distinct in-range row ids,
    ``tab`` ``f32[R, W]`` whole lanes wide, left in HBM and written in
    place. XLA's scatter costs 0.100 us a slot on a v5e whatever the slot
    holds; this pays by the tile a row lies in, 0.05 to 0.075 us a row
    (PERF.md, PR 45).

    A row is reached through its aligned 8-row tile (Mosaic slices a tiled
    HBM array by whole tiles): the tile is read into a VMEM buffer, the
    sums of the rows of ``uniq`` that fall in it are added, each to its
    line, and the tile is written back. Ids ascend, so a tile's rows are
    neighbours in ``uniq``: a tile is read once and written once however
    many of its rows are named, no two DMAs of a call touch the same
    tile, and every bit of a line that is not named comes back as it was
    read. The trip count is the data's, so the loops are inside the
    kernel. A first pass over the slots, scalars only, lists the tiles
    (the id of each tile's first row and its slot). Then the tiles go by
    in rounds of ``ROUND``, each round in its own bank of buffers,
    ``BANKS`` banks in a ring: a round waits for its reads, adds, starts
    its writes, and starts the reads of the round ``ROUNDS_AHEAD`` on,
    into the bank whose writes (``BANKS`` rounds back) it first waits
    for. What the scalar core pays for is control: a DMA started or
    waited for behind a branch of its own cost 0.03 us, five times its
    issue, so a round whose tiles and read-ahead are all there is ONE
    straight line of ``ROUND`` unrolled tiles (buffers at fixed offsets
    of the bank) and only the first and last rounds of a call take the
    loops with their counts. The kernel ends when every write has landed.

    Where ``R`` is no multiple of 8 the last rows are no whole tile (a
    PS block's dummy row is one such): they take one dense add of their
    own outside the kernel, as the head's rows do."""
    rows, width = tab.shape
    slots, whole = uniq.shape[0], rows // TILE * TILE
    if rows > whole:
        edge = jnp.arange(whole, rows + 1, dtype=jnp.int32)
        # where each edge row (and the first id past them) falls in uniq
        at = jnp.sum(uniq[None, :] < edge[:, None], axis=1, dtype=jnp.int32)
        named = ((at[:-1] >= start) & (at[:-1] < end)
                 & (jnp.take(uniq, at[:-1]) == edge[:-1]))
        tab = tab.at[whole:].add(jnp.where(
            named[:, None], jnp.take(sums, at[:-1], axis=0), -0.0))
        end = jnp.clip(at[0], start, end)
        if not whole:
            return tab

    traced = _tile_kernel(rows, width, slots, interpret, ROUND, BANKS,
                          ROUNDS_AHEAD, LISTED)
    return jax.core.eval_jaxpr(
        traced.jaxpr, traced.consts,
        jnp.stack([start, end]).astype(jnp.int32), uniq, sums, tab)[0]


@functools.lru_cache(maxsize=64)
def _tile_kernel(rows: int, width: int, slots: int, interpret,
                 ROUND: int, BANKS: int, ROUNDS_AHEAD: int, LISTED: int):
    """:func:`_walk_tiles`' kernel call on ``(bounds, uniq, sums, tab)``,
    traced once for its shapes and bound again from its jaxpr: a block
    program calls it for the centres and in the columns' loop, and
    tracing the kernel body (then lowering it, which equations that carry
    one jaxpr share) is seconds of Python on a busy host, in a program
    that compiles on every run."""

    # Scalars here are lax's own primitives on int32: a ``//``, ``%``,
    # ``where`` or ``clip`` of jax.numpy is a function of several
    # operations traced and lowered as a call, which a kernel that every
    # process traces pays for in set-up (all operands are non-negative,
    # so ``lax.div`` and ``lax.rem`` are the floor's).
    tile, round_, banks = (np.int32(n) for n in (TILE, ROUND, BANKS))

    def kernel(bounds, uniq, sums, _, tab, buf, landed, written, lead, lo):
        start, end = bounds[0], bounds[1]

        def note(j, carry):
            """Slot ``j`` into the list of tiles: ``lead[k]`` the first
            row id of tile ``k``, ``lo[k]`` its slot; no branch."""
            tiles, last, slot, row = carry
            of = lax.div(uniq[j], tile)
            first = of != last
            tiles = tiles + lax.convert_element_type(first, jnp.int32)
            slot, row = lax.select(first, j, slot), lax.select(
                first, uniq[j], row)
            lead[tiles], lo[tiles] = row, slot
            return tiles, of, slot, row

        def note_some(c, carry):    # LISTED slots in a straight line
            return lax.fori_loop(
                0, LISTED, lambda j, carry: note(start + c * LISTED + j,
                                                 carry), carry, unroll=True)

        lines = lax.div(end - start, np.int32(LISTED))
        carry = lax.fori_loop(0, lines, note_some,
                              (np.int32(-1), np.int32(-1), start, start))
        tiles = lax.fori_loop(start + lines * LISTED, end, note,
                              carry)[0] + 1
        lo[tiles] = end

        def count(r):       # tiles of round r: ROUND, fewer, none
            return lax.select(r >= 0, lax.clamp(
                np.int32(0), tiles - r * ROUND, round_), np.int32(0))

        def bank(r):        # the first buffer of round r's bank
            return lax.rem(r, banks) * ROUND

        def tile_of(row):
            return tab.at[pl.ds(pl.multiple_of(
                row - lax.rem(row, tile), TILE), TILE)]

        def read(row, b):   # the copies are made again to be waited for
            return pltpu.make_async_copy(tile_of(row), buf.at[b],
                                         landed.at[b])

        def write(row, b):
            return pltpu.make_async_copy(buf.at[b], tile_of(row),
                                         written.at[b])

        def add(j, b):
            line = pl.ds(lax.rem(uniq[j], tile), 1)
            buf[b, line, :] = buf[b, line, :] + sums[pl.ds(j, 1), :]

        def add_from(skip, k, b):   # tile k's rows from its skip-th on
            lax.fori_loop(lo[k] + skip, lo[k + 1],
                          lambda j, _: add(j, b), None)

        def each(n, fn):    # fn(0) .. fn(n - 1), n the data's
            lax.fori_loop(0, n, lambda s, _: fn(s), None)

        def a_round(i, _):
            b, k, on = bank(i), i * ROUND, i + ROUNDS_AHEAD
            b_on, k_on = bank(on), on * ROUND
            straight = (on + 1) * ROUND <= tiles

            @pl.when(straight)
            def _():
                # traced once and unrolled as it is lowered
                def line(fn):
                    lax.fori_loop(0, ROUND, lambda s, _: fn(s), None,
                                  unroll=True)
                line(lambda s: read(0, b + s).wait())
                line(lambda s: add(lo[k + s], b + s))

                @pl.when(lo[k + ROUND] - lo[k] > ROUND)
                def _():
                    each(ROUND, lambda s: add_from(1, k + s, b + s))
                line(lambda s: write(lead[k + s], b + s).start())

                @pl.when(on >= BANKS)
                def _():
                    line(lambda s: write(0, b_on + s).wait())
                line(lambda s: read(lead[k_on + s], b_on + s).start())

            @pl.when(lax.bitwise_not(straight))
            def _():
                each(count(i), lambda s: read(0, b + s).wait())
                each(count(i), lambda s: add_from(0, k + s, b + s))
                each(count(i), lambda s: write(lead[k + s], b + s).start())
                reads(on)

        def reads(r):
            """Round ``r``'s reads, once the writes of the round that
            had its bank before have landed."""
            b, k = bank(r), r * ROUND
            each(count(r - BANKS), lambda s: write(0, b + s).wait())
            each(count(r), lambda s: read(lead[k + s], b + s).start())

        rounds = lax.div(tiles + (ROUND - 1), round_)
        for r in range(ROUNDS_AHEAD):
            reads(np.int32(r))
        lax.fori_loop(0, rounds, a_round, None)
        # round r's writes are waited for by round r + BANKS - ROUNDS_AHEAD
        # as it reads ahead: those of the last rounds by nobody yet
        for r in range(1, BANKS - ROUNDS_AHEAD + 1):
            each(count(rounds - r), lambda s, r=r: write(
                0, bank(rounds - r) + s).wait())

    call = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((rows, width), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((BANKS * ROUND, TILE, width), jnp.float32),
                        pltpu.SemaphoreType.DMA((BANKS * ROUND,)),
                        pltpu.SemaphoreType.DMA((BANKS * ROUND,)),
                        pltpu.SMEM((slots + 1,), jnp.int32),
                        pltpu.SMEM((slots + 1,), jnp.int32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            # the sums are held whole: 13 MB at 8,448 rows of 384
            vmem_limit_bytes=slots * width * 4 + (16 << 20)),
        name="row_walk_tiles", interpret=interpret)
    ints = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)    # noqa: E731
    return jax.make_jaxpr(call)(
        ints(2), ints(slots),
        jax.ShapeDtypeStruct((slots, width), jnp.float32),
        jax.ShapeDtypeStruct((rows, width), jnp.float32))


def add_rows(table: jax.Array, ids: jax.Array, updates: jax.Array,
             plan: Optional[RowPlan] = None, sharding=None) -> jax.Array:
    """``table.at[ids].add(updates)`` for ids ``[B]``, updates ``[B, D]``,
    with the duplicates summed first (float32) and every distinct row
    written once: the rows of the head by one dense add, the others by
    the walk. ``plan`` is :func:`plan_rows` of ``ids`` where the caller
    made it ahead (an epoch's, batched before its scan); else it is made
    here. ``sharding`` is the table's where its rows are sharded over a
    mesh (a traced table does not say). Every shard then writes the rows
    it owns and no others, in one ``shard_map``: its own part of the
    head's delta, and a walk of its own slots of the plan, so the trips
    differ by chip and nothing inside the loop waits for another chip.
    Left to slice a row-sharded table, the partitioner sends the head's
    rows round the chips and copies the table; left to scatter into it,
    every chip walks every slot and drops those it does not own."""
    rows, b = table.shape[0], updates.shape[0]
    axis, shards = row_shards(sharding)
    if plan is None:
        plan = plan_rows(ids, rows, shards)
    chunk = min(CHUNK, b)
    # a last chunk may run past the last run: more pad slots, as distinct
    sums = combine_rows(updates, plan, chunk).astype(table.dtype)
    uniq = jnp.concatenate(
        [plan.uniq, rows + b + jnp.arange(chunk, dtype=jnp.int32)])
    part = plan.head_run.shape[0] // shards      # head rows a shard

    def local(tab, shard, sums, uniq, head_run, head, end):
        """Shard ``shard``'s rows ``tab``: the head's add, then the walk
        from the first of its slots past the head, a chunk at a time. A
        last chunk that runs into the next shard's ids (or the pads)
        reads local ids past ``tab`` and drops them; none lies below."""
        # the head's delta: each row's run, and -0.0 where the minibatch
        # has none, which added to a row leaves its every bit
        tab = tab.at[:part].add(jnp.take(
            sums, lax.dynamic_slice_in_dim(head_run, shard * part, part),
            axis=0, mode="fill", fill_value=-0.0))
        start, first_row = head[shard], shard * tab.shape[0]
        interpret = tile_walk(tab, axis)
        if interpret is not None:
            return _walk_tiles(tab, uniq, sums, start, end[shard], interpret)

        def walk(i, tab):
            at = start + i * chunk
            return tab.at[
                lax.dynamic_slice(uniq, (at,), (chunk,)) - first_row].add(
                    lax.dynamic_slice(sums, (at, 0), (chunk, sums.shape[1])),
                    unique_indices=True, mode="drop")

        return lax.fori_loop(
            0, (end[shard] - start + chunk - 1) // chunk, walk, tab)

    plan_args = (sums, uniq, plan.head_run, plan.head, plan.end)
    if axis is None:
        return local(table, 0, *plan_args)
    spec = PartitionSpec(axis, None)
    return jax.shard_map(
        lambda tab, *args: local(tab, lax.axis_index(axis), *args),
        mesh=sharding.mesh, in_specs=(spec,) + (PartitionSpec(),) * 5,
        out_specs=spec)(table, *plan_args)
