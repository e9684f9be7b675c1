"""Scatter-add of a minibatch's update rows with its duplicates combined
first: the table sees each distinct row once.

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
  with distinct ids past the table's last row, their number, and how many
  of them lie below ``HEAD``. Leading axes are walked a minibatch at a
  time, so an epoch makes its plans before its scan, off the minibatch's
  path.
* :func:`combine_rows` — the float32 sum of each run, ``[B, D]``.
* :func:`add_rows` — the sums, then the table write over the distinct
  rows alone: one dense add for the table's first ``HEAD`` rows, and for
  the others the table scatter, a chunk of slots at a time from the first
  id past the head up to the last distinct row. Pad slots of the last
  chunk are dropped by the scatter (``mode="drop"``): they write nothing,
  so ``unique_indices`` is a true promise.

What the chip said about the promises (PERF.md, PR 28): ``unique_indices``
changes nothing in the v5e's scatter today, and ``indices_are_sorted``
selects a program that streams the whole table (11 times slower at 1.8M
rows), so the ids are sorted and only the first promise is made.

``HEAD`` (PERF.md, PR 31). Where ids are frequency ranks (the word2vec
dictionary numbers words by falling count) a minibatch's hot rows are the
first slots of its plan and the first rows of the table: on the
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

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
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


class RowPlan(NamedTuple):
    """How the update rows ``ids [..., B]`` of a table reach it combined."""
    run: jax.Array       # [..., B] int32: the run of each update row
    uniq: jax.Array      # [..., B] int32: run -> row id ascending, then pads
    count: jax.Array     # [...] int32: runs, i.e. distinct ids
    head: jax.Array      # [...] int32: runs below HEAD; the walk starts here
    head_run: jax.Array  # [..., HEAD] int32: head row -> its run, or _NONE


def plan_rows(ids: jax.Array, table_rows: int) -> RowPlan:
    """The plan for update-row ids ``[..., B]`` into a table of
    ``table_rows`` rows. ``uniq[..., u]`` is the id of run ``u``; slots
    past the last run hold ``table_rows + j`` for distinct ``j``: out of
    range, ascending, never equal, so a ``mode="drop"`` scatter skips them
    and the whole of ``uniq`` is sorted and unique. The head is the
    table's first ``min(HEAD, table_rows)`` rows: ``head`` counts the runs
    that lie in it, the first slots of ``uniq``, and ``head_run`` finds
    each of its rows' run."""
    if ids.ndim > 1:
        # a row at a time: a batched sort of [439, 8192] takes the v5e's
        # compiler 9.5 s and 7 ms to run, this loop 1.8 s and 14 ms
        lead = ids.shape[:-1]
        plan = lax.map(lambda row: plan_rows(row, table_rows),
                       ids.reshape(-1, ids.shape[-1]))
        return RowPlan(*(a.reshape(lead + a.shape[1:]) for a in plan))
    ids = ids.astype(jnp.int32)
    b = ids.shape[0]
    # three sorts and no gather: an element gather of an epoch's ids
    # takes a v5e five times what a sort of them does
    slots = jnp.arange(b, dtype=jnp.int32)
    srt, perm = lax.sort((ids, slots), num_keys=1)
    first = jnp.concatenate([jnp.ones(1, bool), srt[1:] != srt[:-1]])
    run_sorted = jnp.cumsum(first.astype(jnp.int32)) - 1
    # back to the ids' own order: update row perm[i] lies in run_sorted[i]
    _, run = lax.sort((perm, run_sorted), num_keys=1)
    uniq = jnp.sort(jnp.where(first, srt, table_rows + slots))
    rows = min(HEAD, table_rows)
    head_run = jnp.full(rows, _NONE, jnp.int32).at[uniq].set(slots,
                                                            mode="drop")
    return RowPlan(run, uniq, run_sorted[-1] + 1,
                   jnp.sum(uniq < rows, dtype=jnp.int32), head_run)


def combine_rows(updates: jax.Array, plan: RowPlan,
                 pad: int = 0) -> jax.Array:
    """``[B + pad, D]`` float32: row ``u`` is the sum of the updates of
    run ``u``, rows past the last run are zero. Whatever the updates'
    type, the sum is taken in float32."""
    return jax.ops.segment_sum(updates.astype(jnp.float32), plan.run,
                               num_segments=updates.shape[0] + pad)


def _add_head(table: jax.Array, delta: jax.Array, sharding) -> jax.Array:
    """``table[:len(delta)] += delta``, one contiguous read-modify-write
    in place. Where the table's rows are sharded over a mesh axis
    (``sharding`` says so) every shard adds its own part of ``delta`` to
    its own rows: left to slice a row-sharded table, the partitioner
    sends the head's rows round the chips and copies the table."""
    head = delta.shape[0]
    axis = (sharding.spec[0] if isinstance(sharding, NamedSharding)
            and len(sharding.spec) else None)
    if axis is None or not head:
        return table.at[:head].add(delta)
    per = table.shape[0] // sharding.mesh.shape[axis]      # rows a shard
    part, holders = min(head, per), -(-head // per)
    parts = jnp.pad(delta, ((0, holders * part - head), (0, 0)),
                    constant_values=-0.0).reshape(holders, part, delta.shape[1])

    def local(tab, parts):
        shard = lax.axis_index(axis)
        mine = lax.dynamic_index_in_dim(
            parts, jnp.minimum(shard, holders - 1), keepdims=False)
        return tab.at[:part].add(jnp.where(shard < holders, mine, -0.0))

    rows = PartitionSpec(axis, None)
    return jax.shard_map(local, mesh=sharding.mesh,
                         in_specs=(rows, PartitionSpec()),
                         out_specs=rows)(table, parts)


def add_rows(table: jax.Array, ids: jax.Array, updates: jax.Array,
             plan: Optional[RowPlan] = None, sharding=None) -> jax.Array:
    """``table.at[ids].add(updates)`` for ids ``[B]``, updates ``[B, D]``,
    with the duplicates summed first (float32) and every distinct row
    written once: the rows below ``HEAD`` by one dense add, the others by
    the walk. ``plan`` is :func:`plan_rows` of ``ids`` where the caller
    made it ahead (an epoch's, batched before its scan); else it is made
    here. ``sharding`` is the table's where its rows are sharded over a
    mesh (a traced table does not say)."""
    rows, b = table.shape[0], updates.shape[0]
    if plan is None:
        plan = plan_rows(ids, rows)
    chunk = min(CHUNK, b)
    # a last chunk may run past the last run: more pad slots, as distinct
    sums = combine_rows(updates, plan, chunk).astype(table.dtype)
    uniq = jnp.concatenate(
        [plan.uniq, rows + b + jnp.arange(chunk, dtype=jnp.int32)])
    # the head's delta: each row's run, and -0.0 where the minibatch has
    # none, which added to a row leaves its every bit
    table = _add_head(table, jnp.take(sums, plan.head_run, axis=0,
                                      mode="fill", fill_value=-0.0),
                      sharding)

    def walk(i, tab):
        at = plan.head + i * chunk
        return tab.at[lax.dynamic_slice(uniq, (at,), (chunk,))].add(
            lax.dynamic_slice(sums, (at, 0), (chunk, sums.shape[1])),
            unique_indices=True, mode="drop")

    return lax.fori_loop(
        0, (plan.count - plan.head + chunk - 1) // chunk, walk, table)
