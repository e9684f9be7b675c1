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
  with distinct ids past the table's last row, and their number. Leading
  axes are walked a minibatch at a time, so an epoch makes its plans
  before its scan, off the minibatch's path.
* :func:`combine_rows` — the float32 sum of each run, ``[B, D]``.
* :func:`add_rows` — both, then the table scatter over the distinct rows
  alone, a chunk of slots at a time up to the last distinct row. Pad slots
  of the last chunk are dropped by the scatter (``mode="drop"``): they
  write nothing, so ``unique_indices`` is a true promise.

What the chip said about the promises (PERF.md, PR 28): ``unique_indices``
changes nothing in the v5e's scatter today, and ``indices_are_sorted``
selects a program that streams the whole table (11 times slower at 1.8M
rows), so the ids are sorted and only the first promise is made.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# Slots a table scatter takes at a time: the scatter's cost follows the
# slots it is handed, so the walk stops within one chunk of the last
# distinct row. Read on the chip for 5,343 distinct rows of 8,192 (PERF.md,
# PR 28): 0.67 / 0.62 / 0.59 / 0.54 / 0.57 / 0.61 ms a minibatch at chunks
# of 32 / 64 / 128 / 256 / 512 / 1,024 (one scatter of 8,192: 0.84).
CHUNK = 256


class RowPlan(NamedTuple):
    """How the update rows ``ids [..., B]`` of a table reach it combined."""
    run: jax.Array     # [..., B] int32: the run of each update row
    uniq: jax.Array    # [..., B] int32: run -> row id ascending, then pads
    count: jax.Array   # [...] int32: runs, i.e. distinct ids


def plan_rows(ids: jax.Array, table_rows: int) -> RowPlan:
    """The plan for update-row ids ``[..., B]`` into a table of
    ``table_rows`` rows. ``uniq[..., u]`` is the id of run ``u``; slots
    past the last run hold ``table_rows + j`` for distinct ``j``: out of
    range, ascending, never equal, so a ``mode="drop"`` scatter skips them
    and the whole of ``uniq`` is sorted and unique."""
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
    srt, perm = lax.sort((ids, jnp.arange(b, dtype=jnp.int32)), num_keys=1)
    first = jnp.concatenate([jnp.ones(1, bool), srt[1:] != srt[:-1]])
    run_sorted = jnp.cumsum(first.astype(jnp.int32)) - 1
    # back to the ids' own order: update row perm[i] lies in run_sorted[i]
    _, run = lax.sort((perm, run_sorted), num_keys=1)
    pad = table_rows + jnp.arange(b, dtype=jnp.int32)
    uniq = jnp.sort(jnp.where(first, srt, pad))
    return RowPlan(run, uniq, run_sorted[-1] + 1)


def combine_rows(updates: jax.Array, plan: RowPlan) -> jax.Array:
    """``[B, D]`` float32: row ``u`` is the sum of the updates of run
    ``u``, rows past the last run are zero. Whatever the updates' type,
    the sum is taken in float32."""
    return jax.ops.segment_sum(updates.astype(jnp.float32), plan.run,
                               num_segments=updates.shape[0])


def add_rows(table: jax.Array, ids: jax.Array, updates: jax.Array,
             plan: Optional[RowPlan] = None) -> jax.Array:
    """``table.at[ids].add(updates)`` for ids ``[B]``, updates ``[B, D]``,
    with the duplicates summed first (float32) and every distinct row
    written once. ``plan`` is :func:`plan_rows` of ``ids`` where the
    caller made it ahead (an epoch's, batched before its scan); else it
    is made here."""
    if plan is None:
        plan = plan_rows(ids, table.shape[0])
    sums = combine_rows(updates, plan).astype(table.dtype)
    uniq, b = plan.uniq, updates.shape[0]

    def scatter(tab, ids_, rows_):
        return tab.at[ids_].add(rows_, unique_indices=True, mode="drop")

    if b <= CHUNK:
        return scatter(table, uniq, sums)
    short = -b % CHUNK       # whole chunks: more pad slots, as distinct
    if short:
        uniq = jnp.concatenate(
            [uniq, table.shape[0] + b + jnp.arange(short, dtype=jnp.int32)])
        sums = jnp.pad(sums, ((0, short), (0, 0)))

    def chunk(i, tab):
        return scatter(
            tab, lax.dynamic_slice(uniq, (i * CHUNK,), (CHUNK,)),
            lax.dynamic_slice(sums, (i * CHUNK, 0), (CHUNK, sums.shape[1])))

    return lax.fori_loop(0, (plan.count + CHUNK - 1) // CHUNK, chunk, table)
