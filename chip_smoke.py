"""chip_smoke.py: prove that the system starts and computes correctly on
the TPU it is measured on. One process, no arguments, no network.

Eight stages, each driven through the entry points a user calls, each
checked by the repo's own means (a NumPy float32 statement of the updater
rule, a falling finite loss, ``jnp.take``,
``parallel.ring.reference_attention``):

  device  a TPU is present; versions and the compile-cache directory
  tables  sync plane: MatrixTable row adds/gets, ArrayTable add/get
  we      WordEmbedding: fused trainer, then the PS-block trainer
  rows    a row-sharded table read by its owners (ops/row_combine.take_rows)
          beside the partitioner's masked gather and all-reduce; a PS
          block's table writes, raw and through row_combine.add_rows
  ps      uncoordinated plane: a two-rank world with device-backed shards
  lm      the 472M transformer step with the Pallas flash kernel
  flash   the language-model cells' flash kernel calls, a crossed pair as
          one tile against its sub-tiles: ms a call and compile seconds
  ssd     the chunked state-space scan at the hybrid cell's shapes: ms
          forward and backward of its two kernels, of the plain form and
          of the mixer's call, and every head against the recurrence
  taps    the mixers' short causal convolution at the delta and hybrid
          cells' shapes: ms forward and backward of the two kernels and of
          the plain form, and both against the convolution a position at
          a time
  delta   the chunked gated delta rule at the delta cell's shapes: ms
          forward and backward for each way of making its triangular
          inverse, and its error against the recurrence
  loop    the looped cell's stack alone: eight published-width blocks run
          four times with one set of weights as a scan, the same passes
          unrolled and one pass alone: trace, lowering and compile
          seconds, ms a call, and the error against the reference

and a closing ``memory`` check that every device ended up holding bytes.

Exit 0 only if every stage passed. Once a TPU was found, a ``summary``
line (stages, cache counters, wall time) is followed by the last stdout
line, which holds exactly ``{"ok": true|false, "device": {"platform",
"kind", "count"}}`` and no other key. Without a TPU it prints no result,
names the reason and exits non-zero before any stage runs: nothing here pins the CPU,
interprets a kernel or swaps one attention for another. The stage
functions take their sizes as arguments so tests/test_chip_smoke.py can
run them tiny on the CPU mesh (``chip=False`` drops the assertions only a
TPU can meet).

This is a health check, not a benchmark: its wall times are set-up costs
(compilation included) and are not device metrics.
"""

from __future__ import annotations

import contextlib
import functools
import json
import re
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple
from unittest import mock

import numpy as np

# ---------------------------------------------------------------------- #
# tolerances (every comparison in this file uses one of these)
# ---------------------------------------------------------------------- #
# f32 updater rules vs NumPy f32: the chip's divide/sqrt differ from
# NumPy's in the last bits, and three chained adagrad steps compound that
TABLE_RTOL, TABLE_ATOL = 1e-4, 1e-5
# bf16 attention vs an f32 reference of the same bf16 inputs, as max|err|
# over max|reference|: bf16 keeps 8 mantissa bits (2^-8 = 4e-3 per
# rounding) and the kernel rounds p, ds and the output
ATTN_BF16_TOL = 2e-2
SEED = 7

# not 2 or 3: the chip tool uses those for calls it refused or lost
EXIT_NO_CHIP = 4
EXIT_STAGE_FAILED = 1


class NoChip(RuntimeError):
    """JAX found no TPU: the smoke has nothing to say."""


def _say(stage: str, **facts: Any) -> None:
    print(json.dumps({"stage": stage, **facts}, default=str), flush=True)


def _close(got: np.ndarray, want: np.ndarray, what: str) -> float:
    """Assert table parity at TABLE_RTOL/TABLE_ATOL; return max |err|."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{what}: non-finite values")
    np.testing.assert_allclose(got, want, rtol=TABLE_RTOL, atol=TABLE_ATOL,
                               err_msg=what)
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def _on_all_devices(arr, what: str) -> List[int]:
    """Assert ``arr`` is sharded over every device; return their ids."""
    import jax
    ids = sorted(d.id for d in arr.sharding.device_set)
    if len(ids) != jax.device_count():
        raise AssertionError(
            f"{what} lives on devices {ids}, not all {jax.device_count()}")
    return ids


# ---------------------------------------------------------------------- #
# NumPy float32 statements of the updater rules (updaters/__init__.py)
# ---------------------------------------------------------------------- #
def _np_dedupe(ids: np.ndarray, vals: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Duplicate ids in one call sum their deltas (float64 accumulate, one
    cast) before the updater sees them — the rule of both table planes."""
    uids, inv = np.unique(ids, return_inverse=True)
    acc = np.zeros((uids.size, vals.shape[1]), np.float64)
    np.add.at(acc, inv, vals.astype(np.float64))
    return uids, acc.astype(np.float32)


def _np_adagrad_rows(data, g_sqr, ids, vals, lr: float, rho: float,
                     eps: float = 1e-10) -> None:
    """adagrad: G += d^2 / lr^2 ; data -= d * rho / (sqrt(G) + eps)."""
    uids, d = _np_dedupe(ids, vals)
    lr, rho = np.float32(lr), np.float32(rho)
    g_sqr[uids] += np.square(d) / np.square(lr)
    data[uids] -= d * rho / (np.sqrt(g_sqr[uids]) + np.float32(eps))


def _np_default_rows(data, ids, vals) -> None:
    """default: data += delta."""
    uids, d = _np_dedupe(ids, vals)
    data[uids] += d


# ---------------------------------------------------------------------- #
# stages
# ---------------------------------------------------------------------- #
def stage_device() -> Dict[str, Any]:
    """A TPU is there; say what it is and where compiled programs go."""
    from importlib import metadata

    import jax
    import jaxlib

    from multiverso_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX could not initialise a backend: {e}") from e
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"jax.devices()[0].platform is {platform!r}, not 'tpu' "
                     f"({len(devices)} {platform} device(s) found)")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "compile_cache_dir": cache_dir}


def stage_tables(rows: int = 100_000, cols: int = 128, batch: int = 4096,
                 array_size: int = 1_000_000) -> Dict[str, Any]:
    """Sync plane on the default mesh: the matrix_sparse_row_add shape
    (three duplicate-carrying adagrad row batches, then a row get) and a
    whole-table ArrayTable add / add_async+wait / get, against NumPy."""
    import multiverso_tpu as mv
    from multiverso_tpu.updaters import AddOption

    mv.init()
    rng = np.random.default_rng(SEED)
    lr, rho = 0.05, 0.1
    opt = AddOption(learning_rate=lr, rho=rho)

    mt = mv.MatrixTable(rows, cols, updater="adagrad", name="smoke_rows")
    matrix_devices = _on_all_devices(mt.raw(), "MatrixTable")
    want = np.zeros((rows, cols), np.float32)
    g_sqr = np.zeros((rows, cols), np.float32)
    touched = []
    for _ in range(3):
        # draws from half the id range: duplicates inside each batch and
        # rows revisited across batches (the g² history must carry over)
        ids = rng.integers(0, max(rows // 2, 1), batch).astype(np.int32)
        vals = rng.normal(size=(batch, cols)).astype(np.float32)
        mt.add_rows(ids, vals, opt)
        _np_adagrad_rows(want, g_sqr, ids, vals, lr, rho)
        touched.append(ids)
    probe = np.concatenate(touched + [np.arange(rows - 8, rows)])
    rows_err = _close(mt.get_rows(probe), want[probe], "MatrixTable rows")
    duplicates = int(sum(t.size - np.unique(t).size for t in touched))

    at = mv.ArrayTable(array_size, updater="default", name="smoke_array")
    array_devices = _on_all_devices(at.raw(), "ArrayTable")
    d1 = rng.normal(size=array_size).astype(np.float32)
    d2 = rng.normal(size=array_size).astype(np.float32)
    at.add(d1)
    at.wait(at.add_async(d2))
    array_err = _close(at.get(), d1 + d2, "ArrayTable")
    return {"matrix": f"{rows}x{cols} adagrad", "batch": batch,
            "duplicate_ids": duplicates, "rows_max_abs_err": rows_err,
            "array": array_size, "array_max_abs_err": array_err,
            "matrix_devices": matrix_devices,
            "array_devices": array_devices}


def _falls(losses: List[float], what: str) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")


def _finite_table(table, what: str) -> None:
    if not np.all(np.isfinite(table.get())):
        raise AssertionError(f"{what} holds NaN/Inf after training")


def stage_we(fused_tokens: int = 400_000, fused_vocab: int = 10_000,
             fused_batch: int = 16384, shared_negatives: int = 256,
             ps_tokens: int = 120_000, ps_vocab: int = 5_000,
             ps_batch: int = 8192, ps_block: int = 50_000,
             dim: int = 128) -> Dict[str, Any]:
    """The flagship trainer through its normal entry, at the bench
    configuration: three fused epochs, then two passes of the PS-block
    trainer (pull -> train -> push per block) on the device plane."""
    import jax.numpy as jnp

    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary

    tokens = synthetic_corpus(fused_tokens, vocab=fused_vocab, seed=SEED)
    cfg = WEConfig(size=dim, min_count=5, batch_size=fused_batch, negative=5,
                   window=5, epoch=1, shared_negatives=shared_negatives)
    we = WordEmbedding(cfg, Dictionary.build(tokens, cfg.min_count))
    ids = we.prepare_ids(tokens)
    fused = [we.train_fused(ids, epochs=1)["loss"] for _ in range(3)]
    _falls(fused, "train_fused")
    for t in (we.table_in, we.table_out):
        _finite_table(t, f"fused {t.name}")
    fused_devices = _on_all_devices(we.table_in.raw(), "fused embed_in")

    tokens = synthetic_corpus(ps_tokens, vocab=ps_vocab, seed=11)
    cfg = WEConfig(size=dim, min_count=5, batch_size=ps_batch, negative=5,
                   window=5, epoch=1, data_block_size=ps_block, use_ps="1")
    wps = WordEmbedding(cfg, Dictionary.build(tokens, cfg.min_count))
    ids_ps = wps.prepare_ids(tokens)
    blocks = -(-ids_ps.size // ps_block)
    if blocks < 2:
        raise AssertionError(f"PS-block run has {blocks} block(s); the "
                             "smoke needs at least two")
    if not wps._use_device_plane(wps._ps_topology()[0]):
        raise AssertionError("PS-block trainer did not take the device "
                             "plane on a single worker")
    ps = [wps.train_ps_blocks(ids_ps, epochs=1)["loss"] for _ in range(2)]
    _falls(ps, "train_ps_blocks")
    for t in (wps.table_in, wps.table_out):
        _finite_table(t, f"ps-block {t.name}")
    return {"fused_loss": [round(x, 4) for x in fused],
            "fused_compute_dtype": jnp.dtype(we.fused_compute_dtype).name,
            "fused_tokens": int(ids.size), "vocab": len(we.dict),
            "fused_devices": fused_devices,
            "ps_block_loss": [round(x, 4) for x in ps],
            "ps_blocks_per_pass": blocks, "ps_device_plane": True,
            "ps_tokens": int(ids_ps.size)}


def stage_rows(rows_per_shard: int = 3_000_001, width: int = 300,
               batch: int = 8192, calls: int = 32,
               block: Dict[str, int] = None) -> Dict[str, Any]:
    """The sharded table path's read (``ops/row_combine.take_rows``) on a
    table row-sharded over every device, at ``we-fused-x4``'s shapes: the
    rows a minibatch's ids name, read by the shards that own them and
    handed round by an all-gather, held bit for bit to what the
    partitioner makes of ``jnp.take`` (a masked gather of every slot on
    every device and an all-reduce), for ids dealt evenly and for ids that
    all lie in the last shard (more rounds than one), and for ids without
    a duplicate (the round's size counts on a minibatch's duplicates:
    ``row_combine.gather_cap``; these take two rounds). Then ``calls``
    minibatches of each in one program: the ms a call, by this process's
    clock around programs it waits for, compilation apart. The read's
    temporaries are held to the partitioner's: left alone, the v5e's
    compiler casts the whole shard ahead of the later rounds' loop, half
    a shard of temporaries (PERF.md, PR 38). On one device the two are
    one program. Then :func:`block_writes` (``block``: its sizes)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format
    from jax.sharding import NamedSharding, PartitionSpec as P

    import multiverso_tpu as mv
    from multiverso_tpu.ops import row_combine
    from multiverso_tpu.table import row_program_layout

    mv.init()
    mesh = mv.mesh()
    axis = mesh.axis_names[-1]
    shards = mesh.shape[axis]
    rows = shards * rows_per_shard
    sharding = NamedSharding(mesh, P(axis, None))
    # laid out as a table's own row programs hold it
    fmt = Format(row_program_layout((rows, width), jnp.float32, sharding),
                 sharding)
    table = jax.jit(
        lambda: jax.random.uniform(jax.random.key(SEED), (rows, width),
                                   jnp.float32, -0.5, 0.5),
        out_shardings=fmt)()
    rng = np.random.default_rng(SEED)
    # frequency ranks of a Zipf law, dealt round the shards as the app's
    words = (rng.zipf(1.1, (calls, batch)) - 1) % (rows - shards)
    ids = {"even": row_combine.striped_row(words, shards, rows_per_shard),
           "last_shard": rows - 1 - words % rows_per_shard,
           "distinct": np.stack([rng.choice(rows, batch, replace=False)
                                 for _ in range(calls)])}
    cd = jnp.bfloat16
    plan = jax.jit(row_combine.plan_rows, static_argnums=(1, 2),
                   out_shardings=NamedSharding(mesh, P()))

    def epoch(read):
        """``calls`` minibatches of ``read`` in one program."""
        return jax.jit(
            lambda tab, ids, plans: jax.lax.scan(
                lambda _, x: (None, read(tab, *x)), None, (ids, plans))[1],
            in_shardings=(fmt, None, None))

    programs = {
        "take_rows": epoch(lambda tab, i, p: row_combine.take_rows(
            tab, i, p, sharding, cd)),
        "partitioner": epoch(
            lambda tab, i, p: jnp.take(tab, i, axis=0).astype(cd))}
    out: Dict[str, Any] = {
        "shards": shards, "table": f"f32[{rows},{width}]", "batch": batch,
        "calls": calls, "cap": row_combine.gather_cap(batch, shards)}
    shard_bytes = rows_per_shard * width * 4
    for kind, rows_of in ids.items():
        i = jnp.asarray(rows_of.astype(np.int32))
        plans = plan(i, rows, shards)
        got, temp = {}, {}
        for name, program in programs.items():
            compiled = program.lower(table, i, plans).compile()
            temp[name] = compiled.memory_analysis().temp_size_in_bytes
            got[name] = np.asarray(compiled(table, i, plans))
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(table, i, plans))
                best = min(best, time.perf_counter() - t0)
            out[f"{kind}_{name}_ms"] = round(best / calls * 1e3, 4)
        if not np.array_equal(got["take_rows"].view(np.uint16),
                              got["partitioner"].view(np.uint16)):
            raise AssertionError(f"take_rows differs from jnp.take on "
                                 f"{kind} ids over {shards} shards")
        if not got["take_rows"].any():
            raise AssertionError(f"take_rows read zeros ({kind})")
        # an eighth of a shard of room (a MiB for a toy table, whose
        # plans outweigh it): a shard cast whole is half a shard
        if temp["take_rows"] > temp["partitioner"] + max(shard_bytes // 8,
                                                         1 << 20):
            raise AssertionError(
                f"take_rows holds {temp['take_rows']} B of temporaries on "
                f"{kind} ids, the partitioner's gather {temp['partitioner']}"
                f", a shard {shard_bytes}")
        out[f"{kind}_rounds_past_first"] = int(
            np.asarray(row_combine.plan_counts(plans))[-1])
    out["take_rows_temp_mb"] = round(temp["take_rows"] / 1e6, 1)
    out["partitioner_temp_mb"] = round(temp["partitioner"] / 1e6, 1)
    if shards > 1 and not (out["last_shard_rounds_past_first"] > 0
                           < out["distinct_rounds_past_first"]):
        raise AssertionError(f"ids of one shard, or ids without a "
                             f"duplicate, took one round: {out}")
    table.delete()
    out["block"] = block_writes(width=width, batch=batch, **(block or {}))
    return out


def block_writes(bucket: int = 2 ** 19, width: int = 300, batch: int = 8192,
                 negative: int = 5, minibatches: int = 16,
                 vocab: int = 1_800_000,
                 walk_rows: Tuple[int, ...] = (1000, 4500, 8192)
                 ) -> Dict[str, Any]:
    """What a PS block's scan pays for its table writes
    (``models/word2vec.skipgram_ns_step`` under ``we-psblock``, PERF.md
    PR 40), into the block's local table ``f32[bucket + 1, width]`` on one
    device: ``minibatches`` minibatches in one program, ms a minibatch by
    this process's clock around a program it waits for, and the seconds
    the compiler took (cold only where the persistent cache had no entry).
    Ids as a block has them: words are ranks of a Zipf(1.1) law, a pair's
    negatives follow its 0.75 power, and a word's local row is its rank
    among the block's words. Three writes of the output table's
    ``(negative + 1) * batch`` update rows: the raw duplicate scatter,
    and held to it ONE ``add_rows`` of them all and ``add_rows`` a column
    of ``batch`` at a time, which is what the step does; the input
    table's ``batch`` beside them; the plans and the sums
    (``combine_rows``) alone, so that what is left is the head's add and
    the walk. Then the walk's two prices (PERF.md, PR 45; ``walks``): into
    the bucket as the block's scan carries it, ``f32[bucket + 1, width in
    whole lanes]``, ``add_rows`` of ``batch`` update rows whose distinct
    rows are ``walk_rows`` of the block's rows past the head, one write a
    minibatch and six in a ``lax.scan`` as the step's columns are, by
    XLA's scatter and by the tile kernel where ``row_combine.tile_walk``
    takes it (on a TPU), held to each other bit for bit; us a row is a
    call's ms over a call's with every row in the head."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import word2vec as w2v
    from multiverso_tpu.ops import row_combine

    rng = np.random.default_rng(SEED + 2)
    rows, cols = bucket + 1, negative + 1
    law = np.arange(1, vocab + 1, dtype=np.float64) ** -1.1
    draw = lambda p, shape: np.searchsorted(           # noqa: E731
        np.cumsum(p / p.sum()), rng.random(shape))
    words = [draw(law, (minibatches, batch)) for _ in range(2)] + [
        draw(law ** 0.75, (minibatches, batch, negative))]
    _, local = np.unique(np.concatenate([w.reshape(-1) for w in words]),
                         return_inverse=True)
    # the rarest of a block of more words than the bucket share rows
    local = (local % bucket).astype(np.int32)
    cut = np.cumsum([w.size for w in words])[:-1]
    c, x, g = (jnp.asarray(a.reshape(w.shape)) for a, w in zip(
        np.split(local, cut), words))
    flat = jnp.concatenate([x[..., None], g], -1).reshape(minibatches, -1)
    by_col = w2v.target_columns(x, g)              # [minibatches, cols, B]
    table = jax.random.uniform(jax.random.key(SEED), (rows, width),
                               jnp.float32, -0.5, 0.5)
    v = jax.random.uniform(jax.random.key(SEED + 1),
                           (minibatches, batch, width), jnp.float32, -.5, .5)
    grad = jax.random.uniform(jax.random.key(SEED + 2),
                              (minibatches, batch, cols), jnp.float32,
                              -0.01, 0.01)
    out: Dict[str, Any] = {
        "table": f"f32[{rows},{width}]", "minibatches": minibatches,
        "update_rows": [batch, cols * batch]}

    def timed(name, fn, *args, donate=False, into=out):
        """Compile ``fn``, run it three times on a fresh copy of what it
        donates; the last result. Its ms a minibatch and compile seconds
        go ``into`` the stage's facts."""
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(0,) if donate else ()).lower(
            *args).compile()
        into[f"{name}_compile_s"] = round(time.perf_counter() - t0, 2)
        best, got = float("inf"), None
        for _ in range(3):
            first = jnp.copy(args[0]) if donate else args[0]
            jax.block_until_ready(first)
            t0 = time.perf_counter()
            got = jax.block_until_ready(compiled(first, *args[1:]))
            best = min(best, time.perf_counter() - t0)
        into[f"{name}_ms"] = round(best / minibatches * 1e3, 4)
        return got

    def du(gm, vm):                     # [B, cols, width], as the step's
        return gm[..., None] * vm[:, None, :]

    def scanned(write):
        return lambda tab, *xs: jax.lax.scan(
            lambda tb, x: (write(tb, *x), None), tab, xs)[0]

    def by_column(tb, i, gm, vm, p):    # as skipgram_ns_step writes them
        return jax.lax.scan(
            lambda t, col: (row_combine.add_rows(t, *col), None), tb,
            (i, jnp.moveaxis(du(gm, vm), 1, 0), p))[0]

    plan = lambda i: row_combine.plan_rows(i, rows)      # noqa: E731
    plans = {"centers": timed("plan_centers", plan, c),
             "one_write": timed("plan_one_write", plan, flat),
             "columns": timed("plan_columns", plan, by_col)}
    for name, p in plans.items():
        unique, head, walk, _ = np.asarray(
            row_combine.plan_counts(p)).tolist()
        out[f"{name}_rows"] = {"unique": unique, "head": head, "walk": walk}
    flat_du = lambda gm, vm: du(gm, vm).reshape(-1, width)   # noqa: E731
    got = {
        "raw": timed("raw", scanned(
            lambda tb, i, gm, vm: tb.at[i].add(flat_du(gm, vm))),
            table, flat, grad, v, donate=True),
        "one_write": timed("one_write", scanned(
            lambda tb, i, gm, vm, p: row_combine.add_rows(
                tb, i, flat_du(gm, vm), p)),
            table, flat, grad, v, plans["one_write"], donate=True),
        "columns": timed("columns", scanned(by_column), table, by_col,
                         grad, v, plans["columns"], donate=True)}
    want = np.asarray(got.pop("raw"))
    if not np.abs(want - np.asarray(table)).max() > 0:
        raise AssertionError("the raw scatter wrote nothing")
    for name, tab in got.items():
        out[f"{name}_max_abs_err"] = _close(np.asarray(tab), want, name)
    timed("raw_centers", scanned(lambda tb, i, vm: tb.at[i].add(vm)),
          table, c, v, donate=True)
    timed("centers", scanned(row_combine.add_rows), table, c, v,
          plans["centers"], donate=True)

    def sums(updates):
        """``combine_rows`` of every minibatch alone: a little of each
        buffer is kept so that none of it is dead code."""
        def body(acc, xs):
            s = row_combine.combine_rows(updates(*xs[:-1]), xs[-1],
                                         min(row_combine.CHUNK, batch))
            return acc + s[:8] + s[-8:], None
        return lambda *xs: jax.lax.scan(
            body, jnp.zeros((8, width), jnp.float32), xs)[0]

    timed("sums_one_write", sums(flat_du), grad, v, plans["one_write"])
    timed("sums_centers", sums(lambda vm: vm), v, plans["centers"])
    del table, v, grad, got, want

    # the walk alone, XLA's and the kernel's, on the scan's own bucket
    wide = row_combine.lane_wide(width)
    table = jax.random.uniform(jax.random.key(SEED), (rows, wide),
                               jnp.float32, -0.5, 0.5)
    updates = jax.random.uniform(jax.random.key(SEED + 3),
                                 (cols, batch, wide), jnp.float32, -.01, .01)
    past = np.unique(local[local >= row_combine.HEAD])
    kernel = row_combine.tile_walk(table)
    walks: Dict[str, Any] = {"table": f"f32[{rows},{wide}]",
                             "kernel": kernel is not None}
    for n in (0,) + tuple(min(n, batch, past.size) for n in walk_rows):
        named = [rng.choice(past, n, replace=False) if n else
                 rng.integers(0, min(row_combine.HEAD, rows), batch)
                 for _ in range(minibatches * cols)]
        ids = jnp.asarray(np.stack([
            rng.permutation(np.concatenate([d, rng.choice(d, batch - n)]))
            if n else d for d in named]).reshape(
                minibatches, cols, batch).astype(np.int32))
        p = jax.jit(plan)(ids)
        forms = {       # the updates an argument: closed over, 75 MB of
            # constant in the program and 7 s of compile
            "alone": lambda tb, i, p, u: jax.lax.scan(
                lambda t, x: (row_combine.add_rows(
                    t, x[0][0], u[0],
                    jax.tree.map(lambda a: a[0], x[1])), None),
                tb, (i, p))[0],
            "six": lambda tb, i, p, u: jax.lax.scan(
                lambda t, x: (jax.lax.scan(
                    lambda t, col: (row_combine.add_rows(t, *col), None),
                    t, (x[0], u, x[1]))[0], None), tb, (i, p))[0]}
        held: Dict[str, Any] = {}
        for form, fn in forms.items():
            # a function of its own a program: jit keeps a function's
            # trace, and with it the walk it was traced with
            with mock.patch.object(row_combine, "_kernel_interpret",
                                   lambda: None):
                want = timed(f"xla_{form}", lambda *a: fn(*a), table, ids,
                             p, updates, donate=True, into=held)
            if kernel is None:
                continue
            got = timed(f"kernel_{form}", lambda *a: fn(*a), table, ids, p,
                        updates, donate=True, into=held)
            if not jnp.array_equal(
                    jax.lax.bitcast_convert_type(got, jnp.uint32),
                    jax.lax.bitcast_convert_type(want, jnp.uint32)):
                raise AssertionError(
                    f"the tile kernel's table differs from XLA's walk's "
                    f"({n} rows, {form})")
            del got
        for k in [k for k in held if k.endswith("six_ms")]:
            held[k] = round(held[k] / cols, 4)      # ms a call
        if n:
            for k in [k for k in held if k.endswith("_ms")]:
                held[k.replace("_ms", "_us_a_row")] = round(
                    (held[k] - walks["0"][k]) * 1e3 / n, 4)
        walks[str(n)] = held
    out["walks"] = walks
    return out


def stage_ps(rows: int = 100_000, cols: int = 128, batch: int = 4096,
             chip: bool = True) -> Dict[str, Any]:
    """Uncoordinated plane: a two-rank world inside this process (every
    cross-rank op crosses a localhost socket), one adagrad table and one
    stateless table, row adds spanning both owners, gets from both ranks,
    against NumPy. On a TPU the shards must be device-backed — the branch
    no CPU run reaches."""
    import jax

    from multiverso_tpu.ps.service import (FileRendezvous, PSContext,
                                           PSService)
    from multiverso_tpu.ps.tables import AsyncMatrixTable
    from multiverso_tpu.updaters import AddOption

    rng = np.random.default_rng(SEED + 1)
    lr, rho = 0.05, 0.1
    opt = AddOption(learning_rate=lr, rho=rho)
    out: Dict[str, Any] = {}
    with tempfile.TemporaryDirectory(prefix="mv_smoke_rdv_") as rdv_dir:
        rdv = FileRendezvous(rdv_dir)
        ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
        try:
            for updater in ("adagrad", "default"):
                tables = [AsyncMatrixTable(rows, cols, updater=updater,
                                           name=f"smoke_ps_{updater}", ctx=c)
                          for c in ctxs]
                want = np.zeros((rows, cols), np.float32)
                g_sqr = np.zeros((rows, cols), np.float32)
                sent = []
                for t in tables:   # each rank pushes its own batch
                    ids = rng.integers(0, rows, batch).astype(np.int64)
                    vals = rng.normal(size=(batch, cols)).astype(np.float32)
                    owners = np.unique(ids // -(-rows // 2))
                    if owners.size != 2:
                        raise AssertionError("batch does not span both "
                                             f"owners: {owners}")
                    t.add_rows(ids, vals, opt)
                    if updater == "adagrad":
                        _np_adagrad_rows(want, g_sqr, ids, vals, lr, rho)
                    else:
                        _np_default_rows(want, ids, vals)
                    sent.append(ids)
                probe = np.concatenate(sent)
                err = max(_close(t.get_rows(probe), want[probe],
                                 f"ps[{updater}] get_rows from rank {r}")
                          for r, t in enumerate(tables))
                shards = [t._shard for t in tables]
                facts = {
                    "max_abs_err": err,
                    "host_serve": [s._host_serve for s in shards],
                    "np_mode": [s._np_mode for s in shards],
                    # a numpy-mode shard (CPU only) has no devices
                    "shard_devices": [
                        sorted(f"{d.platform}:{d.id}"
                               for d in s._data.devices())
                        if isinstance(s._data, jax.Array) else []
                        for s in shards],
                    "local_sharding": [s._local_sharding is not None
                                       for s in shards],
                    "natively_served_shard": [s._native_ref is not None
                                              for s in shards],
                }
                if chip:
                    if any(facts["host_serve"]) or any(facts["np_mode"]):
                        raise AssertionError(
                            f"ps[{updater}] shard is host-served on a "
                            f"TPU: {facts}")
                    for s in shards:
                        for leaf in jax.tree.leaves((s._data, s._ustate)):
                            kinds = {d.platform for d in leaf.devices()}
                            if kinds != {"tpu"}:
                                raise AssertionError(
                                    f"ps[{updater}] buffer on {kinds}")
                    if jax.device_count() > 1 and not all(
                            facts["local_sharding"]):
                        raise AssertionError(
                            f"ps[{updater}] shard of "
                            f"{rows // 2 * cols * 4 / 1e6:.1f} MB is not "
                            "sharded over the local devices")
                out[updater] = facts
            # the wire: C++ connection threads when libmv_ps built (they
            # punt device-backed shards' ops to the Python handler), else
            # the Python plane end to end
            out["wire_plane"] = ("native" if all(
                c.service._native is not None for c in ctxs) else "python")
        finally:
            for c in ctxs:
                c.close()
    return out


def _attention_errors(shape: Tuple[int, int, int, int], block,
                      seed: int) -> Dict[str, float]:
    """flash_attention forward and gradients vs reference_attention run in
    f32 on the same bf16 inputs: max|err| / max|reference| per tensor.
    ``block`` is the q and k block, or the pair of them."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops.attention_kernels import flash_attention
    from multiverso_tpu.parallel.ring import reference_attention

    blocks = block if isinstance(block, tuple) else (block, block)
    rng = np.random.default_rng(seed)
    q, k, v, g = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                  for _ in range(4))

    def fwd_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(g.astype(out.dtype))

    got = jax.jit(lambda q, k, v: fwd_and_grads(
        lambda q, k, v: flash_attention(q, k, v, True, *blocks),
        q, k, v))(q, k, v)
    f32 = lambda t: t.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v: fwd_and_grads(
            lambda q, k, v: reference_attention(q, k, v, causal=True),
            f32(q), f32(k), f32(v)))(q, k, v)
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if not np.all(np.isfinite(a)):
            raise AssertionError(f"flash {name} {shape}/{block}: non-finite")
        errs[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        if errs[name] > ATTN_BF16_TOL:
            raise AssertionError(
                f"flash {name} {shape} block {block}: relative error "
                f"{errs[name]:.4f} > {ATTN_BF16_TOL}")
    return errs


# (name, dim, expert width, experts held, buffer rows, form, experts routed
# over, experts a token, the model's module): ONE expert layer of each
# language-model cell as ``models/mla_moe.held`` sizes it (the buffer twice
# the even load)
EXPERT_CALLS = (
    ("glm47f-train-8k", 2048, 1536, 8, 16384, "gated_silu", 64, 4, "mla_moe"),
    ("mellum2-train-8k", 2304, 896, 16, 65536, "gated_silu", 64, 8,
     "gqa_moe"),
    ("trinity-train-16k", 2048, 1024, 16, 32768, "gated_silu", 128, 8,
     "afmoe"),
    ("nemotron3n-train-16k", 2688, 1856, 8, 12288, "relu2", 128, 6,
     "nemotron_h"),
    ("lfm2-train-8k", 2048, 1792, 8, 32768, "gated_silu", 32, 4, "lfm2_moe"),
)


def grouped_kernels(compiled_text: str) -> Dict[str, int]:
    """The grouped products' kernels in a compiled TPU program's text, by
    the ``megablox`` function that called them (``gmm``: a forward
    product or the buffer's gradient; ``tgmm``: a matrix's gradient)."""
    calls = [line for line in compiled_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return {name: sum(f"jit({name})" in c for c in calls)
            for name in ("gmm", "tgmm")}


def buffer_loops(compiled_text: str) -> Dict[str, int]:
    """The ``while`` loops of a compiled program's text under the scopes
    of the sorted buffer's two passes (``parallel/moe._walk``: a pass
    that walks the rows past an even load in chunks is a loop, one over
    the whole buffer is none). Forward, made again and backward: 3 of
    the dispatch and 2 of the combine in a rematerialised block, whose
    second forward pass needs no sums; 3 and 3 where a norm reads them
    (``cfg.post_norms``)."""
    loops = [line for line in compiled_text.splitlines()
             if " while(" in line]
    return {name: sum(f"mv.lm.moe.{name}" in line for line in loops)
            for name in ("dispatch", "combine")}


def _expert_block(dim: int, ffn: int, held: int, rows: int, form: str,
                  experts: int, top_k: int, model: str, kernel: str,
                  repeats: int) -> Dict[str, Any]:
    """ONE expert block (norm, route, sort, gather, the grouped products,
    combine; no attention, no shared expert) of the configuration class
    in ``models/<model>.py``, so with the cell's own route and with what
    its blocks keep (``mla_moe.kept_names``), forward and backward under
    ``models/mla_moe._run_block`` as a training step rematerialises it,
    over the tokens whose even load half fills a buffer of ``rows``: the
    ms a call (``block_ms``) and the grouped kernels of the compiled
    program by the function that called them (``block_kernels``: ``gmm``
    forward and for the buffer's gradient, ``tgmm`` for a matrix's; 7 and
    3 where the block keeps the results of its products into the experts'
    width and makes the one out of it again, 9 and 3 where the backward
    pass makes them all again; 6 and 2 in ``NemotronHConfig``'s ``relu2``
    block, which keeps nothing, 5 and 2 if it did; none off the chip,
    where no kernel is compiled), and the loops of the sorted buffer's
    passes (``block_loops``: :func:`buffer_loops`; 0 would say a pass
    walks the whole buffer again). ``parallel/moe.CHUNK`` was chosen
    from ``block_ms`` here, and the STEP is the judge all the same: the
    block alone misled once (PR 51: a select that cost nothing here cost
    the step 2 ms a layer, where XLA fused and placed it otherwise)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import (afmoe, gqa_moe, lfm2_moe, mla_moe,
                                       nemotron_h)

    tokens = rows * experts // (2 * top_k * held)
    cfg = {"mla_moe": mla_moe.MLAMoEConfig, "gqa_moe": gqa_moe.GQAMoEConfig,
           "afmoe": afmoe.AFMoEConfig,
           "nemotron_h": nemotron_h.NemotronHConfig,
           "lfm2_moe": lfm2_moe.LFM2MoEConfig}[model](
        dim=dim, moe_ffn=ffn, n_experts=experts, experts_held=held,
        top_k=top_k, expert_kernel=kernel)
    here = mla_moe.held(cfg, tokens)
    if (here.buffer_rows, here.form) != (rows, form):
        raise AssertionError(f"{tokens} tokens of {model} make a buffer of "
                             f"{here.buffer_rows} rows of {here.form}")
    shapes = dict(mla_moe._ffn_shapes(cfg, "experts"), ffn_norm=(dim,),
                  **{"ffn_post_norm": (dim,)} if cfg.post_norms else {})
    keys = jax.random.split(jax.random.key(SEED), len(shapes) + 2)
    p = {n: 0.02 * jax.random.normal(k, s)
         for (n, s), k in zip(sorted(shapes.items()), keys)}
    x = jax.random.normal(keys[-2], (1, tokens, dim))
    weight = jax.random.normal(keys[-1], x.shape)
    layer = mla_moe.Layer("L0", None, "experts")
    bias = jnp.zeros((experts,))

    def loss(x, p):
        y, (_, overflow, _) = mla_moe._run_block(x, p, layer, bias, cfg)
        return jnp.sum(y * weight), overflow

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True)).lower(
        x, p).compile()
    (_, overflow), grads = jax.block_until_ready(compiled(x, p))
    if int(overflow) or not all(bool(jnp.all(jnp.isfinite(g)))
                                for g in jax.tree.leaves(grads)):
        raise AssertionError(f"expert block: {int(overflow)} rows past the "
                             "buffer, or a gradient that is not finite")
    t0 = time.perf_counter()
    for _ in range(repeats):
        res = compiled(x, p)
    jax.block_until_ready(res)
    ms = round((time.perf_counter() - t0) / repeats * 1e3, 3)
    text = compiled.as_text()
    return {"block_tokens": tokens, "block_ms": ms,
            "block_kernels": grouped_kernels(text),
            "block_loops": buffer_loops(text)}


def _expert_products(dim: int, ffn: int, held: int, rows: int, form: str,
                     experts: int, top_k: int, model: str,
                     kernel: str = "pallas", repeats: int = 5
                     ) -> Dict[str, Any]:
    """``parallel/moe.expert_products`` over a buffer half full at an even
    load, forward and with the gradient of the buffer and of every
    matrix, at the tile ``moe.product_tile`` gives the widths: the ms a
    call with the padding in the last expert's group, as the layer had it
    before PR 48 (``experts_ms_padded``), and with it in no group
    (``experts_ms``); ONE program, the groups are data. Beside them the
    row tiles a forward product visits either way, and how far the live
    rows' results and the gradients of the two lie apart (0: the same
    tiles do the same work). Then the whole block they stand in, forward
    and backward as a step runs it (:func:`_expert_block`)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.parallel import moe

    cfg = moe.HeldExperts(num_experts=held, experts_held=held, form=form,
                          tile=moe.product_tile(dim, ffn), buffer_rows=rows)
    k = jax.random.split(jax.random.key(SEED), 5)
    each = rows // 2 // held
    live = (jnp.arange(rows) < each * held)[:, None]
    x = jnp.where(live, jax.random.normal(k[0], (rows, dim)), 0).astype(
        cfg.dtype)
    weight = jax.random.normal(k[1], (rows, dim))
    params = {"w_up": 0.02 * jax.random.normal(k[2], (held, dim, ffn)),
              "w_down": 0.02 * jax.random.normal(k[3], (held, ffn, dim))}
    if form == "gated_silu":
        params["w_gate"] = 0.02 * jax.random.normal(k[4], (held, dim, ffn))

    def both(x, params, groups):
        def loss(x, params):
            y = moe.expert_products(x, params, groups, cfg, kernel)
            y = jnp.where(live, y.astype(jnp.float32), 0.0)
            return jnp.sum(y * weight), y
        (_, y), (dx, dp) = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            x, params)
        return y, jnp.where(live, dx.astype(jnp.float32), 0.0), dp

    ours = jnp.full((held,), each, jnp.int32)
    padded = ours.at[-1].add(rows - each * held)
    compiled = jax.jit(both).lower(x, params, ours).compile()
    facts: Dict[str, Any] = {"tile": list(cfg.tile), "rows": rows,
                             "held_rows": each * held}
    results = {}
    for name, groups in (("experts_ms_padded", padded), ("experts_ms", ours)):
        results[name] = jax.block_until_ready(compiled(x, params, groups))
        t0 = time.perf_counter()
        for _ in range(repeats):
            res = compiled(x, params, groups)
        jax.block_until_ready(res)
        facts[name] = round((time.perf_counter() - t0) / repeats * 1e3, 3)
        facts[name.replace("_ms", "_tiles")] = moe.product_tiles(
            np.asarray(groups), rows, cfg.tile[0])
    apart = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)))
                   / jnp.max(jnp.abs(b.astype(jnp.float32))))
             for a, b in zip(jax.tree.leaves(results["experts_ms"]),
                             jax.tree.leaves(results["experts_ms_padded"]))]
    facts["apart_from_padded"] = max(apart)
    if not max(apart) <= 1e-2:      # NaN too
        raise AssertionError(f"grouped products without the padding lie "
                             f"{apart} from those with it")
    facts.update(_expert_block(dim, ffn, held, rows, form, experts, top_k,
                               model, kernel, repeats))
    return facts


# a held expert's rows a step of ``nemotron3n-train-16k`` as its router
# leaves them (an even 768 and a few dozen rows either way: the cell's
# ``moe.load_max_over_mean.lm`` is 1.03), so that no group starts on a row
# tile's edge: at an exactly even load 768 rows are three whole tiles of
# 256 and two of 384, which no step sees
UNEVEN_ROWS = (781, 746, 773, 759, 785, 765, 757, 778)


def product_kernels(dim: int = 2688, ffn: int = 1856, rows: int = 12288,
                    sizes: Tuple[int, ...] = UNEVEN_ROWS,
                    tiles: Tuple[Tuple[int, int, int], ...] = (),
                    kinds: Tuple[str, ...] = ("fwd", "dbuf", "dw"),
                    repeats: int = 10, chip: bool = True) -> Dict[str, Any]:
    """The SIX kernels of an expert layer's two products alone
    (``parallel/moe.grouped_matmul``'s forward, ``_gmm_bwd``'s two: into
    the experts' width ``up`` [rows, dim] x [G, dim, ffn], out of it
    ``down``), each at every tile of ``tiles`` (over rows, over ``dim``,
    over ``ffn``; the matrices' gradient ``dw`` at that very tile, not at
    ``moe.weights_tile``'s), operands handed in as arguments, groups of
    ``sizes`` rows in a buffer of ``rows``: the ms a call of the kernel
    itself by the device's trace (``<product>.<kind>``) and of the whole
    call by this process's clock (``host_ms``: with the groups' metadata,
    which XLA makes before every kernel, and whatever copies it places
    round a call that stands alone; a kernel inside a step reads nearer
    the first), ``None`` where the chip's compiler refuses the tile
    (scoped VMEM), and the row tiles a forward product visits.
    ``moe.product_tile``'s rule for a width that no multiple of 128
    divides was chosen here (PERF.md section 6, PR 67)."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from multiverso_tpu.parallel import moe

    held = len(sizes)
    k = jax.random.split(jax.random.key(SEED), 4)
    bf = jnp.bfloat16
    groups = jnp.asarray(sizes, jnp.int32)
    wide = {"dim": jax.random.normal(k[0], (rows, dim)).astype(bf),
            "ffn": jax.random.normal(k[1], (rows, ffn)).astype(bf)}
    weights = {"up": (0.02 * jax.random.normal(k[2], (held, dim, ffn))
                      ).astype(bf),
               "down": (0.02 * jax.random.normal(k[3], (held, ffn, dim))
                        ).astype(bf)}
    out: Dict[str, Any] = {}
    for tm, over_dim, over_ffn in tiles:
        facts: Dict[str, Any] = {"tiles_visited": moe.product_tiles(
            np.asarray(sizes), rows, tm)}
        ready, host = [], {}
        for name, lhs, g, tk, tn in (("up", "dim", "ffn", over_dim, over_ffn),
                                     ("down", "ffn", "dim", over_ffn,
                                      over_dim)):
            calls = {
                "fwd": (lambda a, w, n, t=(tm, tk, tn): moe._gmm(
                    a, w, n, bf, t), wide[lhs], weights[name]),
                "dbuf": (lambda c, w, n, t=(tm, tn, tk): moe._gmm(
                    c, w, n, bf, t, transpose_rhs=True), wide[g],
                    weights[name]),
                "dw": (lambda a, c, n, t=(tm, tk, tn): moe._tgmm(
                    a.swapaxes(0, 1), c, n, jnp.float32, t,
                    num_actual_groups=held), wide[lhs], wide[g]),
            }
            for kind in kinds:
                fn, first, second = calls[kind]
                args = (first, second, groups)
                try:
                    compiled = jax.jit(fn).lower(*args).compile()
                    jax.block_until_ready(compiled(*args))
                except Exception as e:      # Mosaic: scoped VMEM
                    facts[f"{name}.{kind}"] = None
                    facts.setdefault("refused", str(e)[-160:])
                    continue
                ready.append((f"{name}.{kind}", compiled, args))
        with tempfile.TemporaryDirectory() as trace_dir:
            with (jax.profiler.trace(trace_dir) if chip
                  else contextlib.nullcontext()):
                for key, compiled, args in ready:
                    t0 = time.perf_counter()
                    for _ in range(repeats):
                        res = compiled(*args)
                    jax.block_until_ready(res)
                    host[key] = (time.perf_counter() - t0) / repeats * 1e3
            # the kernels in the order they ran, ``repeats`` of each
            ops = next(iter(trace_reduce.read_xplane(trace_reduce.find_xplane(
                trace_dir))[0].values()), []) if chip and ready else []
        ran = sorted((o.start, o.dur) for o in ops
                     if o.name.split(".")[0] in ("gmm", "tgmm"))
        if len(ran) != repeats * len(ready):    # off the chip: no trace
            ran = []
        for i, (key, _, _) in enumerate(ready):
            facts[key] = (round(1e3 * sum(
                d for _, d in ran[i * repeats:(i + 1) * repeats]) / repeats,
                4) if ran else None)
        whole = len(ready) == 2 * len(kinds)
        facts["sum_ms"] = (round(sum(facts[key] for key, _, _ in ready), 3)
                           if whole and ran else None)
        facts["host_ms"] = round(sum(host.values()), 3) if whole else None
        out[f"{tm}x{over_dim}x{over_ffn}"] = facts
        _say("products.timed", tile=[tm, over_dim, over_ffn], **facts)
    return out


# (name, positions a step, vocabulary, dim): each language-model cell's
# chunked loss as ``models/mla_moe.loss_fn`` calls it
HEAD_CALLS = (
    ("glm47f-train-8k", 16384, 19360, 2048),
    ("mellum2-train-8k", 16384, 24576, 2304),
    ("trinity-train-16k", 16384, 25024, 2048),
    ("nemotron3n-train-16k", 16384, 16384, 2688),
)
# one v5e chip's bfloat16 peak (benchmark/peaks.json)
V5E_BF16_FLOPS = 197e12


def _head_loss(positions: int, vocab: int, dim: int, chunk: int = 4096,
               repeats: int = 5) -> Dict[str, Any]:
    """``models/mla_moe._chunked_ce`` alone, bfloat16 operands: the ms a
    call of the loss with its gradients to the hidden state and to the
    head, what THREE products of positions x vocabulary x dim would take
    of a v5e's bfloat16 peak at that time (``three_products_peak_share``,
    %: a loss that makes its logits twice reads under 75), and the loss
    and both gradients against plain autodiff of the unchunked
    cross-entropy, a chunk at a time, in the same precision."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import mla_moe

    cfg = mla_moe.MLAMoEConfig(
        vocab=vocab, dim=dim, n_heads=1, q_lora_rank=1, kv_lora_rank=1,
        qk_nope_dim=1, qk_rope_dim=1, v_head_dim=2, dense_ffn=1,
        n_dense_layers=1, n_moe_layers=0, moe_ffn=1, n_experts=1,
        experts_held=1, loss_chunk=chunk)
    k = jax.random.split(jax.random.key(SEED), 3)
    h = jax.random.normal(k[0], (positions, dim))
    head = 0.05 * jax.random.normal(k[1], (vocab, dim))
    targets = jax.random.randint(k[2], (positions,), 0, vocab)
    weights = jnp.full((positions,), 1.0 / positions).at[-1].set(0.0)
    both = jax.jit(jax.value_and_grad(
        lambda h, head: mla_moe._chunked_ce(h, head, targets, weights, cfg),
        (0, 1))).lower(h, head).compile()
    got = jax.block_until_ready(both(h, head))
    t0 = time.perf_counter()
    for _ in range(repeats):
        res = both(h, head)
    jax.block_until_ready(res)
    ms = (time.perf_counter() - t0) / repeats * 1e3

    @jax.jit
    def plain(hc, head, tc, wc):
        def ce(hc, head):
            logits = mla_moe.matmul(hc, head, True, cfg.compute_dtype,
                                    jnp.float32)
            at = jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]
            return jnp.sum(wc * (jax.nn.logsumexp(logits, -1) - at))
        return jax.value_and_grad(ce, (0, 1))(hc, head)

    step = min(chunk, positions)
    parts = [plain(h[i:i + step], head, targets[i:i + step],
                   weights[i:i + step]) for i in range(0, positions, step)]
    want = (sum(p[0] for p in parts),
            (jnp.concatenate([p[1][0] for p in parts]),
             sum(p[1][1] for p in parts)))
    errs = [float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    if not max(errs) <= ATTN_BF16_TOL:      # a NaN fails too
        raise AssertionError(f"chunked loss: relative error {errs} (loss, "
                             f"dh, dhead) > {ATTN_BF16_TOL}")
    flops = 3 * 2 * positions * vocab * dim
    return {"loss_grad_ms": round(ms, 3),
            "three_products_peak_share": round(
                100 * flops / (ms * 1e-3) / V5E_BF16_FLOPS, 2),
            "rel_err_loss_dh_dhead": [round(e, 6) for e in errs]}


def stage_lm(vocab: int = 32768, dim: int = 2048, heads: int = 16,
             layers: int = 8, seq: int = 1024, batch_per_chip: int = 2,
             kernel_shapes: Tuple = (((2, 16, 1024, 128), 512),
                                     ((2, 16, 1024, 128), 128),
                                     ((8, 8, 512, 32), 512),
                                     # glm47f-train-8k's head and length,
                                     # square and as mla_moe.attn_blocks
                                     # has it; four heads, so that the
                                     # reference's float32 [S, S] fits
                                     ((1, 4, 8192, 256), 512),
                                     ((1, 4, 8192, 256), (512, 1024))),
             expert_calls: Tuple = EXPERT_CALLS,
             head_calls: Tuple = HEAD_CALLS,
             chip: bool = True) -> Dict[str, Any]:
    """The widest model the repo runs (472M, d2048/L8, bf16) with the
    Pallas flash kernel: three donated train steps on a fixed batch, then
    the kernel alone against reference_attention. With several devices the
    batch shards over the mesh and the kernel runs under shard_map. Then
    the language-model cells' grouped products alone
    (:func:`_expert_products`) and their chunked loss alone
    (:func:`_head_loss`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import multiverso_tpu as mv
    from multiverso_tpu.models import transformer as tfm
    from multiverso_tpu.ops.attention_kernels import _resolve_interpret

    mv.init()
    interpret = _resolve_interpret(None)
    if chip and interpret is not False:
        raise AssertionError("flash kernel would run in interpret mode")
    mesh, n_dev = mv.mesh(), jax.device_count()
    cfg = tfm.TransformerConfig(
        vocab_size=vocab, dim=dim, num_heads=heads, num_layers=layers,
        max_seq=seq, attn="flash", dtype=jnp.bfloat16,
        batch_axis=mesh.axis_names[0] if n_dev > 1 else None)
    params = jax.device_put(tfm.init_params(cfg, seed=0),
                            NamedSharding(mesh, P()))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    b = batch_per_chip * n_dev
    toks = np.random.default_rng(0).integers(
        0, vocab, (b, seq + 1)).astype(np.int32)
    tok = tfm.shard_batch(toks[:, :-1], cfg)
    tgt = tfm.shard_batch(toks[:, 1:], cfg)
    # donated params, as bench.bench_transformer steps it
    step = jax.jit(tfm.make_train_step(cfg, 1e-2), donate_argnums=(0,))
    mosaic = "tpu_custom_call" in step.lower(params, tok, tgt).as_text()
    if chip and not mosaic:
        raise AssertionError("lowered train step has no Mosaic custom call")
    losses = []
    for _ in range(3):
        params, loss = step(params, tok, tgt)
        losses.append(float(loss))
    _falls(losses, "lm train step")
    if not all(bool(jnp.all(jnp.isfinite(p.astype(jnp.float32))))
               for p in jax.tree.leaves(params)):
        raise AssertionError("lm params hold NaN/Inf after three steps")
    del params
    kernel = {f"{shape}/{block}": _attention_errors(shape, block, SEED + i)
              for i, (shape, block) in enumerate(kernel_shapes)}
    experts = {call[0]: _expert_products(
        *call[1:], kernel="interpret" if interpret else "pallas")
               for call in expert_calls}
    heads = {call[0]: _head_loss(*call[1:]) for call in head_calls}
    return {"params": n_params, "global_batch": b, "seq": seq,
            "batch_axis": cfg.batch_axis, "interpret": interpret,
            "mosaic_custom_call": mosaic,
            "loss": [round(x, 4) for x in losses],
            "kernel_rel_err": kernel, "kernel_tol": ATTN_BF16_TOL,
            "experts": experts, "heads": heads}


# (name, q's shape, key-value heads, (block_q, block_k), window): the
# window layers of mellum2-train-8k and trinity-train-16k, their full
# layers' call, glm47f-train-8k's and lfm2-train-8k's
FLASH_CALLS = (
    ("mellum2.window", (2, 32, 8192, 128), 4, (1024, 1024), 1024),
    ("trinity.window", (1, 32, 16384, 128), 4, (1024, 1024), 2048),
    ("mellum2.full", (2, 32, 8192, 128), 4, (1024, 1024), None),
    ("glm47f.causal", (2, 20, 8192, 256), 20, (512, 1024), None),
    # lfm2-train-8k's: heads of 64, half a lane tile a block
    ("lfm2.causal", (2, 32, 8192, 64), 8, (1024, 1024), None),
    # qwen3next-train-16k's: heads of 256, a group of 8 query heads
    ("qwen3next.causal", (1, 16, 16384, 256), 2, (512, 1024), None),
    # xing4-train-4k's: queries and keys of 192, values (a sixth entry) of 128
    ("xing4.causal", (1, 32, 4096, 192), 32, (1024, 1024), None, 128),
)


def stage_flash(calls: Tuple = FLASH_CALLS, subs: Tuple = (),
                repeats: int = 10, ref_heads: int = 2,
                selected: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The flash kernels' crossed pairs, whole-tile against sub-tiled
    (``ops/attention_kernels.sub_tile``'s choice for the call, or each of
    ``subs``): for the forward with its residual, dQ, and dK with dV, the
    seconds the compiler took and the ms a call by this process's clock
    around ``repeats`` calls it waits for; the sub-tiled results against
    the whole-tile ones on the same inputs (norm of the difference over
    the norm), and both against float32 attention on ``ref_heads`` query
    heads of the call (max|err| over max|reference|, as ``stage_lm``).
    Last, where ``selected`` is given (the run of all stages gives ``{}``),
    the kernels under a selection (:func:`flash_selected` with
    ``selected``'s arguments)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models.mla_moe import _xla_attention
    from multiverso_tpu.ops import attention_kernels as ak

    interpret = ak._resolve_interpret(None)
    out: Dict[str, Any] = {}
    for n, (name, shape, hkv, blocks, window, *own) in enumerate(calls):
        rng = np.random.default_rng(SEED + n)
        kv = (shape[0], hkv) + shape[2:]
        # the values' head size, and the output's: the keys' unless given
        wide = lambda like: like[:3] + (own[0] if own else like[3],)
        q, k, g, v = (jnp.asarray(rng.normal(size=size), jnp.bfloat16)
                      for size in (shape, kv, wide(shape), wide(kv)))
        rule = ak.sub_tile(*ak._blocks(shape[2], *blocks), shape[3])
        facts: Dict[str, Any] = {"sub_tile": rule}
        # float32 attention on a few heads of one sequence, so that the
        # reference's [S, S] fits
        few = lambda t, h: t[:1, :h]
        small = (few(q, ref_heads), few(k, 1), few(v, 1))
        gs = few(g, ref_heads)

        def fwd_and_grads(fn, *args):
            res, vjp = jax.vjp(fn, *args)
            return (res,) + vjp(gs.astype(res.dtype))

        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: fwd_and_grads(
                lambda q, k, v: _xla_attention(q, k, v, window),
                *(t.astype(jnp.float32) for t in a)))(*small)
        results = {}
        for sub in (None,) + (subs or ((rule,) if rule else ())):
            def forward(q, k, v):
                return ak._flash_forward(q, k, v, True, *blocks, interpret,
                                         True, window, sub)

            def backward(keep, q, k, v, o, lse, g):
                # the kernel whose results are dropped is no part of the
                # program: dQ (keep 0) and dK with dV (keep 1) apart
                grads = ak._flash_backward(q, k, v, o, lse, g, True, *blocks,
                                           interpret, window, sub)
                return grads[:1] if keep == 0 else grads[1:]

            o, lse = forward(q, k, v)
            kernels = (("fwd", forward, (q, k, v)),
                       ("dq", functools.partial(backward, 0),
                        (q, k, v, o, lse, g)),
                       ("dkv", functools.partial(backward, 1),
                        (q, k, v, o, lse, g)))
            tag, results[sub] = f"sub{sub or 0}", []
            for kernel, fn, args in kernels:
                t0 = time.perf_counter()
                compiled = jax.jit(fn).lower(*args).compile()
                facts[f"{tag}_{kernel}_compile_s"] = round(
                    time.perf_counter() - t0, 2)
                res = jax.block_until_ready(compiled(*args))
                # the output and the gradients; not the forward's residual
                results[sub] += [r for r in res if r.ndim == 4]
                t0 = time.perf_counter()
                for _ in range(repeats):
                    res = compiled(*args)
                jax.block_until_ready(res)
                facts[f"{tag}_{kernel}_ms"] = round(
                    (time.perf_counter() - t0) / repeats * 1e3, 3)
            if sub is not None:
                facts[f"{tag}_vs_whole"] = [
                    float(jnp.linalg.norm((a - b).astype(jnp.float32))
                          / jnp.linalg.norm(b.astype(jnp.float32)))
                    for a, b in zip(results[sub], results[None])]
                if max(facts[f"{tag}_vs_whole"]) > ATTN_BF16_TOL:
                    raise AssertionError(
                        f"flash {name}: sub-tiles of {sub} differ from "
                        f"whole tiles by {facts[f'{tag}_vs_whole']}")
            got = jax.jit(lambda *a: fwd_and_grads(
                lambda q, k, v: ak._attention(q, k, v, True, *blocks, None,
                                              window, sub), *a))(*small)
            errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                          / jnp.max(jnp.abs(b))) for a, b in zip(got, want)]
            if not max(errs) <= ATTN_BF16_TOL:      # a NaN fails too
                raise AssertionError(
                    f"flash {name} sub {sub}: relative error {errs} > "
                    f"{ATTN_BF16_TOL}")
            facts[f"{tag}_rel_err"] = [round(e, 5) for e in errs]
        out[name] = facts
    if selected is not None:
        # keye-train-16k's: the same kernels with one more operand
        out["keye.selected"] = flash_selected(**selected)
    return out


def stage_ssd(positions: int = 16384, heads: int = 64, head_dim: int = 64,
              groups: int = 8, state: int = 128, chunk: int = 128,
              repeats: int = 5, one_group=(8192, 256)) -> Dict[str, Any]:
    """``ops/ssd.ssd_chunked`` as ``nemotron3n-train-16k`` calls it
    (:func:`_ssd_shape` at 8 groups of 8 heads) and, under ``one_group``
    (its positions and its chunk), as ``granite4h-train-8k`` does: ALL the
    heads in ONE group, walked in blocks of heads, the configuration's
    chunk of 256 walked as lane tiles; forward and with every gradient,
    every head against the recurrence, both shapes."""
    facts = _ssd_shape(positions, heads, head_dim, groups, state, chunk,
                       repeats)
    if one_group:
        facts["one_group"] = _ssd_shape(one_group[0], heads, head_dim, 1,
                                        state, one_group[1], repeats)
    return facts


def _ssd_shape(positions: int, heads: int, head_dim: int, groups: int,
               state: int, chunk: int, repeats: int) -> Dict[str, Any]:
    """``ops/ssd.ssd_chunked`` on one sequence of ``positions`` at the
    given heads, groups, state and chunk, bfloat16 operands: which form
    runs on this
    device (``form``: the two Pallas kernels or the plain ``jax.numpy``
    one; ``head_blocks``: the blocks a group's heads are walked in), the
    seconds the compiler took and the ms a call, forward and
    forward with every gradient, by this process's clock around
    ``repeats`` calls it waits for: of that form (``fwd_ms``,
    ``fwd_bwd_ms``), of the plain form beside it (``plain_*``) and of
    the call as ``nemotron_h.mamba2`` makes it, on the mixer's one ``[x | B |
    C]`` array (``whole``) with the skip (``mixer_*``); every large operand
    is an ARGUMENT of the jitted function. Then EVERY head's output and
    gradients against the recurrence itself, a position at a time in
    float32, a group at a time (max|err| over max|reference|, as
    ``stage_lm``). Inputs are drawn as the mixer makes them: ``x``, ``B``,
    ``C`` a silu of unit normals, step sizes and ``A`` by the Mamba-2
    rule."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops import ssd

    k = jax.random.split(jax.random.key(SEED), 8)
    per = heads // groups
    x = jax.nn.silu(jax.random.normal(k[0], (1, positions, heads, head_dim)))
    b, c = (jax.nn.silu(jax.random.normal(key, (1, positions, groups, state)))
            for key in k[1:3])
    step = jnp.exp(jax.random.uniform(k[3], (heads,), minval=np.log(1e-3),
                                      maxval=np.log(0.1)))
    dt = jax.nn.softplus(jax.random.normal(k[4], (1, positions, heads))
                         + step + jnp.log(-jnp.expm1(-step)))
    a = -jax.random.uniform(k[5], (heads,), minval=1.0, maxval=16.0)
    weight = jax.random.normal(k[6], x.shape)
    skip = jax.random.normal(k[7], (heads,))
    args = (x, dt, a, b, c)
    kernels = ssd.kernel_heads(positions, heads, head_dim, groups, state,
                               chunk) is not None
    scan = lambda *t: ssd.ssd_chunked(*t, chunk)
    plain = lambda *t: ssd.plain(*t, chunk)

    def mixer(xbc, dt, a, skip):
        x, b, c = jnp.split(xbc, (heads * head_dim,
                                  heads * head_dim + groups * state), -1)
        # [positions, heads x head_dim] as the mixer's norm takes it: on
        # the chip a head a tile row is another layout, a pass of its own
        return ssd.ssd_chunked(
            x.reshape(1, positions, heads, head_dim), dt, a,
            b.reshape(1, positions, groups, state),
            c.reshape(1, positions, groups, state), chunk, skip=skip,
            whole=xbc).reshape(1, positions, heads * head_dim)

    def both(fn, n):       # the weighted sum's value and every gradient
        return lambda w, *t: jax.value_and_grad(
            lambda *u: jnp.sum(w * fn(*u)), range(n))(*t)

    xbc = jnp.concatenate([t.reshape(1, positions, -1) for t in (x, b, c)],
                          -1)
    facts: Dict[str, Any] = {
        "form": "kernels" if kernels else "plain",
        "head_blocks": per // ssd.head_block(per, head_dim)}
    timed = [("", scan, args, weight),
             ("mixer_", mixer, (xbc, dt, a, skip),
              weight.reshape(1, positions, -1))] + (
        [("plain_", plain, args, weight)] if kernels else [])
    for tag, fn, operands, over in timed:
        for name, call, ops in ((f"{tag}fwd", fn, operands),
                                (f"{tag}fwd_bwd", both(fn, len(operands)),
                                 (over,) + operands)):
            t0 = time.perf_counter()
            compiled = jax.jit(call).lower(*ops).compile()
            facts[f"{name}_compile_s"] = round(time.perf_counter() - t0, 2)
            jax.block_until_ready(compiled(*ops))
            t0 = time.perf_counter()
            for _ in range(repeats):
                res = compiled(*ops)
            jax.block_until_ready(res)
            facts[f"{name}_ms"] = round(
                (time.perf_counter() - t0) / repeats * 1e3, 3)

    def recurrence(x, dt, a, b, c):
        b, c = (jnp.repeat(t[0], per, axis=1) for t in (b, c))

        def one(h, each):
            xt, dtt, bt, ct = each
            h = (jnp.exp(dtt * a)[:, None, None] * h
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
            return h, jnp.sum(h * ct[:, None, :], -1)

        stretch = jax.checkpoint(lambda h, each: jax.lax.scan(one, h, each))
        each = jax.tree.map(
            lambda t: t.reshape((-1, min(256, positions)) + t.shape[1:]),
            (x[0], dt[0], b, c))
        _, y = jax.lax.scan(stretch, jnp.zeros((per, head_dim, state)), each)
        return y.reshape((1, positions, per, head_dim))

    def group(at, t):      # group ``at``'s part of an operand or gradient
        heads_of = slice(at * per, (at + 1) * per)
        return (t[heads_of] if t.ndim == 1 else t[:, :, at:at + 1]
                if t.shape[2] == groups else t[:, :, heads_of])

    got_y, got = jax.jit(lambda w, *t: (scan(*t), both(scan, 5)(w, *t)[1]))(
        weight, *args)
    want_of = jax.jit(lambda w, *t: (recurrence(*t),
                                     both(recurrence, 5)(w, *t)[1]))
    errs = np.zeros(6)
    for at in range(groups):
        want_y, want = want_of(group(at, weight),
                               *(group(at, t) for t in args))
        pairs = [(group(at, got_y), want_y)] + [
            (group(at, g), w) for g, w in zip(got, want)]
        errs = np.maximum(errs, [float(jnp.max(jnp.abs(g - w))
                                       / jnp.max(jnp.abs(w)))
                                 for g, w in pairs])
    if not max(errs) <= ATTN_BF16_TOL:      # a NaN fails too
        raise AssertionError(f"ssd: relative error {errs} (y, dx, ddt, da, "
                             f"db, dc) > {ATTN_BF16_TOL}")
    facts["rel_err_y_dx_ddt_da_db_dc"] = [round(float(e), 5) for e in errs]
    return facts


def stage_conv(sequences: int = 2, positions: int = 8192, dim: int = 2048,
               taps: int = 3, repeats: int = 10,
               check_positions: int = 512) -> Dict[str, Any]:
    """``models/lfm2_moe.short_conv`` as ``lfm2-train-8k`` calls it (the
    mixer alone on ``sequences`` x ``positions``, bfloat16 operands): the
    seconds the compiler took and the ms a call, forward and forward with
    every gradient, by this process's clock around ``repeats`` calls it
    waits for; the pass between the two products alone (the gates and the
    taps on a bfloat16 ``[positions, 3 dim]``) beside the least the chip's
    memory allows it (``benchmark/conv_shapes.mixer_bytes`` over the HBM
    peak of ``benchmark/peaks.json``); and the mixer's output and
    gradients on the first ``check_positions`` of one sequence against the
    convolution a position at a time in float32
    (``benchmark/reference/lfm2_moe.short_conv``; max|err| over
    max|reference|, as ``stage_lm``)."""
    import jax
    import jax.numpy as jnp

    from benchmark import conv_shapes, shapes
    from benchmark.reference import lfm2_moe as reference
    from multiverso_tpu.models import lfm2_moe

    cfg = lfm2_moe.LFM2MoEConfig(dim=dim, conv_taps=taps)
    k = jax.random.split(jax.random.key(SEED), 5)
    u = jax.random.normal(k[0], (sequences, positions, dim))
    p = {"win": 0.02 * jax.random.normal(k[1], (dim, 3 * dim)),
         "conv_w": taps ** -0.5 * jax.random.normal(k[2], (taps, dim)),
         "wout": 0.02 * jax.random.normal(k[3], (dim, dim))}
    weight = jax.random.normal(k[4], u.shape)
    mixer = lambda u, p: lfm2_moe.short_conv(u, p, cfg)
    both = lambda u, p: jax.value_and_grad(
        lambda u, p: jnp.sum(weight[:u.shape[0], :u.shape[1]] * mixer(u, p)),
        (0, 1))(u, p)

    def between(proj, w):       # what lies between the mixer's products
        return lfm2_moe.gated_taps(proj.astype(jnp.float32), w).astype(
            proj.dtype)

    proj = jax.random.normal(k[0], (sequences, positions, 3 * dim),
                             jnp.bfloat16)
    facts: Dict[str, Any] = {}
    for name, fn, args in (("fwd", mixer, (u, p)), ("fwd_bwd", both, (u, p)),
                           ("taps", between, (proj, p["conv_w"]))):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        facts[f"{name}_compile_s"] = round(time.perf_counter() - t0, 2)
        jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        for _ in range(repeats):
            res = compiled(*args)
        jax.block_until_ready(res)
        facts[f"{name}_ms"] = round(
            (time.perf_counter() - t0) / repeats * 1e3, 3)
    device = jax.devices()[0]
    if device.platform == "tpu":
        facts["taps_least_ms"] = round(
            conv_shapes.mixer_bytes(sequences, positions, dim)
            / shapes.peak(device.device_kind, "hbm_bytes_per_s") * 1e3, 3)

    recurrence = reference.short_conv       # one sequence, from the definition
    few = u[:1, :min(check_positions, positions)]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda u, p: jax.value_and_grad(
            lambda u, p: jnp.sum(weight[0, :u.shape[0]] * recurrence(u, p)),
            (0, 1))(u, p))(few[0], p)
        y_want = jax.jit(recurrence)(few[0], p)
    got = jax.jit(both)(few, p)
    pairs = [(mixer(few, p)[0], y_want), (got[1][0][0], want[1][0])] + [
        (got[1][1][n], want[1][1][n]) for n in ("win", "conv_w", "wout")]
    errs = [float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
            for g, w in pairs]
    if not max(errs) <= ATTN_BF16_TOL:      # a NaN fails too
        raise AssertionError(f"conv: relative error {errs} (y, du, dwin, "
                             f"dconv_w, dwout) > {ATTN_BF16_TOL}")
    facts["rel_err_y_du_dwin_dconvw_dwout"] = [round(e, 5) for e in errs]
    return facts


# the two cells' convolutions: (name, channels, a bias or none)
TAPS_CALLS = (("delta", 8192, False), ("ssm", 6144, True))
# float32 against float32, as max|err| over max|reference|: the sums of
# 16,384 positions' products in another order
TAPS_F32_TOL = 2e-5


def stage_taps(positions: int = 16384, calls: Tuple = TAPS_CALLS,
               taps: int = 4, repeats: int = 10,
               check_positions: int = 2048, tilings: Tuple = (),
               tile: Optional[Tuple[int, int]] = None,
               interpret: bool = False) -> Dict[str, Any]:
    """``ops/short_conv.causal_taps`` as ``qwen3next-train-16k`` and
    ``nemotron3n-train-16k`` call it (ONE sequence of ``positions``, float32,
    a silu; ``calls``: the channels, and whether a bias): the ms a call,
    forward and forward with every gradient (``dx``, ``dw``, ``dbias``), by
    this process's clock around ``repeats`` calls it waits for, of the
    kernels (``tile`` and ``interpret`` are a CPU test's; on the chip the
    op chooses) and of the plain form beside them; the least the chip's
    memory allows each (one read and one write of the array forward, two
    reads and a write more backward, over the HBM peak of
    ``benchmark/peaks.json``); and both forms' output and gradients on the
    first ``check_positions`` against the convolution a position at a time
    in float32 (max|err| over max|reference|). ``tilings`` reads other
    ((position tile, channel tile), rows at a time) of the kernels."""
    import jax
    import jax.numpy as jnp

    from benchmark import shapes
    from multiverso_tpu.ops import short_conv

    def recurrence(x, w, bias):         # one sequence, from the definition
        def one(past, xt):
            window = jnp.concatenate([past, xt[None]], 0)
            pre = jnp.sum(window * w, 0) + (0.0 if bias is None else bias)
            return window[1:], jax.nn.silu(pre)

        return jax.lax.scan(one, jnp.zeros((taps - 1, x.shape[-1])), x)[1]

    device = jax.devices()[0]
    facts: Dict[str, Any] = {}
    for name, channels, has_bias in calls:
        k = jax.random.split(jax.random.key(SEED), 4)
        x = jax.random.normal(k[0], (1, positions, channels))
        w = taps ** -0.5 * jax.random.normal(k[1], (taps, channels))
        bias = jax.random.normal(k[2], (channels,)) if has_bias else None
        weight = jax.random.normal(k[3], x.shape)
        wrt = (0, 1, 2) if has_bias else (0, 1)

        def both(conv):     # the weight is an operand, not a constant
            return lambda x, w, bias, weight: jax.value_and_grad(
                lambda *t: jnp.sum(weight * conv(*t)), wrt)(x, w, bias)

        kernel = lambda *t, tile=tile, rows=short_conv.ROWS: (
            short_conv.causal_taps(*t, True, tile=tile, rows=rows,
                                   interpret=interpret))
        plain = lambda *t: short_conv.plain(*t, True)
        forms = [("", kernel), ("plain_", plain)] + [
            (f"{ts}x{tc}r{rows}_", functools.partial(
                kernel, tile=(ts, tc), rows=rows))
            for (ts, tc), rows in tilings]
        for tag, conv in forms:
            for what, fn, args in (
                    ("fwd", conv, (x, w, bias)),
                    ("fwd_bwd", both(conv), (x, w, bias, weight))):
                compile_s, ms, _ = _timed(fn, args, repeats)
                facts[f"{name}_{tag}{what}_ms"] = ms
                facts[f"{name}_{tag}{what}_compile_s"] = compile_s
        if device.platform == "tpu":
            one = 4 * positions * channels / shapes.peak(
                device.device_kind, "hbm_bytes_per_s") * 1e3
            facts[f"{name}_fwd_least_ms"] = round(2 * one, 3)
            facts[f"{name}_fwd_bwd_least_ms"] = round(5 * one, 3)
        # a failed check keeps the readings
        _say("taps.timed", **{k: v for k, v in facts.items()
                              if k.startswith(name + "_")})
        n = min(check_positions, positions)
        few = (x[:, :n], w, bias, weight[:, :n])
        y_want = jax.jit(recurrence)(few[0][0], w, bias)
        want = jax.jit(both(lambda x, w, bias: recurrence(
            x[0], w, bias)[None]))(*few)
        for tag, conv in forms[:2]:
            got = jax.jit(both(conv))(*few)
            errs = [float(jnp.max(jnp.abs(g - t)) / jnp.max(jnp.abs(t)))
                    for g, t in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want))]
            # the output's own error in the weighted sum's place
            errs[0] = float(jnp.max(jnp.abs(jax.jit(conv)(*few[:3])[0]
                                            - y_want))
                            / jnp.max(jnp.abs(y_want)))
            if not max(errs) <= TAPS_F32_TOL:       # a NaN fails too
                raise AssertionError(
                    f"taps: {name} {tag or 'kernel'} relative error {errs} "
                    f"(y, dx, dw[, dbias]) > {TAPS_F32_TOL}")
            facts[f"{name}_{tag}rel_err_y_dx_dw_db"] = [
                float(f"{e:.3g}") for e in errs]
    facts["kernels"] = bool(tile or short_conv.kernel_tiles(
        positions, calls[0][1]))
    return facts


# ---------------------------------------------------------------------- #
# between a projection and the attention core (PR 63)
# ---------------------------------------------------------------------- #
def parent_gqa(u, p, cfg, kind: str, core=None):
    """``models/gqa_moe.gqa`` as it stood before ``mla_moe.heads``, worded
    from the equations of that file's docstring: three projections, q/k
    norms, rotary positions by the half-split, one rounding, a transpose
    into the core; a transpose out of it, the gate, ``W_o``."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import mla_moe

    b, s, _ = u.shape
    h, hkv, hd, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.compute_dtype
    mm = functools.partial(mla_moe.matmul, dtype=dt)
    q = mm(u, p["wq"], False, out_dtype=jnp.float32).reshape(b, s, h, hd)
    k = mm(u, p["wk"], False, out_dtype=jnp.float32).reshape(b, s, hkv, hd)
    v = mm(u, p["wv"], False, out_dtype=dt).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = mla_moe.rms_norm(q, p["q_norm"], cfg.eps)
        k = mla_moe.rms_norm(k, p["k_norm"], cfg.eps)
    if kind in cfg.rope_kinds:
        yarn = cfg.yarn if kind == "full" else None
        r = getattr(cfg, "rope_dim", None) or hd
        turn = lambda t: jnp.concatenate(
            [mla_moe.rotary(t[..., :r], cfg.rope_theta, yarn), t[..., r:]], -1)
        q, k = turn(q), turn(k)
    q, k, v = (t.astype(dt).transpose(0, 2, 1, 3) for t in (q, k, v))
    if core is None:
        return q, k, v
    o = core(q, k, v).transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(mm(u, p["wgate"], False, out_dtype=jnp.float32))
    return mm(o, p["wo"], False, out_dtype=jnp.float32)


def parent_mla(u, p, cfg, core=None):
    """``models/mla_moe.mla`` as it stood before ``mla_moe.heads``, worded
    from the equations of that file's docstring: one product a latent,
    column windows of its result, a concatenation a core operand."""
    import jax.numpy as jnp

    from multiverso_tpu.models import mla_moe

    b, s, _ = u.shape
    h, dt = cfg.n_heads, cfg.compute_dtype
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    mm = functools.partial(mla_moe.matmul, dtype=dt)
    c_q = mla_moe.rms_norm(mm(u, p["wdq"], False, out_dtype=jnp.float32),
                           p["q_norm"], cfg.eps)
    q = mm(c_q, p["wuq"], False, out_dtype=jnp.float32).reshape(b, s, h, -1)
    down = mm(u, p["wdkv"], True, out_dtype=jnp.float32)
    c_kv = mla_moe.rms_norm(down[..., :cfg.kv_lora_rank], p["kv_norm"],
                            cfg.eps)
    k_r = mla_moe.rotary(down[..., cfg.kv_lora_rank:], cfg.rope_theta,
                         cfg.yarn)
    kv = mm(c_kv, p["wukv"], False, out_dtype=dt).reshape(b, s, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], mla_moe.rotary(
        q[..., nope:], cfg.rope_theta, cfg.yarn)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, :, None, :], (b, s, h, rope)).astype(dt)], -1)
    q, k, v = (t.astype(dt).transpose(0, 2, 1, 3)
               for t in (q, k, kv[..., nope:]))
    if core is None:
        return q, k, v
    o = core(q, k, v).transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return mm(o, p["wo"], False, out_dtype=jnp.float32)


_HLO_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)"
                        r"\[([0-9,]*)\]")
_HLO_FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "after-all", "iota", "partition-id")


def _hlo_bytes(text: str) -> int:
    total = 0
    for kind, dims in _HLO_SHAPE.findall(text):
        size = 1 if kind == "pred" else int(
            re.sub(r"\D", "", kind)) // 8
        for d in filter(None, dims.split(",")):
            size *= int(d)
        total += size
    return total


def bytes_outside_kernels(compiled_text: str) -> Dict[str, Any]:
    """Of a compiled program's text: the bytes its entry computation's
    instructions write and read (result and operands of every top-level
    instruction, a fusion counted as one), those of the Pallas kernels
    (``tpu_custom_call``) apart, in GB: ``{"all_gb", "kernels_gb",
    "outside_gb", "kernels"}``. An upper reading of the HBM traffic round
    the kernels (an operand prefetched into VMEM is counted where it is
    copied and where it is read); nothing where the text has no entry."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", compiled_text,
                      re.S | re.M)
    if not entry:
        return {}
    results: Dict[str, int] = {}
    total = kernels = count = 0
    for line in entry.group(1).splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = (.*?) ([\w\-]+)\((.*)$", line)
        if not m:
            continue
        name, result, op, rest = m.groups()
        results[name] = _hlo_bytes(result)
        if op in _HLO_FREE or (op == "custom-call"
                               and "tpu_custom_call" not in line):
            continue
        moved = results[name] + sum(
            results.get(a, 0) for a in re.findall(
                r"%([\w\.\-]+)", rest.split("), ")[0]))
        total += moved
        if op == "custom-call":
            kernels, count = kernels + moved, count + 1
    return {"all_gb": round(total / 1e9, 3),
            "kernels_gb": round(kernels / 1e9, 3),
            "outside_gb": round((total - kernels) / 1e9, 3), "kernels": count}


def _heads_cells():
    """(name, configuration, layer kind, sequences, positions) of ONE
    attention layer as seven cells run it."""
    from multiverso_tpu.models import (afmoe, gqa_moe, lfm2_moe, mla_moe,
                                       qwen3_next, xing4)

    yarn = mla_moe.Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    mellum = gqa_moe.GQAMoEConfig(dim=2304, n_heads=32, n_kv_heads=4,
                                  head_dim=128, window=1024, yarn=yarn)
    return (
        ("glm", mla_moe.MLAMoEConfig(
            dim=2048, n_heads=20, q_lora_rank=768, kv_lora_rank=512,
            qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256), "latent", 2,
         8192),
        ("mellum_full", mellum, "full", 2, 8192),
        ("mellum_window", mellum, "window", 2, 8192),
        ("trinity", afmoe.AFMoEConfig(
            dim=2048, n_heads=32, n_kv_heads=4, head_dim=128, window=2048),
         "window", 1, 16384),
        # Keye's projections round a plain causal core: no selection here
        ("keye", mellum._replace(dim=2048, qk_norm=True, yarn=None,
                                 rope_theta=1e7, eps=1e-6), "full", 1, 16384),
        ("xing", xing4.Xing4Config(
            dim=3584, n_heads=32, q_lora_rank=768, kv_lora_rank=512,
            qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128), "latent", 1,
         4096),
        # and two more head shapes: 64 whole, 64 of 256 under a gate
        ("lfm2", lfm2_moe.LFM2MoEConfig(
            dim=2048, n_heads=32, n_kv_heads=8, head_dim=64), "full", 2, 8192),
        ("qwen3next", qwen3_next.Qwen3NextConfig(
            dim=2048, n_heads=16, n_kv_heads=2, head_dim=256, rope_dim=64),
         "full", 1, 16384))


# bfloat16 operands on both sides: the gradients' max|err| over max|parent|
HEADS_TOL = 2e-2


def stage_heads(cells: Optional[Tuple] = None, repeats: int = 5,
                attn: Optional[str] = None) -> Dict[str, Any]:
    """ONE attention layer with every gradient (``dx`` and every ``dW``) at
    the shapes five language-model cells run it, the parameters and the
    input ARGUMENTS of the program: ``mla_moe.heads`` / ``out_of_heads``
    round the core (``cfg.attend``) beside the formulation before them
    (:func:`parent_gqa`, :func:`parent_mla`), ms a call by this process's
    clock, compile seconds, and the compiled layer's bytes outside its
    Pallas kernels (:func:`bytes_outside_kernels`, for the device at hand);
    then the two forms' gradients against each other. ``cells``: other
    (name, configuration, kind, sequences, positions); ``attn``: the core
    (a CPU test's ``"xla"``)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import gqa_moe, mla_moe
    from multiverso_tpu.ops.attention_kernels import flash_attention

    facts: Dict[str, Any] = {}
    for name, cfg, kind, b, s in cells or _heads_cells():
        cfg = cfg._replace(attn=attn) if attn else cfg
        window = cfg.window if kind == "window" else None
        scale = getattr(cfg, "softmax_scale", None)

        def core(q, k, v, cfg=cfg, window=window, scale=scale, s=s):
            if mla_moe.attn_core(cfg) != "flash":
                return mla_moe._xla_attention(q, k, v, window, scale=scale)
            return flash_attention(q, k, v, True, *mla_moe.attn_blocks(cfg, s),
                                   None, window, scale=scale)

        if kind == "latent":
            parent = lambda u, p, cfg=cfg, core=core: parent_mla(
                u, p, cfg, core)
            new = lambda u, p, cfg=cfg: mla_moe.mla(u, p, cfg)
        else:
            parent = lambda u, p, cfg=cfg, kind=kind, core=core: parent_gqa(
                u, p, cfg, kind, core)
            new = lambda u, p, cfg=cfg, kind=kind: gqa_moe.gqa(
                u, p, cfg, kind)
        shapes = cfg.attn_shapes(kind)
        keys = jax.random.split(jax.random.key(SEED), len(shapes) + 2)
        p = {n: (jnp.ones(sh) if n.endswith("norm") else
                 sh[0] ** -0.5 * jax.random.normal(key, sh))
             for (n, sh), key in zip(sorted(shapes.items()), keys)}
        u = jax.random.normal(keys[-1], (b, s, cfg.dim))
        weight = jax.random.normal(keys[-2], (b, s, cfg.dim))
        got = {}
        for tag, form in (("parent", parent), ("new", new)):
            fn = lambda u, p, weight, form=form: jax.grad(
                lambda u, p: jnp.sum(weight * form(u, p)), (0, 1))(u, p)
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(u, p, weight).compile()
            facts[f"{name}_{tag}_compile_s"] = round(
                time.perf_counter() - t0, 2)
            _, ms, got[tag] = _timed(fn, (u, p, weight), repeats)
            facts[f"{name}_{tag}_ms"] = ms
            facts.update({f"{name}_{tag}_{k}": v for k, v in
                          bytes_outside_kernels(compiled.as_text()).items()})
        # a failed check keeps the readings
        _say("heads.timed", **{k: v for k, v in facts.items()
                               if k.startswith(name + "_")})
        errs = jax.tree.map(
            lambda g, t: float(jnp.max(jnp.abs(g - t)) / jnp.max(jnp.abs(t))),
            got["new"], got["parent"])
        worst = max(jax.tree.leaves(errs))
        if not worst <= HEADS_TOL:      # a NaN fails too
            raise AssertionError(f"heads: {name} gradients differ from the "
                                 f"parent formulation's: {errs}")
        facts[f"{name}_rel_err"] = float(f"{worst:.3g}")
    return facts



HC_F32_TOL = 1e-4


@contextlib.contextmanager
def _hc_walks_as(tiles, interpret: bool = False):
    """Inside: the sublayer's rule (``mla_moe._hyper_bwd``) takes its walks
    as ``tiles(t, n, c)`` says (``None``: the plain forms) whatever the
    device, in the interpreter where ``interpret``. The program has no
    option for it; a smoke's readings of both forms on one device need
    one."""
    from multiverso_tpu.ops import stream_walks

    names = ("walk_tiles", "gather", "dots", "spread")
    found = {name: getattr(stream_walks, name) for name in names}
    stream_walks.walk_tiles = lambda t, n, c, *dtypes: tiles(t, n, c)
    if interpret:
        for name in names[1:]:
            setattr(stream_walks, name,
                    functools.partial(found[name], interpret=True))
    try:
        yield
    finally:
        for name in names:
            setattr(stream_walks, name, found[name])


def _hc_walks(positions: int, dim: int, streams: int, repeats: int,
              interpret: bool = False) -> Dict[str, Any]:
    """The three backward walks of ``ops/stream_walks.py`` alone at a
    sublayer's shapes: each kernel's ms a call, the bytes it must move over
    that time over the chip's HBM peak, and its error against the plain
    form (max|err| over max|plain|). Off a TPU the kernels run in the
    interpreter where ``interpret`` says so, and no share is made up."""
    import jax
    import jax.numpy as jnp

    from benchmark import shapes
    from multiverso_tpu.ops import stream_walks

    t, n, c = positions, streams, dim
    tiles = (stream_walks.tiles_for(t, n, c) if interpret
             else stream_walks.walk_tiles(t, n, c, jnp.float32))
    if tiles is None:
        return {"kernels": False}
    outs = n * n + 2 * n
    k = jax.random.split(jax.random.key(SEED + 1), 10)
    # the streams as XLA lays them out between its own fusions and as the
    # kernels read them, [n, C, T]: a stream at a time, positions on the
    # lanes (handed over a position at a time, a parameter's layout, a call
    # would open with a copy of each); the branch's side likewise
    g, x = (jax.random.normal(k[i], (n, c, t)) for i in (0, 1))
    y, du = (jax.random.normal(k[i], (c, t)) for i in (2, 3))
    post, pre = (jax.random.uniform(k[i], (n, t)) for i in (4, 5))
    res = jax.random.uniform(k[6], (n, n, t))
    a, q = jax.random.normal(k[7], (outs, t)), jax.random.normal(k[8], (t,))
    phi = (n * c) ** -0.5 * jax.random.normal(k[9], (outs, n * c))
    how = dict(tiles=tiles, interpret=interpret)
    turned = lambda v: v.transpose(2, 0, 1)        # [n, C, T] as [T, n, C]
    calls = (
        ("gather", 2 * n + 2,
         lambda walk, g, x, y, *o: (lambda dy, *sums: (dy.T,) + sums)(
             *walk(turned(g), turned(x), y.T, *o)), (g, x, y, post)),
        ("dots", n + 1, lambda walk, du, x: walk(du.T, turned(x)), (du, x)),
        ("spread", 3 * n + 1,
         lambda walk, g, x, du, *o: walk(
             turned(g), turned(x), du.T, *o).transpose(1, 2, 0),
         (g, x, du, phi, pre, res, a, q)))
    device = jax.devices()[0]
    facts: Dict[str, Any] = {"kernels": True, "tiles": list(tiles)}
    for name, arrays, call, args in calls:
        kernel = functools.partial(getattr(stream_walks, name), **how)
        plain = getattr(stream_walks, name + "_plain")
        compile_s, ms, got = _timed(functools.partial(call, kernel), args,
                                    repeats)
        facts[f"{name}_ms"], facts[f"{name}_compile_s"] = ms, compile_s
        _, facts[f"{name}_plain_ms"], want = _timed(
            functools.partial(call, plain), args, repeats)
        if device.platform == "tpu":
            facts[f"{name}_hbm_share"] = round(
                100 * arrays * 4 * t * c / (ms * 1e-3) / shapes.peak(
                    device.device_kind, "hbm_bytes_per_s"), 1)
        facts[f"{name}_rel_err"] = float("%.3g" % max(
            float(jnp.max(jnp.abs(o - w)) / jnp.max(jnp.abs(w)))
            for o, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))))
    return facts


def stage_hc(positions: int = 4096, dim: int = 3584, streams: int = 4,
             iters: int = 20, repeats: int = 10,
             check_positions: int = 256, stack: int = 10,
             interpret: bool = False) -> Dict[str, Any]:
    """ONE hyper-connected sublayer's stream maps alone at
    ``xing4-train-4k``'s shapes (``models/mla_moe.block`` under ``streams``
    residual streams round a branch that hands its normed input back): the
    norm, the [24 x 14,336] projection, sigmoid, exp, Sinkhorn's ``iters``
    rounds, the pre-mix and the write back to every stream. The ms a call
    forward (``fwd``) and forward with every gradient (the streams', the
    three tables') three ways: under the sublayer's one differentiation
    rule as this device runs it (``fwd_bwd``: the backward walks are
    ``ops/stream_walks.py``'s kernels on a TPU), under the rule with the
    walks in plain ``jax.numpy`` (``rule_plain``) and by plain autodiff of
    the forward's own lines (``autodiff``: what every pass was before PR
    62, 13.20 ms), by this process's clock around ``repeats`` calls it waits
    for; on a TPU the least the chip's memory allows each
    (``benchmark/hc_shapes.py``: 2n + 2 arrays of ``dim`` floats a position
    forward, 7n + 5 with the backward pass, over the HBM peak of
    ``benchmark/peaks.json``); the three walks alone (:func:`_hc_walks`);
    the seconds one trace and one lowering of ``stack`` rematerialised
    sublayers with every gradient take (``stack_trace_s``,
    ``stack_lower_s``); the largest ``abs(row or column sum of H_res -
    1)``; and the result and the gradients of the first two ways on the
    first ``check_positions`` against ``benchmark/reference/xing4.sublayer``
    in float32 (max|err| over max|reference|)."""
    import jax
    import jax.numpy as jnp

    from benchmark import hc_shapes, shapes
    from benchmark.reference import xing4 as ref
    from multiverso_tpu.models import mla_moe, xing4
    from multiverso_tpu.ops import stream_walks

    cfg = xing4.Xing4Config(dim=dim, streams=streams, sinkhorn_iters=iters)
    outs = streams * streams + 2 * streams
    k = jax.random.split(jax.random.key(SEED), 5)
    x = jax.random.normal(k[0], (1, positions, streams, dim))
    weight = jax.random.normal(k[1], x.shape)
    p = {"attn_norm": jnp.ones((dim,)),
         "attn.hc_phi": (streams * dim) ** -0.5 * jax.random.normal(
             k[2], (outs, streams * dim)),
         "attn.hc_b": jax.random.normal(k[3], (outs,)),
         "attn.hc_alpha": jnp.ones((3,))}
    handed_back = lambda u, p: u
    maps = lambda x, p: mla_moe.block(x, p, handed_back, None, cfg)[::2]

    def unruled(x, p):      # the forward's own lines under plain autodiff
        hc = tuple(p[f"attn.hc_{k}"] for k in ("phi", "b", "alpha"))
        branch = lambda u, q: (mla_moe.rms_norm(u, q["attn_norm"], cfg.eps),
                               None)
        return mla_moe._hyper.fun(branch, cfg, x, hc, p)[::2]

    def both(x, p, weight, maps=maps):  # the weight is an operand
        return jax.value_and_grad(
            lambda x, p: jnp.sum(weight * maps(x, p)[0]), (0, 1))(x, p)

    def plain_walks(*args):
        with _hc_walks_as(lambda t, n, c: None):
            return both(*args)

    steered = (_hc_walks_as(stream_walks.tiles_for, True) if interpret
               else contextlib.nullcontext())   # the CPU's rehearsal
    facts: Dict[str, Any] = {}
    with steered:
        for what, fn, args in (
                ("fwd", maps, (x, p)), ("fwd_bwd", both, (x, p, weight)),
                ("rule_plain", plain_walks, (x, p, weight)),
                ("autodiff", functools.partial(both, maps=unruled),
                 (x, p, weight))):
            compile_s, ms, res = _timed(fn, args, repeats)
            facts[f"{what}_ms"], facts[f"{what}_compile_s"] = ms, compile_s
            if what == "fwd":
                facts["res_error"] = float(res[1])
        device = jax.devices()[0]
        if device.platform == "tpu":
            c = {"hc_mult": streams, "hidden_size": dim}
            whole = hc_shapes.sublayer_bytes(c, positions) / shapes.peak(
                device.device_kind, "hbm_bytes_per_s") * 1e3
            facts["fwd_least_ms"] = round(
                whole * (2 * streams + 2) / (7 * streams + 5), 3)
            facts["fwd_bwd_least_ms"] = round(whole, 3)
        facts["walks"] = _hc_walks(positions, dim, streams, repeats,
                                   interpret)

        def stacked(x, p, weight):
            for _ in range(stack):
                x = jax.checkpoint(lambda x, p: maps(x, p)[0])(x, p)
            return jnp.sum(weight * x)

        t0 = time.perf_counter()
        traced = jax.jit(jax.grad(stacked, (0, 1))).trace(x, p, weight)
        t1 = time.perf_counter()
        traced.lower()
        facts["stack_trace_s"] = round(t1 - t0, 2)
        facts["stack_lower_s"] = round(time.perf_counter() - t1, 2)
        _say("hc.timed", **facts)   # a failed check keeps the readings
        n = min(check_positions, positions)
        c = dict(hc_mult=streams, hc_sinkhorn_iters=iters, hc_eps=cfg.hc_eps,
                 rms_norm_eps=cfg.eps, mhc_h_res_clamp_min=cfg.res_clamp[0],
                 mhc_h_res_clamp_max=cfg.res_clamp[1])
        few = (x[:, :n], p, weight[:, :n])

        def plain(x, p, weight):
            return jax.value_and_grad(
                lambda x, p: jnp.sum(weight[0] * ref.sublayer(
                    x[0], p, "attn", lambda u: (u, None), c)[0]),
                (0, 1))(x, p)

        with jax.default_matmul_precision("highest"):
            want = jax.jit(plain)(*few)
        for name, fn in (("rel_err", both), ("rule_plain_rel_err",
                                            plain_walks)):
            got = jax.jit(fn)(*few)
            errs = [float(jnp.max(jnp.abs(g - t)) / jnp.max(jnp.abs(t)))
                    for g, t in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want))]
            if not max(errs) <= HC_F32_TOL:         # a NaN fails too
                raise AssertionError(
                    f"hc: {name} {errs} (the weighted sum, dx, the tables' "
                    f"gradients) > {HC_F32_TOL}")
            facts[name] = [float(f"{e:.3g}") for e in errs]
    return facts


LOOP_BF16_TOL = 0.2     # bfloat16 operands through 32 block applications


def stage_loop(positions: int = 4096, dim: int = 2048, heads: int = 16,
               head_dim: int = 128, ffn: int = 5632, layers: int = 8,
               passes: int = 4, repeats: int = 3,
               check_positions: Optional[int] = None,
               dtype: Any = None) -> Dict[str, Any]:
    """``ouro-train-4k``'s looped stack alone (``models/mla_moe._passes``
    under ``models/ouro.OuroConfig``): ``layers`` published-width blocks run
    ``passes`` times with ONE set of weights, the stream normed after every
    pass, forward and with every gradient (the input's, every table's) at
    ``positions`` positions. Three programs side by side, the weights an
    ARGUMENT of each: the loop as the step has it (``scan``: one
    ``lax.scan`` over the passes), the same passes unrolled in Python
    (``unrolled``: ``passes x layers`` blocks in the program text) and one
    pass alone (``pass``: ``layers`` unrolled blocks): of each the seconds
    one trace and one lowering take (what every warm set-up pays:
    ``*_trace_s``, ``*_lower_s``), the compile seconds and the ms a call by
    this process's clock around ``repeats`` calls it waits for. The scan
    and the unrolled passes must give the same exits and gradients (to the
    operands' rounding: XLA fuses the two differently); and the scan's are
    held to ``benchmark/reference/ouro.exit_states`` in float32, a block at
    a time, on the first ``check_positions`` (all by default): max|err| over
    max|reference| of the exits, the input's gradient and each kind of
    table's. The exits are unit-RMS vectors, so their error reads as the
    operands' rounding carried through the block runs."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import ouro as ref
    from multiverso_tpu.models import mla_moe, ouro

    cfg = ouro.OuroConfig(
        vocab=8, dim=dim, n_heads=heads, n_kv_heads=heads, head_dim=head_dim,
        n_layers=layers, passes=passes, dense_ffn=ffn,
        **({} if dtype is None else {"compute_dtype": dtype}))
    small = 0.02 / (2 * passes * layers) ** 0.5
    p = {n: v for n, v in mla_moe.init(
        cfg, SEED, 0.02, {"wo": small, "wd": small}).items()
        if n.startswith("L") or n == "final_norm"}
    keys = jax.random.split(jax.random.key(SEED), 2)
    x = jax.random.normal(keys[0], (1, positions, dim))
    weight = jax.random.normal(keys[1], (passes,) + x.shape)

    def unrolled(x, p, n_passes=passes):
        exits = []
        for _ in range(n_passes):
            for layer in cfg.layers():
                x, _ = mla_moe._run_block(
                    x, mla_moe._sub(p, layer.name), layer, None, cfg)
            x = mla_moe.rms_norm(x, p["final_norm"], cfg.eps)
            exits.append(x)
        return jnp.stack(exits)

    def with_grads(stack, n_passes=passes):
        """((the weighted sum, the exits), (the input's gradient, every
        table's))."""
        def fn(x, p, weight):       # the weight is an operand
            def value(x, p):
                exits = stack(x, p)
                return jnp.sum(weight[:n_passes] * exits), exits
            return jax.value_and_grad(value, (0, 1), has_aux=True)(x, p)
        return fn

    programs = {
        "scan": with_grads(lambda x, p: mla_moe._passes(x, p, cfg)),
        "unrolled": with_grads(unrolled),
        "pass": with_grads(lambda x, p: unrolled(x, p, 1), 1)}
    facts: Dict[str, Any] = {"block_runs": passes * layers}
    results = {}
    # what a process traces once (the kernels' bodies, the rules of the
    # products) is traced before the clocks start, whichever program is
    # timed first
    jax.jit(lambda *of: programs["pass"](*of)).trace(x, p, weight)
    for name, fn in programs.items():
        t0 = time.perf_counter()
        traced = jax.jit(fn).trace(x, p, weight)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
        results[name] = jax.block_until_ready(compiled(x, p, weight))
        t4 = time.perf_counter()
        for _ in range(repeats):
            res = compiled(x, p, weight)
        jax.block_until_ready(res)
        facts.update({
            f"{name}_trace_s": round(t1 - t0, 2),
            f"{name}_lower_s": round(t2 - t1, 2),
            f"{name}_compile_s": round(t3 - t2, 2),
            f"{name}_ms": round((time.perf_counter() - t4) / repeats * 1e3,
                                3),
            f"{name}_temp_gb": round(
                compiled.memory_analysis().temp_size_in_bytes / 1e9, 3)})
        del compiled, res
    _say("loop.timed", **facts)     # a failed check keeps the readings

    def rel(got, want):
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    def by_kind(got, want):
        """The exits, the input's gradient, and each kind of table's
        worst."""
        kinds: Dict[str, float] = {}
        for n in want[1][1]:
            kind = n.split(".")[-1]
            kinds[kind] = max(kinds.get(kind, 0.0),
                              rel(got[1][1][n], want[1][1][n]))
        return {"exits": rel(got[0][1], want[0][1]),
                "dx": rel(got[1][0], want[1][0]), **kinds}

    facts["scan_against_unrolled"] = {
        k: float(f"{v:.3g}") for k, v in by_kind(
            results["scan"], results["unrolled"]).items()}
    del results["unrolled"], results["pass"]
    n = min(check_positions or positions, positions)
    c = dict(hidden_size=dim, num_attention_heads=heads,
             num_key_value_heads=heads, head_dim=head_dim,
             intermediate_size=ffn, num_hidden_layers=layers,
             total_ut_steps=passes, rms_norm_eps=cfg.eps,
             rope_theta=cfg.rope_theta)

    def plain(x, p, weight):
        def value(x, p):
            exits = jnp.stack(ref.exit_states(
                dict(p, embed=x[0]), jnp.arange(n), c, lean=True))[:, None]
            return jnp.sum(weight * exits), exits
        return jax.value_and_grad(value, (0, 1), has_aux=True)(x, p)

    few = (x[:, :n], p, weight[:, :, :n])
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(plain)(*few))
    got = (results["scan"] if n == positions
           else jax.jit(programs["scan"])(*few))
    errs = by_kind(got, want)
    facts["rel_err"] = {k: float(f"{v:.3g}") for k, v in errs.items()}
    tol = LOOP_BF16_TOL if cfg.compute_dtype == jnp.bfloat16 else HC_F32_TOL
    if not max(errs.values()) <= tol:               # a NaN fails too
        raise AssertionError(f"loop: {facts['rel_err']} > {tol}")
    if not max(facts["scan_against_unrolled"].values()) <= tol:
        raise AssertionError(
            f"loop: scan against unrolled {facts['scan_against_unrolled']}")
    return facts


def _inverse_by(how: str):
    """The ways of making ``T = (I + M)^-1`` that stage ``delta`` reads:
    ``ops/delta_rule.unit_lower_inverse`` (``solve``: XLA's triangular
    solve against the identity); forward substitution by halves
    (``halves``: with the inverses ``A'``, ``B'`` of the two diagonal
    blocks of ``[[A, 0], [C, B]]`` the whole inverse is ``[[A', 0], [-B' C
    A', B']]``, so ``log2 Q`` rounds of two products of whole [Q, Q]
    matrices, the round's ``C`` blocks cut out by a mask); and the doubling
    product ``(I - M)(I + M^2)(I + M^4)...``, exact for a nilpotent ``M``
    but a sum of powers whose entries grow by binomials where keys repeat
    (``doubling``)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops import delta_rule

    mm = lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def halves(m):
        q = m.shape[-1]
        i = jnp.arange(q)
        row, col = i[:, None], i[None, :]
        inv, b = jnp.eye(q, dtype=m.dtype), 1
        while b < q:
            lower_left = ((row // (2 * b) == col // (2 * b))
                          & (row // b % 2 == 1) & (col // b % 2 == 0))
            inv = inv - mm(mm(inv, jnp.where(lower_left, m, 0.0)), inv)
            b *= 2
        return inv

    def doubling(m):
        eye = jnp.eye(m.shape[-1], dtype=m.dtype)
        out, power, n = eye - m, m, 2
        while n < m.shape[-1]:
            power = mm(power, power)
            out, n = mm(out, eye + power), 2 * n
        return out

    return {"solve": delta_rule.unit_lower_inverse, "halves": halves,
            "doubling": doubling}[how]


# (the way ``T`` is made, the chunk, the key heads a group): what stage
# ``delta`` reads; the first is what ``qwen3next-train-16k`` runs
DELTA_CALLS = (("solve", 64, 2), ("halves", 64, 2), ("doubling", 64, 2),
               ("solve", 128, 2), ("solve", 64, 4), ("solve", 64, 8),
               ("solve", 64, 16), ("halves", 128, 8))


def _delta_block(positions: int, dim: int, key_heads: int, value_heads: int,
                 head_dim: int, chunk: int, repeats: int) -> Dict[str, Any]:
    """ONE delta-rule mixer's block (input norm, mixer, residual; no
    feed-forward) of ``models/qwen3_next.Qwen3NextConfig``, forward with
    every gradient under ``models/mla_moe._run_block`` as a training step
    rematerialises it, with what the configuration's blocks keep by name
    (``kept``: the rule's result, ``qwen3_next.KEPT_NAMES``) and with that
    name out of the policy (``bare``): the ms a call, the compiler's
    temporaries, and the compiled program's loops under
    ``mv.lm.delta.rule`` (a group's scan and the groups' map, forward and
    in each pass that makes them again: a third fewer where the rule's
    result is kept would say the block made again runs the rule no more).
    The two must give the same gradients to the bit."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import mla_moe, qwen3_next

    kept = qwen3_next.Qwen3NextConfig(
        dim=dim, n_layers=1, full_every=2, lin_key_heads=key_heads,
        lin_value_heads=value_heads, lin_key_dim=head_dim,
        lin_value_dim=head_dim, delta_chunk=chunk)
    bare = type("Bare", (qwen3_next.Qwen3NextConfig,),
                {"kept_names": ()})(*kept)
    layer = mla_moe.Layer("L0", "delta", None)
    p = mla_moe._sub(mla_moe.init(kept, SEED, 0.02, {"conv_w": 0.3}), "L0")
    p = {n: p[n] for n in kept.attn_shapes("delta")}
    keys = jax.random.split(jax.random.key(SEED), 2)
    x = jax.random.normal(keys[0], (1, positions, dim))
    weight = jax.random.normal(keys[1], x.shape)
    facts: Dict[str, Any] = {}
    grads = {}
    for name, cfg in (("kept", kept), ("bare", bare)):
        # the weights are an ARGUMENT, as ``stage_delta``'s
        def loss(x, p, weight, cfg=cfg):
            return jnp.sum(mla_moe._run_block(x, p, layer, None, cfg)[0]
                           * weight)

        compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
            x, p, weight).compile()
        grads[name] = jax.block_until_ready(compiled(x, p, weight))
        t0 = time.perf_counter()
        for _ in range(repeats):
            res = compiled(x, p, weight)
        jax.block_until_ready(res)
        facts[f"block_{name}_ms"] = round(
            (time.perf_counter() - t0) / repeats * 1e3, 3)
        facts[f"block_{name}_temp_gb"] = round(
            compiled.memory_analysis().temp_size_in_bytes / 1e9, 3)
        facts[f"block_{name}_rule_loops"] = sum(
            " while(" in line and "mv.lm.delta.rule" in line
            for line in compiled.as_text().splitlines())
    facts["block_kept_bytes"] = kept.kept_bytes(1, positions)
    if not all(bool(jnp.all(a == b)) and bool(jnp.all(jnp.isfinite(a)))
               for a, b in zip(jax.tree.leaves(grads["kept"]),
                               jax.tree.leaves(grads["bare"]))):
        raise AssertionError("delta block: keeping the rule's result moved "
                             "a gradient, or one is not finite")
    return facts


def stage_delta(positions: int = 16384, key_heads: int = 16,
                value_heads: int = 32, head_dim: int = 128,
                calls: Tuple = DELTA_CALLS, repeats: int = 5,
                check_positions: int = 2048, dim: int = 2048
                ) -> Dict[str, Any]:
    """``ops/delta_rule.gated_delta_chunked`` as ``qwen3next-train-16k``
    calls it (one sequence of ``positions``, bfloat16 operands), for each
    of ``calls`` (a way of making ``T``, a chunk, the key heads a group):
    the seconds the compiler took and the ms a call, forward and forward
    with every gradient, by this process's clock around ``repeats`` calls
    it waits for; and ONE key head's output and gradients over the first
    ``check_positions`` against the recurrence itself, a position at a time
    in float32 (``benchmark/reference/qwen3_next.delta_rule``; max|err|
    over max|reference|, as ``stage_lm``), for every way of making ``T``
    among the calls, with float32 operands and with bfloat16 ones. Inputs
    are drawn as the mixer makes them: unit keys, scaled unit queries, ``v``
    a silu of unit normals, ``beta`` a sigmoid, ``g = -A softplus(a + 1)``
    with ``A`` uniform in (0, 16), the slowest head at 1e-3. Before the
    checks, the whole mixer's block of width ``dim`` at the first call's
    chunk as a step rematerialises it, with the rule's result kept by
    name and without (:func:`_delta_block`)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next as reference
    from multiverso_tpu.ops.delta_rule import gated_delta_chunked

    k = jax.random.split(jax.random.key(SEED), 7)
    r = value_heads // key_heads
    unit = reference.l2norm
    q = unit(jax.nn.silu(jax.random.normal(
        k[0], (1, positions, key_heads, head_dim)))) * head_dim ** -0.5
    kk = unit(jax.nn.silu(jax.random.normal(
        k[1], (1, positions, key_heads, head_dim))))
    v = jax.nn.silu(jax.random.normal(
        k[2], (1, positions, value_heads, head_dim)))
    a = jax.random.uniform(k[3], (value_heads,), minval=0.0,
                           maxval=16.0).at[0].set(1e-3)
    g = -a * jax.nn.softplus(
        jax.random.normal(k[4], (1, positions, value_heads)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(k[5], g.shape))
    weight = jax.random.normal(k[6], v.shape)
    args = (q, kk, v, g, beta)

    def rule_of(how, chunk, per, dtype=jnp.bfloat16):
        return lambda *t: gated_delta_chunked(*t, chunk, dtype, per,
                                              _inverse_by(how))

    def with_grads(fn):
        # the weights are an ARGUMENT: closed over, 268 MB of them would be
        # a constant of every compiled program
        return lambda w, *t: jax.value_and_grad(
            lambda *u: jnp.sum(w * fn(*u)), range(5))(*t)

    facts: Dict[str, Any] = {}
    for how, chunk, per in calls:
        rule = rule_of(how, chunk, per)
        tag = f"{how}_q{chunk}_k{min(per, key_heads)}"
        for name, fn, given in (("fwd", rule, args),
                                ("fwd_bwd", with_grads(rule),
                                 (weight,) + args)):
            facts[f"{tag}_{name}_compile_s"], facts[f"{tag}_{name}_ms"], _ = (
                _timed(fn, given, repeats))
    facts.update(_delta_block(positions, dim, key_heads, value_heads,
                              head_dim, calls[0][1], repeats))
    _say("delta.timed", **facts)    # a failed check below keeps the readings

    # one key head and its value heads against the recurrence
    n = min(check_positions, positions)
    few = tuple(t[:, :n, :h] for t, h in zip(args, (1, 1, r, r, r)))
    w_few = weight[:, :n, :r]

    def recurrence(q, k, v, g, beta):
        q, k = (jnp.repeat(t[0], r, axis=1) for t in (q, k))
        return reference.delta_rule(q, k, v[0], g[0], beta[0],
                                    lean=True)[None]

    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(with_grads(recurrence))(w_few, *few)
        o_want = jax.jit(recurrence)(*few)
    _, chunk, per = calls[0]
    # every way of making ``T`` among the calls, with float32 operands at
    # the highest precision (a TPU's default rounds a float32 product's
    # operands to bfloat16) and with the cell's own
    for how in dict.fromkeys(c[0] for c in calls):
        for name, dtype, precision, tol in (
                ("float32", jnp.float32, "highest", 1e-3),
                ("bfloat16", jnp.bfloat16, None, ATTN_BF16_TOL)):
            rule = rule_of(how, chunk, per, dtype)
            with jax.default_matmul_precision(precision):
                _, got = jax.jit(with_grads(rule))(w_few, *few)
                o_got = jax.jit(rule)(*few)
            errs = [float(jnp.max(jnp.abs(a - w)) / jnp.max(jnp.abs(w)))
                    for a, w in zip((o_got,) + got, (o_want,) + want)]
            facts[f"rel_err_{how}_{name}_o_dq_dk_dv_dg_dbeta"] = [
                float(f"{e:.3g}") for e in errs]
            if not max(errs) <= tol:        # a NaN fails too
                raise AssertionError(
                    f"delta {how} {name}: relative error {errs} (o, dq, dk, "
                    f"dv, dg, dbeta) > {tol}")
    return facts


def _timed(fn, args, repeats: int) -> Tuple[float, float, Any]:
    """(compile seconds, ms a call by this process's clock around
    ``repeats`` calls it waits for, the last result) of ``jit(fn)``."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    res = jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        res = compiled(*args)
    jax.block_until_ready(res)
    return (round(compile_s, 2),
            round((time.perf_counter() - t0) / repeats * 1e3, 3), res)


def _keye_layer(positions: int, dim: int, heads: int, kv_heads: int,
                head_dim: int, index_heads: int, index_dim: int, topk: int,
                chunk: int, dtype=None):
    """``keye-train-16k``'s configuration of one layer, its indexer's
    parameters at the configuration file's scales and a normed input of
    one sequence."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import keye_moe

    cfg = keye_moe.KeyeMoEConfig(
        dim=dim, n_heads=heads, n_kv_heads=kv_heads, head_dim=head_dim,
        layer_kinds=("sparse",), index_heads=index_heads,
        index_dim=index_dim, index_topk=topk, index_chunk=chunk,
        compute_dtype=dtype or jnp.bfloat16)
    k = jax.random.split(jax.random.key(SEED), 6)
    p = {"wq_i": 0.03 * jax.random.normal(k[0], (dim, index_heads * index_dim)),
         "wk_i": 0.03 * jax.random.normal(k[1], (dim, index_dim)),
         "ww_i": 0.03 * jax.random.normal(k[2], (dim, index_heads)),
         "k_i_norm": jnp.ones((index_dim,)), "k_i_bias": jnp.zeros((index_dim,))}
    u = jax.random.normal(k[3], (1, positions, dim))
    u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True))
    return cfg, p, u, k[4:]


def stage_select(positions: int = 16384, dim: int = 2048,
                 index_heads: int = 16, index_dim: int = 64,
                 topk: int = 2048, chunk: int = 512, top_k_chunks: int = 2,
                 repeats: int = 3) -> Dict[str, Any]:
    """``models/keye_moe``'s indexer and selection as ``keye-train-16k``
    calls them, one sequence of ``positions`` rows over ``positions`` keys
    in chunks of ``chunk`` rows: ms for the indexer's three products, for
    the whole selection by the counting search (scores and thresholds, 32
    passes over a chunk's integer keys) and, on the LAST ``top_k_chunks``
    chunks alone, ms a chunk by the search and by ``lax.top_k`` with a
    scatter of its indices, whose sets must be equal; a row's spread of
    scores; and the share of the selected keys that bfloat16 operands
    decide otherwise than float32 ones at the highest precision (what the
    cell's comparison has to allow)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import keye_moe

    cfg, p, u, _ = _keye_layer(positions, dim, 4, 2, 8, index_heads,
                               index_dim, topk, chunk)
    facts: Dict[str, Any] = {}
    operands = lambda u, p: keye_moe.index_operands(u, p, cfg)
    facts["operands_compile_s"], facts["operands_ms"], (qi, ki, w) = _timed(
        operands, (u, p), repeats)
    whole = lambda qi, ki, w: keye_moe.selection(qi, ki, w, cfg)
    facts["select_compile_s"], facts["select_ms"], chosen = _timed(
        whole, (qi, ki, w), repeats)
    rows = min(chunk, positions)
    first = positions - top_k_chunks * rows
    tail = (qi[:, first:], ki, w[:, first:])

    def scores_of(qi_t, ki, w_t, dtype=cfg.compute_dtype):
        return jax.lax.map(
            lambda c: keye_moe._scores(c[0], ki, c[1], dtype),
            (keye_moe._chunks(qi_t, rows), keye_moe._chunks(w_t, rows)))

    def by_search(qi_t, ki, w_t):
        return jax.lax.map(
            lambda c: keye_moe.select(c[1], cfg, first + c[0] * rows),
            (jnp.arange(top_k_chunks), scores_of(qi_t, ki, w_t)))

    def by_top_k(qi_t, ki, w_t):
        def one(c):
            n, x = c
            t = first + n * rows + jnp.arange(rows)
            causal = jnp.arange(positions)[None, :] <= t[:, None]
            x = jnp.where(causal, jnp.where(x == 0, 0.0, x), -jnp.inf)
            _, at = jax.lax.top_k(x, min(topk, positions))
            mine = jnp.zeros(x.shape, bool).at[
                0, jnp.arange(rows)[:, None], at[0]].set(True)
            return (mine & causal).astype(jnp.int8)
        return jax.lax.map(one, (jnp.arange(top_k_chunks),
                                 scores_of(qi_t, ki, w_t)))

    _, both_ms, scored = _timed(scores_of, tail, repeats)
    facts["search_compile_s"], ms, got = _timed(by_search, tail, repeats)
    facts["search_ms_chunk"] = round((ms - both_ms) / top_k_chunks, 3)
    facts["top_k_compile_s"], ms, want = _timed(by_top_k, tail, repeats)
    facts["top_k_ms_chunk"] = round((ms - both_ms) / top_k_chunks, 3)
    facts["scores_ms_chunk"] = round(both_ms / top_k_chunks, 3)
    if not bool(jnp.all(got == want)):
        raise AssertionError(
            f"select: {int(jnp.sum(got != want))} keys of the search's set "
            f"are not lax.top_k's")
    if not bool(jnp.all(got.reshape(-1, positions)
                        == chosen[0, first:])):
        raise AssertionError("select: a chunk alone selects otherwise "
                             "than the whole sequence's map")
    last = scored[-1, 0, -1]
    facts["row_spread"] = round(float(jnp.std(last)), 4)
    kept = int(jnp.sum(got))
    facts["selected_keys_tail"] = kept
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(lambda qi_t, ki, w_t: jax.lax.map(
            lambda c: keye_moe.select(c[1], cfg, first + c[0] * rows),
            (jnp.arange(top_k_chunks),
             scores_of(qi_t, ki, w_t, jnp.float32))))(*tail)
    facts["bfloat16_decides_otherwise_share"] = round(
        float(jnp.sum(got != exact)) / 2 / kept, 5)
    return facts


def flash_selected(shape=(1, 32, 16384, 128), hkv: int = 4,
                   blocks=((1024, 1024), (512, 1024)), topk: int = 2048,
                   repeats: int = 10, ref_heads: int = 2) -> Dict[str, Any]:
    """The flash kernels under a selection as ``keye-train-16k`` calls
    them (int8 [B, S, S], the ``topk`` largest of seeded scores a causal
    row, shared by the heads), at each of ``blocks``: compile seconds and
    ms a call of the forward with its residual, dQ, and dK with dV, beside
    the same call without the operand (whole tiles too); and output and
    gradients against float32 attention under the same mask on
    ``ref_heads`` query heads (max|err| over max|reference|)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import keye_moe
    from multiverso_tpu.models.mla_moe import _xla_attention
    from multiverso_tpu.ops import attention_kernels as ak

    interpret = ak._resolve_interpret(None)
    b, h, s, d = shape
    rng = np.random.default_rng(SEED + 54)
    q, g = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.bfloat16)
            for _ in range(2))
    cfg = keye_moe.KeyeMoEConfig(index_topk=topk, index_chunk=min(512, s))
    rows = min(512, s)
    chosen = jax.jit(lambda key: jnp.moveaxis(jax.lax.map(
        lambda n: keye_moe.select(jax.random.normal(
            jax.random.fold_in(key, n), (b, rows, s)), cfg, n * rows),
        jnp.arange(s // rows)), 0, 1).reshape(b, s, s))(jax.random.key(SEED))
    facts: Dict[str, Any] = {
        "selected_share": round(float(jnp.mean(chosen.astype(jnp.float32)))
                                * 2 * s / (s + 1), 4)}
    few = lambda t, n: t[:1, :n]
    small = (few(q, ref_heads), few(k, 1), few(v, 1))
    gs = few(g, ref_heads)

    def fwd_and_grads(fn, *args):
        res, vjp = jax.vjp(fn, *args)
        return (res,) + vjp(gs.astype(res.dtype))

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: fwd_and_grads(
            lambda q, k, v: _xla_attention(q, k, v, None, chosen[:1]),
            *(t.astype(jnp.float32) for t in a)))(*small)
    for bq, bk in blocks:
        for tag, select in ((f"{bq}x{bk}_select", chosen),
                            (f"{bq}x{bk}_causal", None)):
            def forward(q, k, v):
                return ak._flash_forward(q, k, v, True, bq, bk, interpret,
                                         True, None, None, select)

            def backward(keep, q, k, v, o, lse, g):
                grads = ak._flash_backward(q, k, v, o, lse, g, True, bq, bk,
                                           interpret, None, None, select)
                return grads[:1] if keep == 0 else grads[1:]

            o, lse = forward(q, k, v)
            for kernel, fn, args in (
                    ("fwd", forward, (q, k, v)),
                    ("dq", functools.partial(backward, 0),
                     (q, k, v, o, lse, g)),
                    ("dkv", functools.partial(backward, 1),
                     (q, k, v, o, lse, g))):
                (facts[f"{tag}_{kernel}_compile_s"],
                 facts[f"{tag}_{kernel}_ms"], _) = _timed(fn, args, repeats)
        got = jax.jit(lambda *a: fwd_and_grads(
            lambda q, k, v: ak.flash_attention(
                q, k, v, True, bq, bk, select=chosen[:1]), *a))(*small)
        errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - w))
                      / jnp.max(jnp.abs(w))) for a, w in zip(got, want)]
        if not max(errs) <= ATTN_BF16_TOL:      # a NaN fails too
            raise AssertionError(f"flash under a selection at {bq} x {bk}: "
                                 f"relative error {errs} > {ATTN_BF16_TOL}")
        facts[f"{bq}x{bk}_rel_err"] = [round(e, 5) for e in errs]
    return facts


def _term_kernels(cfg, qi, ki, w, chosen, chunks, repeats: int,
                  inner: int = 16) -> Dict[str, Any]:
    """The term's two kernels alone (``ops/index_kernels.chunk_calls``) on
    the chunks numbered ``chunks``: ms a call of the statistics' and of the
    gradients', whose walks end at the chunk's diagonal, so the time should
    grow with the chunk's number. A call is well under a dispatch's
    latency, so ``inner`` of them run in one program (a ``fori_loop`` whose
    chunk number XLA cannot tell is the same every trip). The target is a
    stand-in (uniform over a row's selected keys): the kernels' time does
    not depend on it."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops import attention_kernels, index_kernels

    b, s = ki.shape[:2]
    rows, dt = min(cfg.index_chunk, s), cfg.compute_dtype
    walk, stats, grads = index_kernels.chunk_calls(
        b, s, rows, cfg.index_heads, cfg.index_dim, dt,
        interpret=attention_kernels._resolve_interpret(None))

    def looped(call):
        def run(n, *args):
            trip = lambda i, acc: acc + call(
                jnp.minimum(n, n + i), *args)[0].ravel()[0]
            return jax.lax.fori_loop(0, inner, trip, jnp.zeros(()))
        return run

    facts: Dict[str, Any] = {"key_tile": walk.tile, "chunks": {}}
    for n in chunks:
        cut = slice(n * rows, (n + 1) * rows)
        live = chosen[:, cut] != 0
        pbar = live / jnp.maximum(jnp.sum(live, -1, keepdims=True), 1.0)
        number = jnp.full((1,), n, jnp.int32)
        args = (qi[:, cut].astype(dt).transpose(0, 2, 1, 3), ki.astype(dt),
                w[:, cut], chosen[:, cut], pbar.astype(jnp.float32))
        lse, _ = jax.jit(stats)(number, *args)
        compile_s, stats_ms, _ = _timed(looped(stats), (number,) + args,
                                        repeats)
        _, grads_ms, _ = _timed(
            looped(grads),
            (number,) + args + (lse, jnp.zeros(ki.shape, jnp.float32)),
            repeats)
        facts["chunks"][str(n)] = {
            "tiles": int(walk.last(n)) + 1,
            "stats_ms": round(stats_ms / inner, 4),
            "grads_ms": round(grads_ms / inner, 4),
            "stats_compile_s": compile_s}
    return facts


def stage_target(positions: int = 16384, dim: int = 2048, heads: int = 32,
                 kv_heads: int = 4, head_dim: int = 128,
                 index_heads: int = 16, index_dim: int = 64,
                 topk: int = 2048, chunk: int = 512, repeats: int = 3,
                 check_positions: int = 1024) -> Dict[str, Any]:
    """``models/keye_moe.index_loss`` as ``keye-train-16k`` calls it, one
    layer's term over one sequence, in both its forms side by side (the
    two kernels of ``ops/index_kernels.py``, which the cell runs, and XLA's
    whole arrays, ``xla_*``): compile seconds and ms a call forward and
    with the gradients to the indexer's operands (made in its forward), and
    how far the two forms' term and gradients are apart at this size; the
    kernels alone at the first, the middle and the last chunk
    (:func:`_term_kernels`); and on the first ``check_positions`` the term
    and those gradients of each form in float32 against plain autodiff of
    the definition over whole arrays (max|err| over max|reference|)."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import keye_moe

    cfg, p, u, keys = _keye_layer(positions, dim, heads, kv_heads, head_dim,
                                  index_heads, index_dim, topk, chunk)
    forms = (("", cfg._replace(attn="flash")),
             ("xla_", cfg._replace(attn="xla")))
    q = jax.random.normal(keys[0], (1, heads, positions, head_dim),
                          jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, kv_heads, positions, head_dim),
                          jnp.bfloat16)
    qi, ki, w = jax.jit(lambda u, p: keye_moe.index_operands(u, p, cfg))(u, p)
    chosen = jax.jit(lambda *a: keye_moe.selection(*a, cfg))(qi, ki, w)
    facts: Dict[str, Any] = {}
    both = []
    for tag, form in forms:
        term = lambda qi, ki, w: keye_moe.index_loss(
            qi, ki, w, q, k, chosen, form)
        facts[tag + "fwd_compile_s"], facts[tag + "fwd_ms"], value = _timed(
            term, (qi, ki, w), repeats)
        (facts[tag + "fwd_bwd_compile_s"], facts[tag + "fwd_bwd_ms"],
         (_, grads)) = _timed(jax.value_and_grad(term, (0, 1, 2)),
                              (qi, ki, w), repeats)
        facts[tag + "term"] = round(float(value), 5)
        both.append((value,) + tuple(grads))
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    facts["forms_rel_diff_term_dqi_dki_dw"] = [
        round(rel(a, b), 6) for a, b in zip(*both)]
    n_chunks = positions // min(chunk, positions)
    facts["kernels"] = _term_kernels(
        cfg, qi, ki, w, chosen,
        sorted({0, (n_chunks - 1) // 2, n_chunks - 1}), repeats)

    n = min(check_positions, positions)
    exact = cfg._replace(compute_dtype=jnp.float32,
                         index_chunk=min(chunk, n))
    qs, ks = (t[:, :, :n].astype(jnp.float32) for t in (q, k))
    few = (qi[:, :n], ki[:, :n], w[:, :n])
    picked = jax.jit(lambda *a: keye_moe.selection(*a, exact))(*few)

    def definition(qi, ki, w):
        live = picked != 0
        index = keye_moe._scores(qi, ki, w, jnp.float32)
        dots = jnp.einsum("bkgrd,bksd->bkgrs", qs.reshape(
            1, kv_heads, heads // kv_heads, n, head_dim), ks) / head_dim ** 0.5
        pbar = jnp.mean(jax.nn.softmax(jnp.where(
            live[:, None, None], dots, -jnp.inf), -1), (1, 2))
        logq = jax.nn.log_softmax(jnp.where(live, index, -jnp.inf), -1)
        return jnp.sum(jnp.where(live, jax.scipy.special.xlogy(pbar, pbar)
                                 - pbar * jnp.where(live, logq, 0.0),
                                 0.0)) / n

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(definition, (0, 1, 2)))(*few)
        for tag, form in forms:
            form = exact._replace(attn=form.attn)
            got = jax.jit(jax.value_and_grad(
                lambda *a: keye_moe.index_loss(*a, qs, ks, picked, form),
                (0, 1, 2)))(*few)
            errs = [rel(a, b) for a, b in
                    [(got[0], want[0])] + list(zip(got[1], want[1]))]
            if not max(errs) <= 1e-3:   # float32 on both sides; a NaN fails
                raise AssertionError(
                    f"target ({tag or 'kernels'}): relative error {errs} "
                    f"(term, dqI, dkI, dw) > 1e-3")
            facts[tag + "rel_err_term_dqi_dki_dw"] = [round(e, 6)
                                                      for e in errs]
    return facts


def stage_memory() -> Dict[str, Any]:
    """After the run every device holds bytes: every chip was used."""
    import jax
    used = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.devices()]
    if not all(used):
        raise AssertionError(f"a device holds no bytes: {used}")
    return {"bytes_in_use": used}


def _cache_counters() -> Dict[str, int]:
    """Count persistent-compile-cache hits and writes from here on."""
    import jax.monitoring

    counts = {"hits": 0, "writes": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "writes"}

    def on_event(event: str, **_kw) -> None:
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def result_line(ok: bool, device: Dict[str, Any]) -> Dict[str, Any]:
    """The last stdout line once a TPU was found: these keys and no
    others, the device as JAX reports it. The rest goes on ``summary``."""
    return {"ok": ok,
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


STAGES: Tuple[Tuple[str, Callable[[], Dict[str, Any]]], ...] = (
    ("tables", stage_tables), ("we", stage_we), ("rows", stage_rows),
    ("ps", stage_ps),
    ("lm", stage_lm), ("flash", lambda: stage_flash(selected={})),
    ("ssd", stage_ssd),
    ("conv", stage_conv), ("taps", stage_taps), ("heads", stage_heads),
    ("delta", stage_delta), ("hc", stage_hc), ("loop", stage_loop),
    ("select", stage_select), ("target", stage_target),
    ("memory", stage_memory))


def main() -> int:
    t_start = time.perf_counter()
    try:
        import multiverso_tpu as mv
    except ImportError as e:
        print(f"chip_smoke: the multiverso_tpu package is not beside this "
              f"script: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    try:
        device = stage_device()
    except NoChip as e:
        print(f"chip_smoke: no TPU, nothing checked: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    _say("device", ok=True, **device)
    cache = _cache_counters()
    from multiverso_tpu import native
    from multiverso_tpu.ps import native as ps_native
    _say("native", libmv_data=native.available(),
         libmv_ps=ps_native.available(),
         build_failures={n: native.build_failure(n)
                         for n in ("libmv_data.so", "libmv_ps.so")
                         if native.build_failure(n)})

    verdicts: Dict[str, str] = {}
    for name, stage in STAGES:
        t0 = time.perf_counter()
        try:
            facts = stage()
            verdicts[name] = "pass"
            _say(name, ok=True, seconds=round(time.perf_counter() - t0, 1),
                 **facts)
        except Exception as e:   # noqa: BLE001 — report, run the rest
            verdicts[name] = "FAIL"
            traceback.print_exc()
            _say(name, ok=False, seconds=round(time.perf_counter() - t0, 1),
                 error=f"{type(e).__name__}: {e}"[:2000])
    mv.shutdown()

    ok = all(v == "pass" for v in verdicts.values())
    _say("summary", ok=ok, stages=verdicts,
         compile_cache={"dir": device["compile_cache_dir"], **cache},
         wall_s=round(time.perf_counter() - t_start, 1), claim=None)
    print(json.dumps(result_line(ok, device)), flush=True)
    if not ok:
        print(f"chip_smoke: FAILED {verdicts}", file=sys.stderr)
        return EXIT_STAGE_FAILED
    return 0


if __name__ == "__main__":
    sys.exit(main())
