"""``WordEmbedding.train_fused`` back to back on tables that are
row-sharded over the host's chips: ``we_fused``'s loop (one call per
equal chunk of the seeded stream, epoch after epoch until the window
closes; work is the words of completed calls), on a vocabulary that no
single chip holds, so nothing table-sized may cross to the host.

What differs from ``we_fused``, and why:

* the vocabulary is counts alone (no 12M strings), and the program must
  be one that trains such a vocabulary and says which pool a call drew
  (``WordEmbedding.fused_pool``): a program without them cannot run this
  cell and is refused at the start of set-up, before anything is built;
* ``table_shapes`` is the PER-SHARD padded shape (``[3000001, 300]``:
  what the partitioned program's operations name in the trace) and
  ``must_move_bytes`` is PER CHIP (the total by ``shapes.py`` over the
  chips: ``trace_reduce``'s ``table_s`` is a mean over chips); no table
  copy is reckoned in, the program makes none;
* ``facts`` carries the window's ``update_rows_by_shard`` and
  ``allreduce_bytes``, summed from the program's ``we.fused`` spans;
* the check compares touched rows and per-shard digests computed on the
  device (below); ``we_fused`` reads both tables whole, four times, which
  here would be 4 x 14.4 GB through the host.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import gen, shapes, w2v_setup, weights

SPAN = "we.fused"


def setup(cell) -> Dict[str, Any]:
    from multiverso_tpu.apps.word_embedding import WEConfig, WordEmbedding
    from multiverso_tpu.data.dictionary import Dictionary

    if not hasattr(WordEmbedding, "fused_pool"):
        raise SystemExit(
            f"cell {cell.name!r}: this program's WordEmbedding has no "
            "fused_pool and takes no vocabulary of counts alone; it cannot "
            "run the configuration")
    cfg, tr = cell.config, cell.traffic
    vocab, law = int(cfg["vocab_size"]), cfg["corpus_law"]
    with cell.timed("vocab_counts"):
        counts = gen.vocab_counts(vocab, int(cfg["vocab_corpus_words"]),
                                  int(cfg["min_count"]), law)
        dictionary = Dictionary.from_counts(None, counts,
                                            int(cfg["min_count"]))
    we_cfg = WEConfig(
        size=cfg["vector_size"], window=cfg["window"],
        negative=cfg["negative"], alpha=cfg["alpha"], sample=cfg["sample"],
        min_count=cfg["min_count"], epoch=1, seed=tr["program_seed"],
        **tr["program"])
    with cell.timed("tables_init"):
        # embed_in drawn a shard at a time on the host, embed_out zeros
        # made on the devices
        we = WordEmbedding(we_cfg, dictionary)
    with cell.timed("weights_from_seed"):
        # the program's own law for embed_in (ref communicator.cpp:20)
        weights.seed_table(we.table_in, cell.seed, 0.5 / we_cfg.size)
    state: Dict[str, Any] = {"we": we, "cfg": we_cfg, "counts": counts,
                             "dictionary": dictionary, "law": law}
    with cell.timed("corpus"):
        state["chunks"] = w2v_setup.chunks(
            cell, state, int(tr["words_per_call"]), int(tr["chunks"]))
    state["distinct_rows"] = int(np.unique(np.concatenate(
        state["chunks"])).size)
    # every chunk once: the first call lays both tables out for their row
    # programs and compiles the donated epoch, and each call leaves its
    # chunk's pair batches on the device (the program's pair cache),
    # where the window's calls find them
    for k, chunk in enumerate(state["chunks"]):
        with cell.timed(f"warmup_call_{k + 1}"):
            out = we.train_fused(chunk, epochs=1)
        state.setdefault("loss_before", out["loss"])
    return state


def _span_counts(since_us: float) -> Dict[str, Any]:
    """The window's ``update_rows_by_shard`` and ``allreduce_bytes``,
    summed over the program's ``we.fused`` spans begun at or after
    ``since_us`` (the ring's clock, microseconds)."""
    from multiverso_tpu.telemetry import trace

    calls = [e["args"] for e in trace.events()
             if e["name"] == SPAN and e["ts"] >= since_us
             and "update_rows_by_shard" in e["args"]]
    if not calls:
        return {}
    return {"shards": calls[-1]["shards"],
            "update_rows_by_shard": np.sum(
                [c["update_rows_by_shard"] for c in calls], axis=0).tolist(),
            "allreduce_bytes": int(sum(c["allreduce_bytes"]
                                       for c in calls))}


def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    we, chunks = state["we"], state["chunks"]
    losses, call_s, words, i = [], [], 0, 0
    since_us = time.time_ns() / 1e3
    t0 = now = time.perf_counter()
    while now - t0 < seconds:
        chunk = chunks[i % len(chunks)]
        with jax.profiler.TraceAnnotation("bench.train_fused"):
            out = we.train_fused(chunk, epochs=1)
        losses.append(out["loss"])
        call_s.append(out["seconds"])
        words += int(chunk.size)
        i += 1
        now = time.perf_counter()
    t = we.table_in
    width = t.padded_shape[1]
    batch, pool = state["cfg"].batch_size, state["cfg"].shared_negatives
    # per batch: gather B centres, B contexts and the pool, and scatter-add
    # as many; the algorithm's bytes, shared out over the chips
    per_batch = 2 * batch + pool
    moved = (i * (out["pairs"] // batch)
             * (shapes.row_gather_bytes(per_batch, width)
                + shapes.scatter_add_bytes(per_batch, width))) // t.num_shards
    facts = {"calls": i, "batch": batch, "pool": pool,
             "words_per_call": int(chunks[0].size),
             "pairs_per_call": out["pairs"],
             "distinct_rows_in_corpus": state["distinct_rows"],
             "loss_first": losses[0], "loss_last": losses[-1]}
    facts.update(_span_counts(since_us))
    return {"work": words, "must_move_bytes": moved, "elapsed_s": now - t0,
            "attempted": i, "failed": 0, "losses": losses,
            "spans_ms": {"call": [s * 1e3 for s in call_s]},
            "table_shapes": [tuple(t.sharding.shard_shape(t.padded_shape))],
            "facts": facts}


@functools.partial(jax.jit, static_argnums=2)
def _probe(data: jax.Array, ids: jax.Array, shards: int
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Of one row-sharded table, on the device: the rows ``ids`` (the
    touched rows, a few thousand: all that goes to the host), a digest
    per shard of every OTHER row (the sum of their bits as ``uint32``,
    wrapping), and whether each shard is finite throughout."""
    rows = jnp.take(data, ids, axis=0)
    untouched = jnp.ones(data.shape[0], jnp.uint32).at[ids].set(0)
    bits = jax.lax.bitcast_convert_type(data, jnp.uint32)
    digest = (bits.sum(axis=1, dtype=jnp.uint32) * untouched).reshape(
        shards, -1).sum(axis=1, dtype=jnp.uint32)
    finite = jnp.isfinite(data).all(axis=1).reshape(shards, -1).all(axis=1)
    return rows, digest, finite


def _probe_both(we, in_ids: np.ndarray, out_ids: np.ndarray, n: int):
    """``_probe`` of both tables; the id lists padded to ``n`` with their
    first id (a touched row again), so one program serves every call."""
    out = []
    for table, ids in ((we.table_in, in_ids), (we.table_out, out_ids)):
        padded = np.full(n, ids[0], np.int32)
        padded[:ids.size] = ids
        rows, digest, finite = _probe(table.raw(), jnp.asarray(padded),
                                      table.num_shards)
        out.append((np.asarray(rows)[:ids.size], np.asarray(digest),
                    np.asarray(finite)))
    return out


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """After the window, one batch through ``train_fused`` itself: a piece
    of the stream that makes one batch of pairs, so the call is the
    measured epoch program with a scan of one. The pairs are the arrays
    the epoch scans (the program's pair cache); the pool is what the
    program says the call will draw (``WordEmbedding.fused_pool``), and
    said again afterwards.

    Held, with nothing table-sized leaving the device:

    * the touched rows of both tables (the batch's distinct centres;
      its distinct contexts and pool words: at most 16,640 a table),
      gathered on the device before and after, against
      ``reference/w2v_sgns.step`` on the same pairs and pool
      (``w2v_setup.compare_batch`` on the gathered rows): deltas within
      ``TOL_BF16`` of the largest reference delta where the program
      computes in bfloat16 (on a TPU; ``TOL_F32`` on the CPU: the reasons
      are with the constants in ``w2v_setup``), at least 90% of them
      moved, the loss within the same tolerance;
    * no other row moved: per shard and table, the ``uint32`` sum of the
      bits of every row but the touched ones is the same number before
      and after;
    * the window's last loss lies under the first warm-up call's
      (``w2v_setup.loss_falls``); every shard of both tables is finite.
    """
    we, cfg, facts = state["we"], state["cfg"], run["facts"]
    pool = cfg.shared_negatives
    per_word = facts["pairs_per_call"] / facts["words_per_call"]
    ids = state["chunks"][0][:int(1.5 * cfg.batch_size / per_word)]
    cb, xb, _ = we._device_pairs(ids)
    centers, contexts = np.asarray(cb[0]), np.asarray(xb[0])
    negs = np.asarray(we.fused_pool(next_batches=1)[0], np.int32)
    in_ids = np.unique(centers)
    out_ids = np.unique(np.concatenate([contexts, negs]))
    n = 2 * cfg.batch_size + pool
    old = _probe_both(we, in_ids, out_ids, n)
    out = we.train_fused(ids, epochs=1)
    new = _probe_both(we, in_ids, out_ids, n)
    cd = we.fused_compute_dtype
    tol = (w2v_setup.TOL_BF16 if cd == jnp.bfloat16 else w2v_setup.TOL_F32)
    # the gathered rows stand for the tables: ids become positions in them
    detail = w2v_setup.compare_batch(
        (old[0][0], old[1][0]), (new[0][0], new[1][0]),
        np.searchsorted(in_ids, centers), np.searchsorted(out_ids, contexts),
        np.searchsorted(out_ids, negs), out["loss"], cfg.alpha,
        cfg.negative / pool, tol)
    detail["one_batch"] = int(cb.shape[0]) == 1
    detail["pool_as_foretold"] = bool((we.fused_pool() == negs).all())
    for k, side in ((0, "in"), (1, "out")):
        # compare_batch saw touched rows only; the rest is the digests'
        detail[f"{side}_others_unchanged"] = bool(
            (old[k][1] == new[k][1]).all())
        detail[f"{side}_digests"] = [int(d) for d in new[k][1]]
    detail["shards"] = int(we.table_in.num_shards)
    detail["compute_dtype"] = str(jnp.dtype(cd))
    detail["loss_before"] = state["loss_before"]
    detail["loss_falls"] = w2v_setup.loss_falls(state["loss_before"],
                                                run["losses"][-1])
    detail["tables_finite"] = bool(new[0][2].all() and new[1][2].all())
    return {"correct": bool(
        detail["step_agrees"] and detail["one_batch"]
        and detail["pool_as_foretold"] and detail["in_others_unchanged"]
        and detail["out_others_unchanged"] and detail["loss_falls"]
        and detail["tables_finite"]), "detail": detail}
