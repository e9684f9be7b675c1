"""``WordEmbedding.train_fused`` back to back: one call per equal chunk
of the seeded stream, epoch after epoch over the corpus until the window
closes. Work is the words of completed calls (the words handed to the
trainer, as the program's own ``words_per_sec`` counts them: after
frequent-word subsampling)."""

from __future__ import annotations

import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import shapes, w2v_setup


def setup(cell) -> Dict[str, Any]:
    tr = cell.traffic
    state = w2v_setup.build(cell)
    with cell.timed("corpus"):
        state["chunks"] = w2v_setup.chunks(
            cell, state, int(tr["words_per_call"]), int(tr["chunks"]))
    state["distinct_rows"] = int(np.unique(np.concatenate(
        state["chunks"])).size)
    we = state["we"]
    # every chunk once: the first two calls compile the donated epoch
    # program for the layout a fresh table has and for the one it hands
    # back, and each call leaves its chunk's pair batches on the device
    # (the program's pair cache), where the window's calls find them
    for k, chunk in enumerate(state["chunks"]):
        with cell.timed(f"warmup_call_{k + 1}"):
            out = we.train_fused(chunk, epochs=1)
        state.setdefault("loss_before", out["loss"])
    return state


def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    we, chunks = state["we"], state["chunks"]
    losses, call_s, words, i = [], [], 0, 0
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
    rows, width = t.padded_shape
    batch, pool = state["cfg"].batch_size, state["cfg"].shared_negatives
    # per batch: gather B centres, B contexts and the pool, and scatter-add
    # as many; per call two whole-table copies (the program chains its
    # donated epoch from copies of both tables)
    per_batch = 2 * batch + pool
    moved = (i * (out["pairs"] // batch)
             * (shapes.row_gather_bytes(per_batch, width)
                + shapes.scatter_add_bytes(per_batch, width))
             + i * 2 * shapes.table_copy_bytes(rows, width))
    return {"work": words, "must_move_bytes": moved, "elapsed_s": now - t0, "attempted": i, "failed": 0,
            "losses": losses, "spans_ms": {"call": [s * 1e3 for s in call_s]},
            "table_shapes": [tuple(t.padded_shape)],
            "facts": {"calls": i, "batch": batch, "pool": pool,
                      "words_per_call": int(chunks[0].size),
                      "pairs_per_call": out["pairs"],
                      "distinct_rows_in_corpus": state["distinct_rows"],
                      "loss_first": losses[0], "loss_last": losses[-1]}}


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """After the window, one batch through ``train_fused`` itself: a piece
    of the stream that makes one batch of pairs, so the call is the
    measured epoch program with a scan of one. Both tables are read to
    the host before and after, and the rows that moved are held to
    ``reference/w2v_sgns`` on the same pairs and negatives
    (``w2v_setup.compare_batch``). The pairs are the arrays the epoch
    scans (the program's pair cache); the pool is read from the sampler
    state the call hands back (``we._lcg``, through the program's slot
    table, as the epoch draws it). The window's last loss lies under the
    first warm-up call's (``w2v_setup.loss_falls``); both tables are
    finite."""
    from multiverso_tpu.models import word2vec as w2v

    we, cfg, facts = state["we"], state["cfg"], run["facts"]
    pool = cfg.shared_negatives
    per_word = facts["pairs_per_call"] / facts["words_per_call"]
    ids = state["chunks"][0][:int(1.5 * cfg.batch_size / per_word)]
    centers, contexts, _ = we._device_pairs(ids)
    old = w2v_setup.host_tables(we)
    out = we.train_fused(ids, epochs=1)
    new = w2v_setup.host_tables(we)
    slots = w2v.build_negative_table(we.unigram, 1 << 20)
    negs = slots[np.asarray(we._lcg) >> np.uint32(12)]
    cd = we.fused_compute_dtype
    tol = (w2v_setup.TOL_BF16 if cd == jnp.bfloat16 else w2v_setup.TOL_F32)
    detail = w2v_setup.compare_batch(
        old, new, np.asarray(centers[0]), np.asarray(contexts[0]), negs,
        out["loss"], cfg.alpha, cfg.negative / pool, tol)
    detail["one_batch"] = int(centers.shape[0]) == 1
    detail["compute_dtype"] = str(jnp.dtype(cd))
    detail["loss_before"] = state["loss_before"]
    detail["loss_falls"] = w2v_setup.loss_falls(state["loss_before"],
                                                run["losses"][-1])
    detail["tables_finite"] = w2v_setup.tables_finite(we)
    return {"correct": bool(detail["step_agrees"] and detail["one_batch"]
                            and detail["loss_falls"]
                            and detail["tables_finite"]), "detail": detail}
