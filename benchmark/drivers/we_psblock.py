"""``WordEmbedding.train_ps_blocks`` on the device plane: pull rows,
train, push deltas per block, the prepare threads in the loop.

A user makes one call over a whole corpus, thousands of blocks long, so
the window is one long call too: set-up times a short call, sizes the
window's call from it (a few percent over ``--seconds``), and the window
repeats that call only if it came out short. Calls of five blocks, the
first version, put a pipeline fill and a drain every 2.4 s; the fill
took 70 ms more or less from one process to the next and the cell read
105k or 108k words/s by the process, a spread of 3.4% (PERF.md, Findings,
PR 24). Work is the words of the blocks of completed calls."""

from __future__ import annotations

import math
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import w2v_setup

OVERSHOOT = 1.04     # the sized call aims this far past --seconds


def setup(cell) -> Dict[str, Any]:
    tr = cell.traffic
    state = w2v_setup.build(cell)
    block = int(tr["program"]["data_block_size"])
    pilot = int(tr["pilot_blocks"])
    with cell.timed("corpus"):
        state["stream"], = w2v_setup.chunks(
            cell, state, block * int(tr["corpus_blocks"]), 1)
    we, stream = state["we"], state["stream"]
    with cell.timed("warmup"):
        # equal blocks of one law share their pair and row buckets, so a
        # short call compiles what every block uses
        we.train_ps_blocks(stream[-pilot * block:], epochs=1)
    with cell.timed("pilot"):
        t0 = time.perf_counter()
        out = we.train_ps_blocks(stream[-pilot * block:], epochs=1)
        per_block = (time.perf_counter() - t0) / pilot
        n = min(int(tr["corpus_blocks"]),
                max(pilot, math.ceil(cell.seconds * OVERSHOOT / per_block)))
        # the call ends by stacking its n block losses (replicated device
        # scalars): a program per n, compiled here and not in the window
        import multiverso_tpu as mv
        zero = jax.device_put(jnp.zeros((), jnp.float32), jax.sharding.
                              NamedSharding(mv.mesh(),
                                            jax.sharding.PartitionSpec()))
        np.asarray(jnp.stack([zero] * n))
    state.update(block=block, blocks_per_call=n, pilot_loss=out["loss"])
    return state


def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    from multiverso_tpu.utils.dashboard import Dashboard

    we, stream, block = state["we"], state["stream"], state["block"]
    per_call = state["blocks_per_call"] * block
    Dashboard.reset()        # the program's monitors count this window only
    losses, call_s, words, i = [], [], 0, 0
    t0 = now = time.perf_counter()
    while now - t0 < seconds:
        lo = (i * per_call) % (stream.size - per_call + 1)
        with jax.profiler.TraceAnnotation("bench.train_ps_blocks"):
            out = we.train_ps_blocks(stream[lo:lo + per_call], epochs=1)
        losses.append(out["loss"])
        call_s.append(out["seconds"])
        words += per_call
        i += 1
        now = time.perf_counter()
    monitors = {name: {"count": m.count, "p50_ms": m.p50_ms,
                       "mean_ms": m.average_ms}
                for name, m in Dashboard.snapshot().items()
                if name.startswith("we.")}
    blocks = words // block
    return {"work": words, "elapsed_s": now - t0, "attempted": blocks,
            "failed": 0, "losses": losses, "monitors": monitors,
            # a block's time by the benchmark's clock: a call's seconds
            # over its blocks (the program's we.block monitor on the device
            # plane times an asynchronous dispatch, about 1 ms)
            "spans_ms": {"call": [s * 1e3 for s in call_s],
                         "block": [s * 1e3 / state["blocks_per_call"]
                                   for s in call_s]},
            "table_shapes": [tuple(we.table_in.padded_shape)],
            "facts": {"blocks_per_call": state["blocks_per_call"], "calls": i,
                      "device_plane": bool(we._use_device_plane(1)),
                      "loss_first": losses[0], "loss_last": losses[-1]}}


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """After the window, twice against ``reference/w2v_sgns`` on the live
    tables at full width, against copies of both tables taken on the
    device before each (this path leaves room: 6.6 of 16.9 GB at peak).
    First one seeded batch through what a block is made of (pull the
    touched rows, ``models/word2vec.skipgram_ns_step`` on them, push new -
    old through the tables' ``functional_add_rows``), held row for row
    (``w2v_setup.compare_batch``). Then one block of the stream through
    ``train_ps_blocks`` itself, held in size (``w2v_setup.compare_block``:
    the program draws that block's windows and negatives itself; the
    reference draws its own from the program's sampling table). That
    block's loss lies under the loss of the call before the window
    (``w2v_setup.loss_falls``); both tables are finite."""
    from multiverso_tpu.models import word2vec as w2v

    we, cfg = state["we"], state["cfg"]
    centers, contexts, negs = w2v_setup.seeded_batch(
        state["stream"], len(state["dictionary"]), cfg.batch_size,
        (cfg.batch_size, cfg.negative), 1)
    in_ids = np.unique(centers)
    out_ids = np.unique(np.concatenate([contexts, negs.reshape(-1)]))
    local = [jnp.asarray(np.searchsorted(in_ids, centers), jnp.int32),
             jnp.asarray(np.searchsorted(out_ids, contexts), jnp.int32),
             jnp.asarray(np.searchsorted(out_ids, negs), jnp.int32)]
    t_in, t_out = we.table_in, we.table_out
    s_in, s_out = t_in.state, t_out.state

    def block_of_one(win, wout):
        ids_i, ids_o = jnp.asarray(in_ids), jnp.asarray(out_ids)
        ri, ro = jnp.take(win, ids_i, axis=0), jnp.take(wout, ids_o, axis=0)
        ni, no, loss = w2v.skipgram_ns_step(ri, ro, *local, cfg.alpha)
        a_in = t_in.functional_add_rows(
            {"data": win, "ustate": s_in["ustate"]}, ids_i, ni - ri)
        a_out = t_out.functional_add_rows(
            {"data": wout, "ustate": s_out["ustate"]}, ids_o, no - ro)
        return a_in["data"], a_out["data"], loss

    # a float32 step at XLA's default matmul precision, which on the TPU
    # may multiply in bf16 passes
    on_tpu = jax.devices()[0].platform == "tpu"
    tol = w2v_setup.TOL_BF16 if on_tpu else w2v_setup.TOL_F32
    old = w2v_setup.device_tables(we)
    win, wout, loss = jax.jit(block_of_one, donate_argnums=(0, 1))(
        s_in["data"], s_out["data"])
    t_in.adopt({"data": win, "ustate": s_in["ustate"]})
    t_out.adopt({"data": wout, "ustate": s_out["ustate"]})
    detail = w2v_setup.compare_batch(
        old, (t_in.raw(), t_out.raw()), centers, contexts, negs, float(loss),
        cfg.alpha, 1.0, tol)
    del old
    mid = w2v_setup.device_tables(we)
    block = state["stream"][:state["block"]]
    out = we.train_ps_blocks(block, epochs=1)
    detail.update(w2v_setup.compare_block(
        mid, (t_in.raw(), t_out.raw()), block, state, we._neg_host, 1))
    detail["loss_before"], detail["loss_after"] = state["pilot_loss"], out["loss"]
    detail["loss_falls"] = w2v_setup.loss_falls(state["pilot_loss"],
                                                out["loss"])
    detail["tables_finite"] = w2v_setup.tables_finite(we)
    return {"correct": bool(detail["step_agrees"] and detail["block_agrees"]
                            and detail["loss_falls"]
                            and detail["tables_finite"]), "detail": detail}
