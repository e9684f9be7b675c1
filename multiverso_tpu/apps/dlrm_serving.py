"""DLRM online serving: train-while-serve over the async PS + replica.

The "millions of users" workload (ROADMAP open item 3): a recommender
whose embedding tables live in the sharded async PS, with TWO traffic
classes hitting them at once —

* **training** (class ``"train"``): workers pull the minibatch's rows
  straight from the owning shards (read-your-writes), compute the DLRM
  loss/gradients in one jitted program (models/dlrm.py), and push the
  row gradients back as ``add_rows`` deltas the server-side updater
  applies (AdaGrad by default) — the reference's async PS loop;
* **inference** (class ``"infer"``): a pool of clients scores
  (user, item) candidates against a **bounded-staleness read replica**
  (serving/replica.py) instead of the shards — zero wire hops per
  request, a device-resident hot-row cache under the zipf head, and
  admission control shedding excess load before it can crowd the
  training writes (serving/admission.py).

The two classes meet only at the replica's epoch cadence (MSG_SNAPSHOT
pulls), which is the whole point: inference QPS scales without loading
the write path, at a staleness cost that is bounded and advertised.

Driven by ``tools/bench_serving.py`` (served QPS, tail latency,
staleness, shed rate -> bench ``extra.serving``); the operator story is
docs/SERVING.md.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.models import dlrm
from multiverso_tpu.ps.tables import AsyncMatrixTable
from multiverso_tpu.serving.admission import AdmissionController
from multiverso_tpu.serving.replica import ReadReplica
from multiverso_tpu.telemetry import devstats as _devstats
from multiverso_tpu.telemetry import trace as _trace
from multiverso_tpu.updaters import AddOption


class DLRMServing:
    """One process's view of the train-while-serve recommender.

    The embedding table is the PS object (shared across ranks); the
    dot-interaction MLP is deliberately local to the trainer — it is
    tiny next to the embeddings (the PS story is the sparse side), and
    inference reads it in-process. ``start_replica=False`` leaves the
    replica in manual-refresh mode (tests, step-driven loops).
    """

    def __init__(self, cfg: dlrm.DLRMConfig, ctx=None,
                 name: str = "dlrm_serving", updater: str = "adagrad",
                 lr: float = 0.1, seed: int = 0,
                 infer_qps: float = 0.0,
                 cache_rows: Optional[int] = None,
                 refresh_s: Optional[float] = None,
                 staleness_s: Optional[float] = None,
                 start_replica: bool = True):
        self.cfg = cfg
        self.emb = AsyncMatrixTable(
            dlrm.total_rows(cfg), cfg.embed_dim, updater=updater,
            seed=seed, init_scale=0.05, name=f"{name}_emb", ctx=ctx)
        self.mlp = dlrm.init_mlp_params(cfg, seed)
        self._offsets = dlrm.field_offsets(cfg)
        self._opt = AddOption(learning_rate=lr, rho=0.1)
        self._mlp_lr = lr
        cfg_ = cfg

        def _grad(mlp, rows, dense, labels):
            loss, (g_mlp, g_rows) = jax.value_and_grad(
                dlrm.loss_fn, argnums=(0, 1))(mlp, rows, dense, labels,
                                              cfg_)
            return loss, g_mlp, g_rows

        self._grad = jax.jit(_grad)
        self._fwd = jax.jit(
            lambda mlp, rows, dense: jax.nn.sigmoid(
                dlrm.forward(mlp, rows, dense, cfg_)))
        self.admission = AdmissionController()
        if infer_qps > 0:
            self.admission.set_limit(self.emb.name, "infer", infer_qps)
        # MLP updates from concurrent trainer threads apply DELTAS to
        # the current params under this lock (async-SGD semantics,
        # same contract as the embedding side: gradients computed
        # against a pulled snapshot, applied to whatever the params
        # are now) — an unguarded read-modify-write rebind would let
        # two trainers silently drop each other's updates
        self._mlp_lock = threading.Lock()
        self.replica = ReadReplica(
            self.emb, admission=self.admission, cache_rows=cache_rows,
            refresh_s=refresh_s, staleness_s=staleness_s,
            start=start_replica)

    # ------------------------------------------------------------------ #
    def _ids(self, cat: np.ndarray) -> np.ndarray:
        """[B, F] per-field categorical ids -> flat global row ids in
        the one concatenated embedding table."""
        return (np.asarray(cat, np.int64)
                + self._offsets[None, :]).reshape(-1)

    def train_step(self, cat, dense, labels) -> Tuple[float, float]:
        """One async-PS training step: gather rows from the shards,
        grad, push row-gradient deltas (blocking — the ack means
        applied). Returns ``(loss, write_ms)``: the write latency is
        the serving bench's protected metric (admission control exists
        so THIS number survives an inference storm). One step span
        (``trace.step_report``): prepare / ps_wait / compute / push
        phases, and with ``trace_ids`` on the table layer's
        send-to-reply spans beside them."""
        import time
        with _trace.span("dlrm.train_step", step=1):
            with _trace.span("dlrm.prepare", phase="prepare"):
                b, f = np.asarray(cat).shape
                ids = self._ids(cat)
            with _trace.span("dlrm.pull", phase="ps_wait"):
                rows = self.emb.get_rows(ids).reshape(
                    b, f, self.cfg.embed_dim)
            with _trace.span("dlrm.compute", phase="compute"):
                # pulled rows ride to device through the devstats
                # chokepoint (per-direction device-plane accounting)
                _devstats.note_transfer(rows.nbytes, "h2d")
                loss, g_mlp, g_rows = self._grad(
                    self.mlp, jnp.asarray(rows), jnp.asarray(dense),
                    jnp.asarray(labels))
                with self._mlp_lock:
                    self.mlp = jax.tree.map(
                        lambda p, g: p - self._mlp_lr * g,
                        self.mlp, g_mlp)
                g_host = np.asarray(g_rows).reshape(
                    b * f, self.cfg.embed_dim)
                _devstats.note_transfer(g_host.nbytes, "d2h")
            t0 = time.perf_counter()
            # duplicate ids (same user twice in a batch) f64-accumulate
            # in the client's _dedupe_batch — scatter-add semantics,
            # exactly the fused path's .at[].add
            with _trace.span("dlrm.push", phase="push"):
                self.emb.add_rows(ids, g_host, self._opt)
            return float(loss), (time.perf_counter() - t0) * 1e3

    def infer(self, cat, dense, cls: str = "infer") -> np.ndarray:
        """Score candidates against the replica (bounded staleness;
        may shed with SheddingError under admission pressure).
        Returns click probabilities [B]."""
        b, f = np.asarray(cat).shape
        rows = self.replica.get_rows(self._ids(cat), cls=cls).reshape(
            b, f, self.cfg.embed_dim)
        return np.asarray(self._fwd(self.mlp, jnp.asarray(rows),
                                    jnp.asarray(dense)))

    def serving_stats(self) -> Dict[str, Any]:
        return self.replica.stats()

    def close(self) -> None:
        self.replica.close()
