"""Telemetry plane: histogram metrics, wire-correlated trace spans, and
the periodic metrics exporter.

The reference's observability story was a count/total-ms ``Monitor``
registry printed at shutdown (ref include/multiverso/dashboard.h:16-73);
``utils/dashboard.py`` keeps that surface for parity but its Monitors now
carry a fixed-bucket log-scale latency histogram from this package, so
the shutdown report (and any exporter) sees p50/p90/p99/max — tail
regressions on the batched, compressed PS plane do not hide behind a
stable mean.

Nine cooperating pieces:

* :mod:`~multiverso_tpu.telemetry.histogram` — the lock-free (caller-
  synchronized) log2-bucket histogram every Monitor embeds.
* :mod:`~multiverso_tpu.telemetry.trace` — the one span primitive:
  always-on coarse program spans with counts (per call, block, table
  build, compile; also written into a ``jax.profiler`` trace while one
  is captured) and flag-gated fine spans keyed by the per-request trace
  IDs carried in PS frame meta (``ps/wire.TRACE_META_KEY``), kept in a
  bounded ring and dumped as JSONL for Perfetto
  (``tools/dump_metrics.py to-perfetto`` wraps them for the viewer).
* :mod:`~multiverso_tpu.telemetry.exporter` — flag-gated background
  thread (``metrics_interval_s`` / ``metrics_dir``) dumping Dashboard +
  shard snapshots as JSONL and Prometheus-style text.
* :mod:`~multiverso_tpu.telemetry.flightrec` — the ALWAYS-ON black box:
  a fixed-slot ring of the last N wire events / state transitions plus
  the live in-flight request table, dumped atomically as JSONL at fault
  time (fatal log, SIGTERM/SIGABRT, peer death, watchdog trip,
  Zoo.stop); ``tools/postmortem.py`` merges per-rank dumps.
* :mod:`~multiverso_tpu.telemetry.watchdog` — per-request slow/stuck
  deadlines over the recorder's in-flight table; its verdict feeds the
  ``MSG_HEALTH`` RPC and ``elastic.Heartbeat`` beacons.
* :mod:`~multiverso_tpu.telemetry.hotkeys` — the always-on, bounded-
  memory Space-Saving heavy-hitter sketch each shard keeps over its
  served row ids; feeds ``stats()["hotkeys"]`` and the cluster top-K +
  cache-hit-if-cached curve.
* :mod:`~multiverso_tpu.telemetry.memstats` — the ALWAYS-ON byte
  ledger: every owning component (shard, send window, table, replica,
  checkpointer) registers pull-only memory gauges; a flag-gated
  sampler adds host RSS + a ``jax.live_arrays()`` device census, leak
  verdicts (epoch-hoard, retention-leak, rss-creep) ride the watchdog
  sweep, and every flight-recorder dump carries the ledger + sample
  history for OOM forensics (docs/OBSERVABILITY.md "Memory view").
* :mod:`~multiverso_tpu.telemetry.devstats` — the DEVICE plane:
  host<->device transfer byte counters (one chokepoint, per
  direction), per-mesh-shape compile attribution off the
  ``jax.monitoring`` hook, collective op spans (every
  ``parallel/collectives.py`` entry lands Dashboard ``coll[op]``
  monitors, flightrec ``coll.begin``/``coll.end`` events, and one
  ``coll.<op>`` trace span), the per-device ``jax.live_arrays()``
  rollup riding MSG_STATS as the ``"devices"`` block, and the SPMD
  compile-hygiene capture ``tools/bench_scale.py`` asserts clean
  (docs/OBSERVABILITY.md "Device view & scale curves").
* :mod:`~multiverso_tpu.telemetry.aggregator` — the controller-side
  cluster plane: flag-gated (``stats_poll_interval_s``) polling of
  every rank's MSG_STATS + MSG_HEALTH over one-shot probe connections,
  exact histogram merge, shard-skew + rate derivation, and the rolling
  ``cluster.jsonl``/``cluster.prom`` series ``tools/mvtop.py`` renders
  live.

See docs/OBSERVABILITY.md for the end-to-end story (including the
MSG_STATS / MSG_HEALTH RPCs in ``ps/service.py``).
"""

from multiverso_tpu.telemetry.histogram import Histogram  # noqa: F401
