"""Benchmark harness: prints ONE JSON line for the driver.

Primary metric (BASELINE.json): WordEmbedding words/sec/chip, measured by the
fused skipgram-NS trainer on a synthetic zipf corpus (text8 stand-in; this
environment has no network egress). Secondary metrics (ArrayTable Add/Get p50
latency and bandwidth) ride along in "extra".

``vs_baseline``: the reference publishes no words/sec number
(BASELINE.json "published": {}), so the ratio is computed against a locally
recorded baseline in BENCH_BASELINE.json when present (first run writes it),
else 1.0. The recorded baseline (150,881 w/s) is this framework's first
working implementation — reference-shaped per-pair negative sampling, no
fusion or batch tuning — so the ratio reads as "TPU-first design over naive
translation" measured at equal loss (batch/pool retunes are only taken at
loss parity, see bench_wordembedding). Methodology note: the baseline was
recorded with wall-clock timing (fixed sync cost included), which
understates the naive implementation's device rate by the intercept's share
of its ~8 s run — so the slope-vs-wall-clock ratio carries at most a
few percent of methodology inflation on top of the real speedup.

Timing methodology: every metric here is measured DIFFERENTIALLY — run the
workload at two repeat counts, ending in a host readback, and take the
slope. The slope is the steady-state device time per unit of work; the
fixed intercept (sync + dispatch) is reported alongside in "extra".

``main()`` needs a TPU and fails without one; it exits non-zero when any
phase raised. What has and has not been measured on a chip is in the
README's "Measured performance" note.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

import numpy as np


# Exit status of a SIGTERM-truncated run that still salvaged its headline
# JSON line: 75 (BSD EX_TEMPFAIL — "try again with more budget"). 0 means
# a COMPLETE run; 1 means the salvage itself failed (no usable line).
# tools/run_bench.py keys the recorded "truncated" field off this.
TRUNCATED_EXIT = 75


def _percentile_ms(samples):
    return float(np.percentile(np.asarray(samples) * 1e3, 50))


def _dashboard_hist(max_monitors: int = 64):
    """Histogram snapshots of every timed Dashboard monitor (count, p50/
    p90/p99/max) — the telemetry-plane replacement for ad-hoc counter
    scraping in the BENCH extra. Taken BEFORE mv.shutdown() (which
    displays and resets the dashboard). Bounded so a pathological
    monitor explosion cannot bloat the record."""
    from multiverso_tpu.utils.dashboard import Dashboard
    out = {}
    for name, snap in sorted(Dashboard.snapshot().items()):
        if not snap.timed:
            continue   # pure counters carry no latency story
        if len(out) >= max_monitors:   # only when a monitor is DROPPED
            out["_truncated"] = True
            break
        out[name] = snap.brief_dict()
    return out


def _cluster_extra():
    """Compact cluster record from the stats aggregator, when one ran
    (flag ``stats_poll_interval_s`` > 0 starts it on PS rank 0): merged
    cross-rank histograms, per-shard op counts, skew, and the hot-key
    top-K — the all-ranks view ``_dashboard_hist`` (this process's local
    monitors only) cannot give a multi-process run. None when no
    aggregator ran, so single-process records are unchanged."""
    from multiverso_tpu.telemetry import aggregator
    agg = aggregator.global_aggregator()
    if agg is None:
        return None
    # fresh final poll so the record reflects run-end counters
    return aggregator.compact_record(agg.poll_once())


# degenerate two-point measurements (t_hi < t_lo: timing noise swamped the
# signal) recorded here and surfaced in the bench record's "extra" — a
# floored slope must stay visible as a bad measurement, not pass as data
_DEGENERATE_DIFFERENTIALS = []


def _differential(run, n_lo: int, n_hi: int):
    """Two-point slope timing: ``run(n)`` performs n units of work ending in
    a host readback and returns its wall seconds. Returns
    ``(sec_per_unit, intercept_s)`` — the steady-state device time per unit
    and the fixed sync/dispatch cost the slope removed. A noise-negative
    slope (t_hi < t_lo) floors at 0 and logs the raw pair to
    ``_DEGENERATE_DIFFERENTIALS`` instead of reporting a negative ms/call."""
    t_lo = run(n_lo)
    t_hi = run(n_hi)
    slope = (t_hi - t_lo) / (n_hi - n_lo)
    if slope < 0.0:
        _DEGENERATE_DIFFERENTIALS.append(
            {"n_lo": n_lo, "n_hi": n_hi,
             "t_lo_s": round(t_lo, 6), "t_hi_s": round(t_hi, 6)})
        slope = 0.0
    return slope, max(t_lo - n_lo * slope, 0.0)


def bench_wordembedding(n_lo: int = 2, n_hi: int = 10):
    import multiverso_tpu as mv
    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary

    tokens = synthetic_corpus(400_000, vocab=10_000, seed=7)
    # batch/negative-pool tuned on-chip: bs=16384 with a 256-wide shared
    # pool matches the bs=4096/K'=64 loss (0.498 vs 0.497 after 5 epochs)
    # at ~1.2x the throughput — bigger scatters amortize, and the larger
    # pool keeps the negative-sharing correlation at parity. (A later sweep
    # found bs=32768 ~6% faster but at a worse 5-epoch loss — rejected.)
    cfg = WEConfig(size=128, min_count=5, batch_size=16384, negative=5,
                   window=5, epoch=1, shared_negatives=256)
    d = Dictionary.build(tokens, cfg.min_count)
    we = WordEmbedding(cfg, d)
    ids = we.prepare_ids(tokens)
    # warmup: compile + first dispatch; 2 epochs because the donated-table
    # epoch fn compiles twice (initial device_put layout vs donated layout)
    we.train_fused(ids, epochs=2)
    # differential timing: slope between n_lo and n_hi epochs removes the
    # fixed sync/dispatch intercept (train_fused reads the loss back on
    # the host, which waits for the whole epoch chain)
    last = {}

    def run(n):
        last.update(we.train_fused(ids, epochs=n))
        return last["seconds"]

    sec_per_epoch, intercept = _differential(run, n_lo, n_hi)
    words_per_sec = ids.size / sec_per_epoch
    n_chips = max(len(mv.mesh().devices.reshape(-1)), 1)
    stats = {"loss": last["loss"], "sec_per_epoch": sec_per_epoch,
             "fixed_overhead_s": intercept,
             "words_per_sec": words_per_sec}
    return words_per_sec / n_chips, stats


def bench_wordembedding_ps(num_tokens: int = 120_000):
    """The PS-parity path (train_ps_blocks: pull rows / train / push
    deltas, ref distributed_wordembedding.cpp) — benchmarked alongside the
    fused path so the Add/Get plane can't silently regress. The reference's
    words/sec was inherently a number of THIS shape. Reports the r02-
    comparable 120k-token run AND a 1M-token run where the per-run fixed
    costs (final drain RTT, first-block pipeline fill) amortize out."""
    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary

    cfg = WEConfig(size=128, min_count=5, batch_size=8192, negative=5,
                   window=5, epoch=1, data_block_size=50_000, use_ps="1")

    def run(n_tokens, seed, best_of):
        tokens = synthetic_corpus(n_tokens, vocab=5_000, seed=seed)
        d = Dictionary.build(tokens, cfg.min_count)
        we = WordEmbedding(cfg, d)
        ids = we.prepare_ids(tokens)
        we.train_ps_blocks(ids, epochs=1)   # compile all block programs
        runs = [we.train_ps_blocks(ids, epochs=1) for _ in range(best_of)]
        # throughput: best-of-N (run-to-run noise); loss/seconds: the
        # FIRST post-warmup run, so the reported loss stays at a fixed
        # epoch count across rounds regardless of N
        return {"words_per_sec": max(r["words_per_sec"] for r in runs),
                "loss": runs[0]["loss"], "seconds": runs[0]["seconds"],
                "tokens": int(ids.size)}

    # best-of-N: more samples keep one official measurement from landing
    # on a slow run (each 120k run is <1 s, each 1M run ~2-3 s)
    small = run(num_tokens, 11, 6)
    large = run(1_000_000, 12, 3)
    return {"ps_words_per_sec": small["words_per_sec"],
            "loss": small["loss"], "seconds": small["seconds"],
            "tokens": small["tokens"],
            "ps_words_per_sec_1M": large["words_per_sec"],
            "loss_1M": large["loss"], "seconds_1M": large["seconds"]}


def bench_lr_real():
    """Tier-4 convergence on REAL data (BASELINE config 1): LR test
    accuracy on MNIST idx files when present, else sklearn's bundled UCI
    handwritten digits (real data; MNIST is not downloadable here —
    provenance is recorded)."""
    from multiverso_tpu.apps.logistic_regression import LogReg, LogRegConfig
    from multiverso_tpu.io import mnist

    data = mnist.load_real()
    cfg = LogRegConfig({
        "input_size": str(data["x_train"].shape[1]), "output_size": "10",
        "minibatch_size": "64", "learning_rate": "0.05",
        "train_epoch": "30", "objective_type": "softmax",
    })
    lr = LogReg(cfg)
    stats = lr.train_arrays(data["x_train"], data["y_train"])
    acc = lr.test_arrays(data["x_test"], data["y_test"])
    return {"test_accuracy": round(acc, 4),
            "train_loss": round(stats["loss"], 4),
            "n_train": int(len(data["y_train"])),
            "n_test": int(len(data["y_test"])),
            "provenance": data["provenance"]}


def bench_we_real(n_lo: int = 1, n_hi: int = 5):
    """Tier-4 WE on REAL text (BASELINE config 2): the committed
    text8-normalized real-prose shard (or an actual text8 file when
    present — io/realtext.py). Reports words/sec + loss, and a nearest-
    neighbor probe as qualitative convergence evidence."""
    from multiverso_tpu.apps.word_embedding import WEConfig, WordEmbedding
    from multiverso_tpu.data.dictionary import Dictionary
    from multiverso_tpu.io import realtext

    tokens = realtext.load_tokens()
    cfg = WEConfig(size=128, min_count=5, batch_size=16384, negative=5,
                   window=5, shared_negatives=256)
    d = Dictionary.build(tokens, cfg.min_count)
    we = WordEmbedding(cfg, d)
    ids = we.prepare_ids(tokens)
    we.train_fused(ids, epochs=2)   # warm both compile layouts
    last = {}

    def run(n):
        last.update(we.train_fused(ids, epochs=n))
        return last["seconds"]

    sec_per_epoch, _ = _differential(run, n_lo, n_hi)
    probe = next((w for w in ("array", "matrix", "value", "data")
                  if w in d.word2id), None)
    neighbors = we.nearest(probe, 6)[1:] if probe else []
    return {"words_per_sec": ids.size / sec_per_epoch,
            "loss": round(last["loss"], 4),
            "tokens": int(ids.size), "vocab": len(d),
            "neighbors_of_" + (probe or "none"): neighbors,
            "provenance": realtext.provenance()}


_REPO = os.path.dirname(os.path.abspath(__file__))


def _worker_env() -> dict:
    """Environment of every process this file spawns: the repo on the
    path, and libtpu's import-time hugepages warning silenced — the
    workers never open the TPU, and on a TPU host that warning filled the
    stderr tail a failed worker is reported by, hiding the real error."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONWARNINGS"] = ",".join(filter(None, (
        env.get("PYTHONWARNINGS"), "ignore:Transparent hugepages")))
    return env


def _collect_worker_results(cmds, timeout: float = 240):
    """Spawn one subprocess per argv, harvest their ``RESULT {json}``
    lines; kill stragglers on the way out (a leaked sibling would skew
    later benchmarks). Raises if a worker fails or nothing reported — an
    empty measurement must not masquerade as a recorded one.

    One process holds a chip, and this parent does (``mv.init()`` ran):
    a child that reached for the TPU would fail or hang. Every worker
    spawned here pins ``jax_platforms`` to the CPU before its first
    device use (tools/bench_async_ps.py, bench_aggregate.py and
    bench_we_async.py, the last because bench.py never passes
    ``MV_WE_BENCH_TPU``), so these phases are CPU counts and correctness
    checks, never device timings. The children inherit the environment,
    and with it the compile-cache directory."""
    import subprocess

    procs = [subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                              env=_worker_env()) for cmd in cmds]
    results = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError(
                    f"bench worker rc={p.returncode}: {p.args[-4:]}")
            for line in out.splitlines():
                if line.startswith("RESULT "):
                    results.append(json.loads(line[len("RESULT "):]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    if not results:
        raise RuntimeError("bench workers produced no RESULT line")
    return results


def _run_async_ps_world(world: int, wire: str, seconds: float,
                        native: bool = True, pattern: str = "strided"):
    """One configuration of the uncoordinated-plane bench: ``world`` real
    OS processes (CPU) pushing/pulling 1024-row batches against each
    other's shards over loopback TCP (1/world of the traffic
    short-circuits). ``native=False`` pins the pure-Python plane
    (MV_PS_NATIVE=0) for the A/B rows."""
    import tempfile

    prior = os.environ.get("MV_PS_NATIVE")   # restore, don't clobber: a
    if not native:                           # user-exported value must
        os.environ["MV_PS_NATIVE"] = "0"     # survive this helper
    try:
        with tempfile.TemporaryDirectory(prefix="mv_bench_ps_") as rdv:
            results = _collect_worker_results(
                [[sys.executable,
                  os.path.join(_REPO, "tools", "bench_async_ps.py"),
                  rdv, str(world), str(r), str(seconds), wire, pattern]
                 for r in range(world)])
    finally:
        if prior is None:
            os.environ.pop("MV_PS_NATIVE", None)
        else:
            os.environ["MV_PS_NATIVE"] = prior
    if all("get_lat_ms" in r for r in results):
        # plane-wide percentiles from the pooled raw samples (paced mode)
        lat = np.concatenate([np.asarray(r["get_lat_ms"])
                              for r in results])
        p50 = float(np.percentile(lat, 50))
        p99 = float(np.percentile(lat, 99))
        return {
            "rows_per_sec": round(sum(r["rows_per_sec"] for r in results)),
            "msgs_per_sec": round(sum(r.get("msgs_per_sec", 0)
                                      for r in results)),
            "mb_per_sec": round(sum(r["mb_per_sec"] for r in results), 1),
            "get_p50_ms": round(p50, 2), "get_p99_ms": round(p99, 2),
            "p99_over_p50": round(p99 / max(p50, 1e-9), 2),
            "n_lat_samples": int(lat.size),
            "batch_rows": results[0]["batch_rows"],
            "dim": results[0]["dim"],
        }
    return {
        "rows_per_sec": round(sum(r["rows_per_sec"] for r in results)),
        # aggregate request rate across the plane (each op = `world`
        # messages with these strided row sets): the metric that shows
        # server throughput RISING with worker count even when rows/s —
        # which pays world messages per batch — tilts down on a 1-core
        # host
        "msgs_per_sec": round(sum(r.get("msgs_per_sec", 0)
                                  for r in results)),
        "mb_per_sec": round(sum(r["mb_per_sec"] for r in results), 1),
        "get_p50_ms": round(float(np.median(
            [r["get_p50_ms"] for r in results])), 2),
        "get_p99_ms": round(float(np.max(
            [r["get_p99_ms"] for r in results])), 2),
        "coalesce_ratio": round(float(np.mean(
            [r.get("coalesce_ratio", 1.0) for r in results])), 2),
        "batch_rows": results[0]["batch_rows"],   # worker-reported truth
        "dim": results[0]["dim"],
    }


def bench_we_async(world: int = 4, n_tokens: int = 1_000_000):
    """WordEmbedding on the UNCOORDINATED plane at np=world — the
    reference's actual product shape (N independent processes, async
    tables, ref trainer.cpp:44-49 words/sec) — so the async plane has a
    tracked perf number, not just the sync/fused paths. Same corpus/seed
    as bench_wordembedding_ps's 1M run: the losses are comparable.

    Two stages (ISSUE 11): the measured np=world run takes the pipelined
    path (producer-thread prepared-block queue + hot-row training cache);
    a parity stage then reruns a REDUCED corpus at world=1 twice —
    pipelined vs the unpipelined/uncached oracle — and asserts the
    embedding digests match BIT-FOR-BIT (single-writer runs are
    deterministic, so any divergence is a real pipeline/cache bug, the
    class the test suite's tiny corpus might miss at bench scale)."""
    import tempfile

    worker = os.path.join(_REPO, "tools", "bench_we_async.py")
    with tempfile.TemporaryDirectory(prefix="mv_bench_we_async_") as rdv:
        results = _collect_worker_results(
            [[sys.executable, worker, rdv, str(world), str(r),
              str(n_tokens), "pipeline"]
             for r in range(world)], timeout=600)
    # parity stage: world=1, reduced corpus, pipeline vs oracle
    parity_tokens = max(30_000, n_tokens // 8)
    digests = {}
    for mode in ("pipeline", "oracle"):
        with tempfile.TemporaryDirectory(
                prefix=f"mv_bench_we_parity_{mode}_") as rdv:
            digests[mode] = _collect_worker_results(
                [[sys.executable, worker, rdv, "1", "0",
                  str(parity_tokens), mode]], timeout=600)[0]["emb_sha"]
    parity_ok = digests["pipeline"] == digests["oracle"]
    assert parity_ok, (
        "ISSUE-11 parity gate: pipelined WE run is NOT bit-identical to "
        f"the unpipelined/uncached oracle at {parity_tokens} tokens "
        f"({digests['pipeline'][:16]} != {digests['oracle'][:16]})")
    out = {
        "world": world, "tokens": n_tokens,
        "words_per_sec_aggregate": round(
            sum(r["words_per_sec"] for r in results), 1),
        "words_per_sec_per_worker": [r["words_per_sec"] for r in results],
        "loss_mean": round(float(np.mean([r["loss"] for r in results])), 4),
        "loss_per_worker": [round(r["loss"], 4) for r in results],
        "parity": {"ok": parity_ok, "tokens": parity_tokens},
        "perf_gate": results[0].get("perf_gate"),
    }
    caches = [r["train_cache"] for r in results if r.get("train_cache")]
    if caches:
        hits = sum(c["hits"] for c in caches)
        misses = sum(c["misses"] for c in caches)
        out["train_cache"] = {
            "hit_rate": (round(hits / (hits + misses), 4)
                         if hits + misses else None),
            "mode": caches[0]["mode"],
            "rows_per_worker": [c["rows"] for c in caches],
        }
    # the steps' evidence (ISSUE 9): the worker reads its measured
    # epoch's step spans and asserts >= 90% attribution + zero steady recompiles
    # in-run; the record keeps rank 0's per-step phase breakdown as the
    # headline plus the cross-rank stall/attribution spread. bench.main
    # lifts this to extra.profile so run_bench can flag PHASE-level
    # regressions (stall growth, steady recompiles) run-over-run.
    profs = [r["profile"] for r in results if isinstance(r, dict)
             and r.get("profile")]
    if profs:
        head = dict(profs[0])
        head["stall_fraction_per_worker"] = [
            p["stall_fraction"] for p in profs]
        head["attributed_fraction_per_worker"] = [
            p["attributed_fraction"] for p in profs]
        head["stall_fraction"] = round(float(np.max(
            [p["stall_fraction"] for p in profs])), 4)
        head["steady_recompiles"] = int(sum(
            p["steady_recompiles"] for p in profs))
        out["profile"] = head
    return out


def bench_aggregate_path(world: int = 4, mb: float = 16.0):
    """MV_Aggregate path comparison at np=world (VERDICT r3 item 7): the
    device-AllReduce process_sum vs the legacy allgather+numpy-sum on the
    same payload; per-host cost of the new path is O(size), the old one
    O(world*size)."""
    import socket

    last = None
    for _ in range(2):   # bind-then-close port pick is TOCTOU; retry once
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        try:
            out = _collect_worker_results(
                [[sys.executable,
                  os.path.join(_REPO, "tools", "bench_aggregate.py"),
                  str(port), str(world), str(r), str(mb)]
                 for r in range(world)], timeout=180)[0]
            out["world"], out["mb"] = world, mb
            return out
        except RuntimeError as e:
            last = e
    raise last


def bench_async_ps(seconds: float = 4.0):
    """Uncoordinated-plane scaling curve (ref dense-perf harness intent,
    Test/main.cpp:340-495): throughput + request latency at np=2/4/8,
    plus the bf16 wire variant (the SparseFilter-analogue compression)."""
    out = {"note": "real CPU processes, add+get interleaved, loopback TCP; "
                   f"host has {os.cpu_count()} cores (np8 oversubscribes); "
                   "best-of-2 per config (oversubscription noise is "
                   "~±25% single-shot). npN = strided fanout (1 op = N "
                   "messages, conflates server capacity with O(N) client "
                   "work on this host); npN_local = owner-local batches "
                   "(1 op = 1 message, isolates the servers); npN_paced = "
                   "owner-local at a FIXED total offered load with "
                   "plane-wide pooled latency percentiles"}
    for world in (2, 4, 8):
        out[f"np{world}"] = max(
            (_run_async_ps_world(world, "none", seconds) for _ in range(2)),
            key=lambda r: r["rows_per_sec"])
        # load-controlled variant: one real TCP message per op at every
        # world size (batch lives wholly in the next rank's shard), so
        # the aggregate curve measures what the SERVERS sustain — the
        # strided rows above conflate that with O(world) per-op client
        # fanout, which on this 1-core host tilts rows/s down as np grows
        out[f"np{world}_local"] = max(
            (_run_async_ps_world(world, "none", seconds, pattern="local")
             for _ in range(2)),
            key=lambda r: r["rows_per_sec"])
        # fixed-total-offered-load: the plane sustains a constant 150
        # pairs/s at every world size (flat aggregate = the monotone
        # done-bar) and the pooled latency percentiles measure SERVING
        # latency, not saturation queueing. Best-of-2 on the tail.
        out[f"np{world}_paced"] = min(
            (_run_async_ps_world(world, "none", seconds, pattern="paced")
             for _ in range(2)),
            key=lambda r: r["get_p99_ms"])
    # A/B: the same np8 load on the pure-Python plane (ps_native off) —
    # the native transport's measured margin at the worst
    # oversubscription. Same best-of-2 protocol as the native rows (an
    # asymmetric single shot would inflate the ratio by the ±25%
    # single-run noise alone).
    from multiverso_tpu.ps import native as _ps_native
    if _ps_native.available():
        out["np8_python_plane"] = max(
            (_run_async_ps_world(8, "none", seconds, native=False)
             for _ in range(2)),
            key=lambda r: r["rows_per_sec"])
    out["np2_bf16"] = _run_async_ps_world(2, "bf16", seconds)
    # r02-comparable aliases
    out["rows_per_sec_2workers"] = out["np2"]["rows_per_sec"]
    out["mb_per_sec_2workers"] = out["np2"]["mb_per_sec"]
    return out


def _run_result_worker(script: str, args, timeout: float = 300):
    """Spawn a tools/ bench worker in a subprocess (so its 2-rank PS
    world and CPU backend never touch this process's runtime) and parse
    its "RESULT <json>" line — the one worker-spawn contract shared by
    the small-add and get-rows benches. Same rule as
    :func:`_collect_worker_results`: this parent holds the chip, and each
    worker (bench_small_add, bench_get_rows, bench_serving, bench_chaos,
    bench_scale) pins the CPU before its first device use."""
    import subprocess

    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", script),
         *[str(a) for a in args]],
        capture_output=True, text=True, timeout=timeout, env=_worker_env(),
        cwd=_REPO)
    if out.returncode != 0:
        raise RuntimeError(f"{script} rc={out.returncode}: "
                           f"{out.stderr[-300:]}")
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"{script} produced no RESULT line")


def bench_small_add_window(iters: int = 400):
    """Small-add (1-row) p50 per-call latency with the client send window
    on vs off (ISSUE 2 acceptance metric). The worker interleaves both
    arms over the same ids/values and refuses to report latency unless
    the final states match bit-for-bit."""
    return _run_result_worker("bench_small_add.py", [iters])


def bench_get_rows_plane(iters: int = 300):
    """PS read-path bench (ISSUE 5): small-get p50/p99 with the client
    get coalescer on vs off, the concurrent fan-in dedupe ratio, and a
    large get plain vs chunk-streamed. The worker refuses to report
    latency unless both parity checks held bit-for-bit."""
    return _run_result_worker("bench_get_rows.py", [iters])


def bench_dlrm_serving(seconds: float = 10.0):
    """Online-serving bench (ISSUE 8 acceptance): DLRM training writes
    and a zipf inference storm hit the same sharded embedding table —
    reads served by a bounded-staleness ReadReplica behind admission
    control. Records served QPS, p50/p99/p999 tail latency, measured
    replica staleness (asserted <= the advertised bound in-run), shed
    rate, and the sketch-estimate-vs-measured cache hit rate; the tool
    exits nonzero — failing this sub-bench — if replica parity,
    staleness, or the overload-protection contract broke."""
    return _run_result_worker("bench_serving.py", [seconds], timeout=420)


def bench_scale_curve(seconds: float = 3.0, shards: str = "1,2,4,8"):
    """Mesh scale-curve harness (ISSUE 12 instrument, ISSUE 15 plane +
    methodology — tools/bench_scale.py): the async-PS workload at
    1->2->4->8 server shards on the 8-virtual-device host platform
    (process-per-point, CONSTANT offered load at every point), with
    the ISSUE-15 mesh data plane armed (ps_fanout routing +
    super-frames, ps_spmd_stack grouped SPMD apply/gather), plus a
    quiesced model-average collective measurement per shard count.
    Records T_n, E_n = T_n/(n*T_1) computed in-run (plus the e2/e4/e8
    per-point scalars), per-shard skew, stall fraction, and the
    per-mesh-shape transfer/compile costs from telemetry/devstats.py.
    The worker exits nonzero — failing this sub-bench — if the SPMD
    compile-hygiene report is not clean for every mesh shape, if any
    point's mesh-plane result diverges bit-for-bit from its 1-shard
    classic oracle, or if the warmed measured loop recompiled in
    steady state. run_bench flags run-over-run drops of
    extra.scale.efficiency_min / e2 / e4 / t1_rows_per_s.
    The worker bounds each point's subprocess at 120 + 30*n s; this
    outer budget exceeds the 1+2+4+8 sum (~1050 s) so a wedged point
    surfaces as the worker's structured per-point error, never a
    generic worker timeout that hides which shard count hung."""
    return _run_result_worker("bench_scale.py", [seconds, shards],
                              timeout=1200)


def bench_chaos_failover(seconds: float = 16.0):
    """Chaos scenario matrix (ISSUE 7 → ISSUE 14): partition-heal,
    dup+reorder under replay, slow-shard shed, replica kill, and the
    combined shard-SIGKILL + replica-kill storm — each with in-run
    gates (exactly-once ledger vs the acked-op oracle, staleness
    bound never exceeded on a served read, recovery-to-90%) and a
    per-scenario ``recovery_s`` under ``extra.chaos.scenarios`` that
    run_bench trend-tracks. The tool exits nonzero — failing this
    sub-bench — when any scenario's gate fails."""
    return _run_result_worker("bench_chaos.py", [seconds], timeout=900)


def bench_array_table_cpu(size: int = 1_000_000, iters: int = 10):
    """The BASELINE ArrayTable metric with no host<->device link at all:
    the same host-plane code on the CPU backend (subprocess so the
    parent's TPU backend is untouched) — what the table layer itself
    costs (VERDICT r2 item 9)."""
    import subprocess

    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import json, bench\n"
        "import multiverso_tpu as mv\n"
        "mv.init()\n"
        f"r = bench.bench_array_table(size={size}, iters={iters})\n"
        "print('RESULT ' + json.dumps(bench._sanitize(r)))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                         env=_worker_env(), capture_output=True, text=True,
                         timeout=300)
    if out.returncode != 0:
        raise RuntimeError(f"cpu array bench rc={out.returncode}: "
                           f"{out.stderr[-300:]}")
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            r = json.loads(line[len("RESULT "):])
            r["note"] = "CPU backend: the same host-plane code, no link"
            return r
    raise RuntimeError("cpu array bench produced no RESULT line")


def bench_host_wire():
    """Measure the host<->device wire itself (BASELINE breakdown evidence):
    per-dispatch round-trip (RTT) and upload bandwidth via a two-size
    differential — every host-plane p50 decomposes against these."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a + 1)
    x = jnp.zeros(())
    float(f(x))

    def rtt_once():
        t0 = time.perf_counter()
        float(f(x))
        return time.perf_counter() - t0

    rtts = [rtt_once() for _ in range(12)]

    def upload(nfloats):
        h = np.ones(nfloats, np.float32)
        jax.device_put(h).block_until_ready()
        t0 = time.perf_counter()
        jax.device_put(h).block_until_ready()
        return time.perf_counter() - t0

    t_small = np.median([upload(1 << 20) for _ in range(4)])
    t_big = np.median([upload(1 << 23) for _ in range(4)])
    bw = ((1 << 23) - (1 << 20)) * 4 / max(t_big - t_small, 1e-9)
    return {"rtt_ms": _percentile_ms(rtts),
            "upload_gbps": bw / 1e9,
            "upload_4mb_ms": t_small * 1e3,
            "upload_32mb_ms": t_big * 1e3}


def bench_array_table(size: int = 1_000_000, iters: int = 10):
    import multiverso_tpu as mv
    from multiverso_tpu.updaters import AddOption

    t = mv.ArrayTable(size, updater="sgd", name="bench_array")
    delta = np.random.default_rng(0).normal(size=size).astype(np.float32)
    opt = AddOption(learning_rate=0.01)
    t.add(delta, opt)  # compile
    t.get()
    adds, gets = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        t.add(delta, opt)
        adds.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        t.get()
        gets.append(time.perf_counter() - t0)

    # pipelined plane: the app-realistic shape — N in-flight async adds,
    # one wait (ref LR pipeline AddAsync; amortizes the dispatch RTT, so
    # the steady rate is wire-bandwidth-bound, not latency-bound)
    def pipelined(n):
        mids = [t.add_async(delta, opt) for _ in range(n)]
        t.wait(mids[-1])
        return None

    pipelined(4)
    pipe = []
    for _ in range(4):
        t0 = time.perf_counter()
        pipelined(8)
        pipe.append((time.perf_counter() - t0) / 8)

    # in-run bit-parity of the read path (ISSUE 5 acceptance): the bytes
    # the gets above returned must equal the live table's exactly. A
    # latency number without this is meaningless, so parity failure FAILS
    # the bench.
    host_now = t.get()
    raw_now = np.asarray(t.raw())[: size].reshape(host_now.shape)
    if not np.array_equal(host_now, raw_now):
        raise AssertionError(
            "bench_array get parity broke: the read path returned "
            "different bytes than the live device table")
    # device plane: delta already resident (the real TPU deployment shape —
    # grads are produced on device; host numbers above are link-bound)
    import jax

    delta_dev = jax.device_put(t.pad_delta(delta), t.sharding)
    # long chain: the per-add time is ~us-scale, so the slope base must be
    # large enough that ~10 ms of sync jitter cannot swamp it
    chain = 1000

    # chain the adds inside one program: per-dispatch overhead would
    # otherwise swamp the ~us-scale device op
    @jax.jit
    def fadd_chain(state, d):
        return jax.lax.scan(
            lambda s, _: (t.functional_add(s, d, opt), None),
            state, None, length=chain)[0]

    state = fadd_chain(t.state, delta_dev)  # compile
    float(state["data"][0])
    box = {"state": state}

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            box["state"] = fadd_chain(box["state"], delta_dev)
        float(box["state"]["data"][0])  # host readback = reliable sync
        return time.perf_counter() - t0

    # differential over chained runs: slope removes the fixed sync cost
    # (wide 4->32 spread: the signal must dominate ~100 ms sync jitter)
    per_chain, dev_intercept = _differential(run, 4, 32)
    dev_add_s = per_chain / chain
    t.adopt(box["state"])

    nbytes = size * 4
    return {
        "add_p50_ms": _percentile_ms(adds),
        "get_p50_ms": _percentile_ms(gets),
        "add_gbps": nbytes / np.percentile(adds, 50) / 1e9,
        "get_gbps": nbytes / np.percentile(gets, 50) / 1e9,
        "pipelined_add_ms": _percentile_ms(pipe),
        "pipelined_add_gbps": nbytes / np.percentile(pipe, 50) / 1e9,
        "get_parity_bit_for_bit": True,   # asserted above, else raise
        "device_add_ms": dev_add_s * 1e3,
        "device_add_gbps": nbytes / dev_add_s / 1e9,
        "fixed_overhead_ms": dev_intercept * 1e3,
        "size_mb": nbytes / 1e6,
    }


# Published bf16 peak per chip, keyed by ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s). A device that is not listed is
# an error, not a default.
_PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def bench_transformer(steps: int = 40, b: int = 8, s: int = 512,
                      dim: int = 256, layers: int = 4, vocab: int = 8192,
                      heads: int = 8, repeats: int = 1,
                      attn: str = "flash"):
    """LM train-step throughput (tokens/sec) in bf16 with the fused
    flash-attention kernel (``attn="local"`` is the XLA-attention A/B
    arm). ``repeats`` re-runs the differential measurement on the SAME
    compiled step and records the best slope, so one slow sample does not
    become the recorded number."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, dim=dim, num_heads=heads, num_layers=layers,
        max_seq=s, attn=attn, dtype=jnp.bfloat16)
    params = tfm.init_params(cfg, seed=0)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (b, s + 1)).astype(np.int32)
    tok, tgt = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    # donate params: the step's output params alias the input buffers, so
    # XLA updates in place instead of allocating+copying 0.94 GB of bf16
    # weights per step (interleaved A/B: ~0.6 ms/step on the chip; safe
    # here because the loop rebinds `params` every call)
    step = jax.jit(tfm.make_train_step(cfg, 1e-2), donate_argnums=(0,))
    params, loss = step(params, tok, tgt)  # compile
    float(loss)

    last = {}

    def run(n):
        nonlocal params
        t0 = time.perf_counter()
        for _ in range(n):
            params, loss = step(params, tok, tgt)
        last["loss"] = float(loss)  # host readback = reliable sync
        return time.perf_counter() - t0

    # fwd+bwd FLOPs ~ 6 * params * tokens (dense matmul count), the
    # standard LM accounting; reported so MFU vs the chip's peak is one
    # division away, and used for the plausibility floor below
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    flops_per_step = 6.0 * n_params * b * s
    samples = [_differential(run, max(steps // 4, 1), steps)
               for _ in range(max(repeats, 1))]
    # best slope = least-disturbed sample; a stall landing on an n_lo
    # run can push a sample's slope to ~0, negative, OR merely
    # implausibly small — min() would record a physically impossible
    # peak. Keep only samples whose implied rate is under THIS device's
    # published peak; fall back to the median sample only if every one
    # is corrupt.
    floor_s = flops_per_step / _PEAK_BF16_FLOPS[
        jax.devices()[0].device_kind]
    valid = [x for x in samples if x[0] > floor_s]
    step_s, intercept = (min(valid) if valid
                         else sorted(samples)[len(samples) // 2])
    tflops = flops_per_step / step_s / 1e12
    out = {"lm_tokens_per_sec": b * s / step_s,
           "lm_step_ms": step_s * 1e3,
           "lm_tflops_per_sec": tflops,
           "fixed_overhead_ms": intercept * 1e3,
           "attn": cfg.attn, "loss": last["loss"]}
    if repeats > 1:
        out["best_of"] = repeats
        out["all_tflops"] = [
            round(flops_per_step / ss / 1e12, 2) if ss > 0 else None
            for ss, _ in samples]
    return out


def bench_matrix_rows(rows: int = 100_000, cols: int = 128,
                      batch: int = 4096):
    """Sparse row push (the PS differentiator: WE pushes only the block's
    rows, ref Test/main.cpp TestSparsePerf) — device-plane row-batch add
    through the updater, differential-timed like everything else."""
    import jax

    import multiverso_tpu as mv
    from multiverso_tpu.updaters import AddOption

    t = mv.MatrixTable(rows, cols, updater="adagrad", name="bench_rows")
    rng = np.random.default_rng(0)
    ids = jax.device_put(rng.integers(0, rows, batch).astype(np.int32))
    vals = jax.device_put(rng.normal(size=(batch, cols)).astype(np.float32))
    opt = AddOption(learning_rate=0.05, rho=0.1)
    chain = 200

    @jax.jit
    def chain_add(state, ids, vals):
        return jax.lax.scan(
            lambda s, _: (t.functional_add_rows(s, ids, vals, opt), None),
            state, None, length=chain)[0]

    box = {"state": chain_add(t.state, ids, vals)}
    float(box["state"]["data"][0, 0])

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            box["state"] = chain_add(box["state"], ids, vals)
        float(box["state"]["data"][0, 0])
        return time.perf_counter() - t0

    per_chain, _ = _differential(run, 2, 8)
    per_add = per_chain / chain
    t.adopt(box["state"])
    nbytes = batch * cols * 4
    return {"row_add_us": per_add * 1e6,
            "rows_per_sec": batch / per_add,
            "row_add_gbps": nbytes / per_add / 1e9,
            "batch_rows": batch, "table": f"{rows}x{cols}"}


def bench_decode(new_tokens: int = 128, b: int = 8):
    """Autoregressive decode throughput (tokens/sec) on the KV-cache scan,
    f32 weights vs weight-only int8 (ops/quantization.py) — the decode
    surface (prefill, cache, sampling) has its own perf profile distinct
    from training."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import transformer as tfm
    from multiverso_tpu.ops.quantization import quantize_lm_params

    s = 64 + new_tokens
    cfg = tfm.TransformerConfig(vocab_size=8192, dim=256, num_heads=8,
                                num_layers=4, max_seq=s, attn="local")
    params = tfm.init_params(cfg, seed=0)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (b, 64)).astype(np.int32))
    out = {}
    for label, p in (("f32", params), ("int8", quantize_lm_params(params))):
        # jit the whole decode (the serving shape); a bare generate call
        # would re-trace its scan every invocation
        gen = jax.jit(lambda p, pr: tfm.generate(p, pr, cfg, new_tokens))
        gen(p, prompt)  # compile

        def run(n):
            t0 = time.perf_counter()
            for _ in range(n):
                toks = gen(p, prompt)
            np.asarray(toks[0, -1:])  # host readback = reliable sync
            return time.perf_counter() - t0

        run(2)  # settle: secondary compiles / queue state
        per_call, _ = _differential(run, 4, 40)
        out[f"decode_tok_per_sec_{label}"] = b * new_tokens / per_call
        out[f"decode_ms_per_step_{label}"] = per_call / new_tokens * 1e3
    return out


def bench_resnet(depth: int = 32, n_images: int = 50_000):
    """CIFAR ResNet sec/epoch — the reference's published headline
    (binding BENCHMARK.md tables: Lasagne ResNet-32 100.02 s/epoch on a
    GTX TITAN X; Torch 20.366 s/epoch; see BASELINE.md). Synthetic CIFAR
    (no egress), same 50k-image epoch, batch 128, data-parallel trainer
    with all params in one Adam ArrayTable."""
    import jax.numpy as jnp

    from multiverso_tpu.apps.resnet_cifar import ResNetTrainer
    from multiverso_tpu.models import resnet as resnet_lib

    trainer = ResNetTrainer(depth=depth, batch_size=128)
    x, y = resnet_lib.synthetic_cifar(n_images, seed=1)
    # upload the dataset ONCE (the 600 MB host->device transfer would
    # otherwise dominate every timed call)
    x, y = jnp.asarray(x), jnp.asarray(y)
    # warm twice: the epoch fn can compile a second time when the adopted
    # (donated) buffer layout differs from the first device_put
    trainer.train(x, y, epochs=1)
    trainer.train(x, y, epochs=1)
    sec_per_epoch, intercept = _differential(
        lambda n: trainer.train(x, y, epochs=n)["seconds"], 1, 9)
    # the trainer drops the 50k % 128 remainder; count what actually ran,
    # and scale the reference comparison to a full-50k-image epoch
    n_eff = (n_images // 128) * 128
    sec_50k = sec_per_epoch * n_images / n_eff
    return {"sec_per_epoch": sec_per_epoch,
            "images_per_sec": n_eff / sec_per_epoch,
            "images_per_epoch": n_eff, "depth": depth,
            "fixed_overhead_s": intercept,
            "vs_ref_theano_titanx": 100.02 / sec_50k,
            "vs_ref_torch_titanx": 20.366 / sec_50k}


def _flightrec_salvage_dump(signum) -> "Optional[str]":
    """Flight-recorder half of the SIGTERM salvage (separate function so
    tests exercise it without a live signal): record the signal and dump
    the black box — a truncated run must leave its tape, not just its
    headline. Returns the dump path (None when no dump directory
    resolves or the recorder is unavailable)."""
    try:
        from multiverso_tpu.telemetry import flightrec
        flightrec.record(flightrec.EV_SIGNAL,
                         note=f"bench salvage: signal {signum}")
        return flightrec.dump_global(f"bench salvage: signal {signum}",
                                     stacks=True)
    except BaseException:   # noqa: BLE001 — salvage must keep going
        return None


def _require_tpu() -> None:
    """Every number this file prints is a device number: without a TPU
    there is nothing to measure, and a CPU run must not be recorded
    under a device metric's name."""
    import jax

    from multiverso_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()   # before the first jit
    try:
        platform = jax.devices()[0].platform
    except RuntimeError as e:
        sys.exit(f"bench.py needs a TPU; JAX found no backend: {e}")
    if platform != "tpu":
        sys.exit(f"bench.py needs a TPU; jax.devices()[0].platform is "
                 f"{platform!r}")


def _device_record() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind, "count": len(devices)}


def main() -> None:
    import signal

    _require_tpu()
    import multiverso_tpu as mv

    mv.init()
    words_per_sec_chip, we_stats = bench_wordembedding()

    # Salvage path: if a driver-side timeout SIGTERMs the run after the
    # headline measurement but before the final print, emit the headline
    # (with whatever vs_baseline the baseline file gives) instead of
    # dying silently — a truncated run must not erase the record. The
    # normal path still prints exactly one JSON line (this handler never
    # fires then). The salvage exits TRUNCATED_EXIT (not 0): a truncated
    # run with a usable headline must stay distinguishable from a
    # complete one (tools/run_bench.py records the distinction).
    def _salvage(signum, frame):
        ok = False
        _flightrec_salvage_dump(signum)   # black box first: the print
        try:                              # below may be the thing that dies
            print(json.dumps(_headline(words_per_sec_chip, {
                "truncated": f"bench interrupted by signal {signum}; "
                             "secondary metrics incomplete",
            }), allow_nan=False), flush=True)
            ok = True
        except BaseException:   # noqa: BLE001 — the exit must still run
            pass                # (an exception here must not turn the
        finally:                # truncation into a silent success)
            os._exit(TRUNCATED_EXIT if ok else 1)

    signal.signal(signal.SIGTERM, _salvage)

    # a phase that raises is recorded as {"error": ...} so the others
    # still run and the JSON line still prints — and the process then
    # exits non-zero, so a broken phase cannot pass for a complete run
    failed = []

    def phase(name, fn, **kw):
        try:
            return fn(**kw)
        except Exception as e:
            failed.append(name)
            return {"error": f"{type(e).__name__}: {e}"[:200]}

    we_ps_stats = phase("we_ps_block_path", bench_wordembedding_ps)
    we_real_stats = phase("we_realtext", bench_we_real)
    lr_real_stats = phase("lr_real_digits", bench_lr_real)
    wire_stats = phase("host_wire", bench_host_wire)
    async_ps_stats = phase("async_ps_plane", bench_async_ps)
    we_async_stats = phase("we_async_np4", bench_we_async)
    aggregate_np8_stats = phase("aggregate_np8_16MB", bench_aggregate_path,
                                world=8)
    aggregate_stats = phase("aggregate_np4_16MB", bench_aggregate_path)
    array_stats = bench_array_table()
    array_cpu_stats = phase("array_table_cpu", bench_array_table_cpu)
    lm_stats = phase("transformer_lm_bs8_seq512_d256_L4", bench_transformer)
    # MXU-saturating config; steps=24 smooths within-run noise, repeats=6
    # keeps the RECORDED number off one slow sample (one compile, six
    # measurements, best slope)
    lm_large = dict(steps=24, b=2, s=1024, dim=2048, layers=8, vocab=32768,
                    heads=16, repeats=6)
    lm_large_stats = phase("transformer_lm_472M_bs2_seq1024_d2048_L8",
                           bench_transformer, **lm_large)

    def attn_ab():
        # A/B: the same 472M step with XLA-native attention instead of
        # the Pallas flash kernel — what the kernel buys end-to-end.
        # SAME repeats as the flash arm: unequal sample counts would
        # bias the speedup toward whichever arm drew more
        xla_attn = bench_transformer(attn="local", **lm_large)
        return {
            "xla_native_attn_step_ms": xla_attn["lm_step_ms"],
            "flash_step_ms": lm_large_stats.get("lm_step_ms"),
            "flash_speedup": round(
                xla_attn["lm_step_ms"] / lm_large_stats["lm_step_ms"], 3)
            if lm_large_stats.get("lm_step_ms") else None,
        }

    lm_attn_ab = phase("transformer_lm_472M_attn_ab", attn_ab)
    resnet_stats = phase("resnet32_cifar_50k", bench_resnet)
    rows_stats = phase("matrix_sparse_row_add", bench_matrix_rows)
    decode_stats = phase("lm_decode_b8_d256_L4", bench_decode)
    small_add_stats = phase("small_add_send_window", bench_small_add_window)
    get_rows_stats = phase("get_rows_plane", bench_get_rows_plane)
    chaos_stats = phase("chaos", bench_chaos_failover)
    serving_stats = phase("serving", bench_dlrm_serving)
    scale_stats = phase("scale", bench_scale_curve)
    # telemetry-plane record: latency HISTOGRAMS of every monitored op
    # this process ran (shutdown resets the dashboard, so snapshot now)
    dashboard_hist = phase("dashboard_hist", _dashboard_hist)
    # cluster view (aggregator flag-gated; None on the default
    # single-process config). When polling was live, the merged
    # cross-rank monitor histograms REPLACE the local-only
    # dashboard_hist snapshot — a multi-process run's record must
    # reflect every rank's latencies, not just rank 0's monitors.
    cluster_stats = phase("cluster", _cluster_extra)
    if isinstance(cluster_stats, dict) and cluster_stats.get("monitors"):
        dashboard_hist = dict(cluster_stats["monitors"])
        dashboard_hist["_source"] = "cluster_aggregator (all ranks merged)"
    # flight-recorder plane, snapshotted BEFORE shutdown: a non-zero
    # count here means a FAULT dumped during the run (watchdog trip,
    # peer death, fatal) — a diagnosable anomaly even when every
    # sub-bench "succeeded". The routine Zoo.stop tape lands AFTER this
    # snapshot, so it never pollutes the anomaly signal; it still shows
    # up in tools/run_bench.py's dump-file listing (whose headers name
    # each dump's reason).
    from multiverso_tpu.telemetry import flightrec
    flightrec_dumps = phase("flightrec_dumps", flightrec.dump_stats)
    # memory plane (telemetry/memstats.py), snapshotted BEFORE shutdown
    # like the dashboard: one final ledger sample, then the run's peaks
    # — kernel-tracked VmHWM for RSS plus the sampled ledger/device
    # high-waters. run_bench.py flags >2x run-over-run growth of the
    # peak RSS / retained-frame bytes, never fails.
    from multiverso_tpu.telemetry import memstats as _memstats_mod
    memory_stats_rec = phase("memory", _memstats_mod.bench_extra)
    mv.shutdown()

    baseline_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "BENCH_BASELINE.json")
    if not os.path.exists(baseline_path):
        try:
            with open(baseline_path, "w") as f:
                json.dump({"we_words_per_sec_per_chip": words_per_sec_chip},
                          f)
        except OSError:
            pass

    extra = {
        "we_loss": round(we_stats["loss"], 4),
        "we_sec_per_epoch": round(we_stats["sec_per_epoch"], 4),
        "we_ps_block_path": we_ps_stats,
        "we_realtext": we_real_stats,
        "lr_real_digits": lr_real_stats,
        "host_wire": wire_stats,
        "async_ps_plane": async_ps_stats,
        "we_async_np4": we_async_stats,
        "aggregate_np4_16MB": aggregate_stats,
        "aggregate_np8_16MB": aggregate_np8_stats,
        "array_table_4M_float32": array_stats,
        "array_table_cpu": array_cpu_stats,
        "transformer_lm_bs8_seq512_d256_L4": lm_stats,
        "transformer_lm_472M_bs2_seq1024_d2048_L8": lm_large_stats,
        "transformer_lm_472M_attn_ab": lm_attn_ab,
        "resnet32_cifar_50k": resnet_stats,
        "matrix_sparse_row_add": rows_stats,
        "lm_decode_b8_d256_L4": decode_stats,
        "small_add_send_window": small_add_stats,
        "get_rows_plane": get_rows_stats,
        "chaos": chaos_stats,
        "serving": serving_stats,
        # mesh scale curve (ISSUE 12): T_n / E_n per shard count, the
        # SPMD hygiene verdict, and the device-plane cost attribution —
        # run_bench flags efficiency_min / t1_rows_per_s drops
        "scale": scale_stats,
        "dashboard_hist": dashboard_hist,
        "flightrec_dumps": flightrec_dumps,
        "memory": memory_stats_rec,
    }
    # phase-level profile of the WE async measured epoch (its step spans,
    # ISSUE 9): first-class extra key so tools/run_bench.py can flag
    # stall-fraction growth and steady-state recompiles run-over-run
    if isinstance(we_async_stats, dict) and we_async_stats.get("profile"):
        extra["profile"] = we_async_stats["profile"]
    # ISSUE 11: the tracked WE scale metric — words/s plus the per-phase
    # breakdown, parity verdict, and cache hit rate, first-class under
    # extra.we so run_bench flags a >2x words/s DROP run-over-run (the
    # higher-is-better direction) and the scale trajectory has a number
    if isinstance(we_async_stats, dict) \
            and "words_per_sec_aggregate" in we_async_stats:
        we_extra = {
            "words_per_s": we_async_stats["words_per_sec_aggregate"],
            "parity_ok": int(bool(
                we_async_stats.get("parity", {}).get("ok"))),
        }
        tc = we_async_stats.get("train_cache")
        if tc and tc.get("hit_rate") is not None:
            we_extra["train_cache_hit_rate"] = tc["hit_rate"]
        prof_b = we_async_stats.get("profile") or {}
        if prof_b.get("phase_ms_per_step"):
            we_extra["phase_ms_per_step"] = prof_b["phase_ms_per_step"]
            we_extra["stall_fraction"] = prof_b.get("stall_fraction")
        extra["we"] = we_extra
    if cluster_stats is not None:
        extra["cluster"] = cluster_stats
    # SLO sentinel episode counts (ISSUE 19, telemetry/slo.py): lifted
    # first-class from the chaos matrix so run_bench can flag an
    # objective that fired this run but not last, by name
    if isinstance(chaos_stats, dict) \
            and isinstance(chaos_stats.get("slo"), dict):
        extra["slo"] = chaos_stats["slo"]
    if _DEGENERATE_DIFFERENTIALS:
        # floored noise-negative slopes (see _differential): the raw pairs
        # stay on the record so a degenerate measurement is visible
        extra["degenerate_differentials"] = list(_DEGENERATE_DIFFERENTIALS)
    extra = _sanitize(extra)
    # bulky sub-bench detail goes to a side file; the driver-parsed line
    # stays compact, strictly-valid JSON (r02's record lost its headline to
    # an unparseable final line), last and alone on stdout
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        with open(os.path.join(here, "BENCH_EXTRA.json"), "w") as f:
            json.dump(extra, f, indent=1, allow_nan=False)
    except (OSError, ValueError, TypeError):
        pass
    # The salvage handler must not race the real line: restore default
    # SIGTERM handling before printing, so the complete headline is
    # always the last (and only) JSON line once it is out.
    import signal
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    print(json.dumps(_headline(words_per_sec_chip, {
        # 1M first: the per-run fixed costs amortize there, so it is
        # the headline PS-block number (the 120k row stays for
        # r02-comparability)
        "we_ps_block_words_per_sec_1M": _num(
            we_ps_stats.get("ps_words_per_sec_1M")),
        "we_ps_block_words_per_sec_120k": _num(
            we_ps_stats.get("ps_words_per_sec")),
        "detail": "BENCH_EXTRA.json",
        "failed_phases": failed,
    }), allow_nan=False), flush=True)
    if failed:
        sys.exit(f"bench.py: {len(failed)} phase(s) raised: {failed}")


def _num(x):
    """Round a possibly-missing/non-finite number for the headline line."""
    try:
        x = float(x)
    except (TypeError, ValueError):
        return None
    return round(x, 1) if np.isfinite(x) else None


def _headline(words_per_sec_chip, extra):
    """The driver-parsed JSON line — ONE builder shared by the normal
    path and the SIGTERM salvage path so the two can never drift."""
    vs_baseline = 1.0
    baseline_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "BENCH_BASELINE.json")
    try:
        with open(baseline_path) as f:
            recorded = float(
                json.load(f).get("we_words_per_sec_per_chip", 0) or 0)
        if recorded > 0:
            vs_baseline = words_per_sec_chip / recorded
    except (ValueError, TypeError, OSError):
        pass
    return {
        "metric": "WordEmbedding words/sec/chip (fused skipgram-NS, "
                  "synthetic zipf corpus, dim=128, neg=5)",
        "value": _num(words_per_sec_chip) or 0.0,
        "unit": "words/s/chip",
        "vs_baseline": round(vs_baseline, 3) if np.isfinite(vs_baseline)
        else 0.0,
        "device": _device_record(),
        "extra": extra,
    }


def _sanitize(obj):
    """Make an arbitrary bench-stats tree strictly-JSON-serializable:
    numpy scalars -> python, non-finite floats -> strings."""
    if isinstance(obj, dict):
        return {str(k): _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return _sanitize(obj.tolist())
    if isinstance(obj, (np.floating, np.integer, np.bool_)):
        obj = obj.item()
    if isinstance(obj, float) and not np.isfinite(obj):
        return repr(obj)
    if not isinstance(obj, (str, int, float, bool, type(None))):
        return repr(obj)
    return obj


if __name__ == "__main__":
    main()
