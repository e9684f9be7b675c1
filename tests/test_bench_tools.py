"""The bench workers themselves are load-bearing: the driver's official
run is the round's artifact of record, and a broken tool records an
error dict instead of a number. Smoke every multi-process bench path at
minimal scale (seconds, np=2) through the REAL spawn/collect machinery.
"""

import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402  (repo-root module, not a package)


@pytest.mark.parametrize("pattern", ["strided", "local", "paced"])
def test_async_ps_worker_patterns(pattern):
    r = bench._run_async_ps_world(2, "none", 1.0, pattern=pattern)
    assert r["rows_per_sec"] > 0
    assert r["get_p99_ms"] >= r["get_p50_ms"] > 0
    if pattern in ("local", "paced"):
        # pooled-percentile path engaged (raw samples were reported)
        assert r["p99_over_p50"] > 0 and r["n_lat_samples"] > 0
    if pattern == "paced":
        # offered load is 150 add+get pairs/s plane-wide, 1024 rows each
        # way: 150 * 2 * 1024 rows/s
        assert r["rows_per_sec"] == pytest.approx(150 * 2 * 1024,
                                                  rel=0.15)


def test_aggregate_worker_all_variants():
    r = bench.bench_aggregate_path(world=2, mb=1.0)
    for k in ("process_sum_ms", "allgather_ms", "allgather_bf16_ms"):
        assert r[k] > 0, r
    for k in ("speedup", "bf16_vs_plain"):
        assert np.isfinite(r[k]), r
    assert not any("1bit" in k for k in r), r


def test_we_async_worker_tiny():
    """Tier-1 smoke of the full pipelined bench path (ISSUE 11): the
    np=2 measured run takes the producer-queue + training-cache path
    with the step profiler's stall/attribution gates asserted IN-RUN by
    the worker, and the parity stage (world=1, pipeline vs oracle)
    asserts bit-identical embedding digests — so this tiny run proves
    every in-run gate actually executes, not just that numbers exist."""
    r = bench.bench_we_async(world=2, n_tokens=30_000)
    assert r["words_per_sec_aggregate"] > 0
    assert len(r["words_per_sec_per_worker"]) == 2
    assert np.isfinite(r["loss_mean"])
    # the ISSUE-11 gates ran: bit parity vs the unpipelined oracle...
    assert r["parity"] == {"ok": True, "tokens": 30_000}
    # ...the platform-gated words/s floor (recorded; enforced on TPU)...
    assert r["perf_gate"]["target_words_per_s"] == 2_000_000
    assert r["perf_gate"]["enforced"] is False        # CPU bench box
    # ...and the training cache actually served on the measured run
    assert r["train_cache"]["hit_rate"] is not None
    # profiler gates (attribution >= 0.90, stall < 0.2, zero steady
    # recompiles) are asserted inside the workers; the profile block
    # surviving to the record means they passed. The block must EXIST:
    # the measured run always brackets steps (_prof.step per block), so
    # a missing block means the worker's zero-steps guard skipped every
    # in-run gate — the acceptance gates going silently dark, not a
    # benign config difference
    assert r.get("profile"), "profiler recorded no steps — in-run gates skipped"
    assert r["profile"]["stall_fraction"] < 0.2
    assert r["profile"]["attributed_fraction"] >= 0.90


def test_array_table_bench_smoke():
    """Tier-1 smoke of the full bench_array_table path at toy scale: a
    regression of the one Add path or the one Get path surfaces here
    instead of only in a full driver bench run. Asserts the dashboard
    reports the benched table's counters."""
    import multiverso_tpu as mv
    from multiverso_tpu.utils.dashboard import Dashboard

    mv.init()
    r = bench.bench_array_table(size=10_000, iters=2)
    assert r["add_p50_ms"] > 0 and r["get_p50_ms"] > 0
    assert r["pipelined_add_ms"] > 0 and r["get_parity_bit_for_bit"]
    # the filter, the cached repeat Get and the prefetch left with PRs 29
    # and 46: the record names none of them
    for gone in ("wire_filtered", "get_repeat_cached_ms", "get_cache_hits",
                 "get_prefetch_hits"):
        assert gone not in r
    snap = Dashboard.snapshot()
    for op in ("add", "get"):
        key = f"table[bench_array].{op}"
        assert key in snap and snap[key].count > 0, key


def test_dump_metrics_tool(tmp_path):
    """tools/dump_metrics smoke: show/diff real exporter records and
    wrap a JSONL trace for Perfetto — the bench-comparison workflow the
    telemetry plane exists for."""
    import json
    import time

    from multiverso_tpu.telemetry.exporter import MetricsExporter
    from multiverso_tpu.utils.dashboard import Dashboard, monitor
    from tools.dump_metrics import (diff_records, format_record,
                                    load_records, main, pick_record,
                                    to_perfetto)

    def payload():
        return {"rank": 0,
                "monitors": {n: s.hist_dict()
                             for n, s in Dashboard.snapshot().items()},
                "notes": {"n": "x = 1"},
                "shards": {"t": {"kind": "row", "adds": 2,
                                 "queue_depth": 0}}}

    with monitor("tool.op"):
        time.sleep(0.001)
    exp = MetricsExporter(0, str(tmp_path), 0.0, payload)
    exp.export_once()
    with monitor("tool.op"):
        pass
    exp.export_once()
    path = str(tmp_path / "metrics-rank0.jsonl")
    recs = load_records(path)
    assert len(recs) == 2
    text = format_record(pick_record(recs))
    assert "tool.op" in text and "p50" in text and "shard[t]" in text
    dtext = diff_records(recs[0], recs[1])
    assert "tool.op" in dtext and "p50 b/a" in dtext
    # trace wrap: JSONL events -> Perfetto envelope
    tpath = str(tmp_path / "trace.jsonl")
    with open(tpath, "w") as f:
        f.write(json.dumps({"name": "s", "ph": "X", "ts": 1, "dur": 2,
                            "pid": 0, "tid": 1, "args": {}}) + "\n")
    out = str(tmp_path / "trace.json")
    assert to_perfetto(tpath, out) == 1
    with open(out) as f:
        env = json.load(f)
    assert env["traceEvents"][0]["name"] == "s"
    # CLI entry points return 0
    assert main(["show", path]) == 0
    assert main(["diff", path, path]) == 0


def test_bench_main_refuses_a_cpu():
    """bench.py prints device numbers only: with no TPU main() exits
    non-zero before it measures anything."""
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)


def test_bench_truncation_recording(tmp_path):
    """The SIGTERM salvage exits bench.TRUNCATED_EXIT (documented,
    nonzero, distinct from a hard failure) and tools/run_bench records
    the distinction — a timeout-truncated run can never masquerade as a
    complete one."""
    import json

    from tools.run_bench import last_json_line, record

    assert bench.TRUNCATED_EXIT not in (0, 1)
    headline = {"metric": "m", "value": 1.0, "vs_baseline": 1.0,
                "extra": {"truncated": "bench interrupted by signal 15"}}
    out = "log noise\n" + json.dumps(headline) + "\n"
    rec = record(bench.TRUNCATED_EXIT, out)
    assert rec["truncated"] and not rec["complete"]
    assert rec["headline"]["value"] == 1.0
    complete = {"metric": "m", "value": 2.0, "vs_baseline": 1.0,
                "extra": {}}
    rec2 = record(0, json.dumps(complete))
    assert not rec2["truncated"] and rec2["complete"]
    # belt: the headline's own salvage marker flags truncation even if
    # the exit status was lost by a wrapper — and the record can never
    # be simultaneously complete and truncated
    rec3 = record(0, out)
    assert rec3["truncated"] and not rec3["complete"]
    assert last_json_line("no json here") is None


def test_flightrec_dumps_recorded(tmp_path, monkeypatch):
    """PR-4 CI satellite: the bench SIGTERM salvage dumps the flight
    recorder, and tools/run_bench records which dump files a run left —
    a truncated run is diagnosable from the recorded artifact alone."""
    import json

    from multiverso_tpu.telemetry import flightrec
    from tools.run_bench import collect_flightrec_dumps, record

    # the salvage hook itself (separable from the live signal handler)
    monkeypatch.setenv("MV_FLIGHTREC_DIR", str(tmp_path))
    flightrec.record(flightrec.EV_STATE, note="pre-salvage traffic")
    path = bench._flightrec_salvage_dump(15)
    assert path is not None and os.path.exists(path)
    with open(path) as f:
        recs = [json.loads(x) for x in f]
    assert recs[0]["reason"].startswith("bench salvage: signal 15")
    assert any(r.get("ev") == "signal" for r in recs)
    # ...and the recording side: the dump listing lands in the artifact
    dumps = collect_flightrec_dumps(str(tmp_path))
    assert dumps == [os.path.basename(path)]
    rec = record(bench.TRUNCATED_EXIT, "{}", flightrec_dumps=dumps)
    assert rec["truncated"] and rec["flightrec_dumps"] == dumps
    # a clean run with no dump dir records an empty listing, not a crash
    assert collect_flightrec_dumps(str(tmp_path / "never-made")) == []
    assert record(0, "{}")["flightrec_dumps"] == []
    # review regression: the dump dir is reused across runs — a stale
    # dump from a PREVIOUS run must not be attributed to this one
    import time as _time
    assert collect_flightrec_dumps(str(tmp_path),
                                   since=_time.time() + 60) == []
    assert collect_flightrec_dumps(str(tmp_path), since=0.0) == dumps


def test_get_rows_bench_smoke():
    """Tier-1 smoke of tools/bench_get_rows.py (ISSUE 5 read-path bench)
    at toy scale through the REAL subprocess spawn/collect machinery:
    both parity gates are in-run assertions, so a pass here means the
    coalesced and chunk-streamed planes returned exact bytes."""
    import json
    import subprocess

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "bench_get_rows.py"),
         "30", "2000"],
        capture_output=True, text=True, timeout=240, env=env, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-800:]
    line = [x for x in out.stdout.splitlines()
            if x.startswith("RESULT ")][-1]
    r = json.loads(line[len("RESULT "):])
    assert r["parity_bit_for_bit"] and r["chunk_parity_bit_for_bit"]
    assert r["small_get_on_p50_ms"] > 0 and r["small_get_off_p50_ms"] > 0
    assert r["big_get_chunked_ms"] > 0
    # the fan-in phase must have actually deduped something
    assert r["fanout_frames"] < r["fanout_gets"]


def test_run_bench_regression_flagging():
    """ISSUE 5 CI satellite: run_bench FLAGS (never fails) a >2x
    latency regression of the get/small-add planes vs the previous
    recorded BENCH file, and skips keys either side is missing."""
    from tools.run_bench import flag_regressions

    prev = {"extra": {
        "get_rows_plane": {"small_get_on_p50_ms": 0.5,
                           "small_get_off_p50_ms": 0.6,
                           "big_get_chunked_ms": 20.0},
        "small_add_send_window": {"window_on_p50_ms": 0.04},
    }}
    same = flag_regressions(prev, prev)
    assert same == []
    worse = {"extra": {
        "get_rows_plane": {"small_get_on_p50_ms": 1.2,   # 2.4x: flagged
                           "small_get_off_p50_ms": 0.9,  # 1.5x: fine
                           "big_get_chunked_ms": 90.0},  # 4.5x: flagged
        "small_add_send_window": {"window_on_p50_ms": 0.05},
    }}
    flags = flag_regressions(prev, worse)
    assert len(flags) == 2
    assert any("coalesced small-get p50" in f for f in flags)
    assert any("chunked big-get" in f for f in flags)
    # missing keys (older record / errored sub-bench) are skipped
    assert flag_regressions(None, worse) == []
    assert flag_regressions({"extra": {}}, worse) == []
    assert flag_regressions(
        prev, {"extra": {"get_rows_plane": {"error": "boom"}}}) == []


def test_run_bench_flags_skew_growth():
    """ISSUE 6 satellite: when both records carry a cluster snapshot
    (the stats aggregator ran), >2x run-over-run shard-skew growth is
    FLAGGED (never fails the run); missing/partial cluster data is
    skipped like any other absent key. Worker-level cluster blocks
    (e.g. small_add_send_window.cluster) are scanned too."""
    from tools.run_bench import flag_regressions

    def rec(skew, nested=False):
        cluster = {"tables": {"we": {"adds": 10, "skew": skew}}}
        extra = ({"small_add_send_window": {"cluster": cluster}}
                 if nested else {"cluster": cluster})
        return {"extra": extra}

    assert flag_regressions(rec(1.1), rec(1.9)) == []       # 1.7x: fine
    flags = flag_regressions(rec(1.1), rec(2.5))            # 2.3x
    assert len(flags) == 1 and "table[we] shard skew" in flags[0]
    # nested worker-level cluster blocks count as well
    flags = flag_regressions(rec(1.1, nested=True), rec(2.5, nested=True))
    assert len(flags) == 1 and "shard skew" in flags[0]
    # one side missing the cluster record: skipped, never flagged
    assert flag_regressions({"extra": {}}, rec(9.0)) == []
    assert flag_regressions(rec(1.0), {"extra": {}}) == []


def test_run_bench_flags_serving_regressions():
    """ISSUE 8 satellite: run_bench FLAGS (never fails) a >2x
    run-over-run growth of the serving plane's inference p99 AND a >2x
    served-QPS DROP (the higher-is-better mirror); missing serving data
    (errored bench, older record) is skipped."""
    from tools.run_bench import flag_regressions

    def rec(p99, qps):
        return {"extra": {"serving": {"infer_p99_ms": p99,
                                      "served_qps": qps}}}

    assert flag_regressions(rec(5.0, 1000), rec(9.0, 900)) == []
    # p99 grew 2.4x: flagged
    flags = flag_regressions(rec(5.0, 1000), rec(12.0, 1000))
    assert len(flags) == 1 and "serving inference p99" in flags[0]
    # served QPS dropped 2.5x: flagged (higher-is-better direction)
    flags = flag_regressions(rec(5.0, 1000), rec(5.0, 400))
    assert len(flags) == 1 and "serving served QPS" in flags[0]
    assert "drop" in flags[0]
    # QPS GROWTH is never flagged, nor is missing data
    assert flag_regressions(rec(5.0, 1000), rec(5.0, 9000)) == []
    assert flag_regressions({"extra": {}}, rec(12.0, 100)) == []
    assert flag_regressions(
        rec(5.0, 1000), {"extra": {"serving": {"error": "boom"}}}) == []


def test_run_bench_flags_we_words_drop():
    """ISSUE 11 satellite: a >2x run-over-run DROP of the WE async
    plane's words/s (extra.we.words_per_s, higher-is-better direction)
    is FLAGGED — never fails the run; growth and missing data are
    skipped. This is the tracked scale-trajectory metric for ROADMAP
    item 2."""
    from tools.run_bench import flag_regressions

    def rec(wps):
        return {"extra": {"we": {"words_per_s": wps, "parity_ok": 1}}}

    assert flag_regressions(rec(2.0e6), rec(1.5e6)) == []
    flags = flag_regressions(rec(2.0e6), rec(0.8e6))
    assert len(flags) == 1 and "WE async words/s" in flags[0]
    assert "drop" in flags[0]
    # growth is never flagged, nor is missing data on either side
    assert flag_regressions(rec(0.5e6), rec(3.0e6)) == []
    assert flag_regressions({"extra": {}}, rec(1.0e6)) == []
    assert flag_regressions(rec(1.0e6), {"extra": {}}) == []


def test_run_bench_flags_chaos_recovery_growth():
    """ISSUE 7 satellite: >2x run-over-run growth of the chaos bench's
    recovery-time-to-full-throughput (extra.chaos.recovery_s) is
    FLAGGED — never fails the run — mirroring the skew flag; missing
    chaos data (bench errored, older record) is skipped."""
    from tools.run_bench import flag_regressions

    def rec(recovery_s):
        return {"extra": {"chaos": {"recovery_s": recovery_s,
                                    "ops_lost": 0}}}

    assert flag_regressions(rec(4.0), rec(6.0)) == []        # 1.5x: fine
    flags = flag_regressions(rec(4.0), rec(9.0))             # 2.25x
    assert len(flags) == 1
    assert "chaos failover recovery time" in flags[0]
    # missing on either side (errored chaos bench, older record): skip
    assert flag_regressions({"extra": {}}, rec(9.0)) == []
    assert flag_regressions(
        rec(4.0), {"extra": {"chaos": {"error": "boom"}}}) == []
