"""Memory observability plane (ISSUE 10): the byte ledger, deliberate-
leak verdicts (epoch-hoard, retention-leak), the flag-off null path,
the MSG_STATS "memory" block through aggregator/mvtop/exporter/
dump_metrics, OOM forensics through the flight-recorder dump path +
postmortem's memory timeline, the stats-surface lint, and the
run_bench memory regression flags. All tier-1 (CPU, seconds)."""

import gc
import json
import os
import sys
import time
import tracemalloc

import numpy as np
import pytest

from multiverso_tpu.ps.shard import RowShard
from multiverso_tpu.ps.tables import AsyncMatrixTable
from multiverso_tpu.telemetry import flightrec, memstats, watchdog
from multiverso_tpu.updaters import AddOption, get_updater
from multiverso_tpu.utils import config

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _ring_kinds(last=32):
    return [s[2] for s in flightrec.RECORDER.snapshot(last=last)]


# ---------------------------------------------------------------------- #
# the ledger itself
# ---------------------------------------------------------------------- #
class TestLedger:
    def test_register_snapshot_totals_and_dead_prune(self):
        class C:
            def __init__(self, n):
                self.n = n

            def memory_stats(self):
                return {"x_bytes": self.n, "pins": 1, "note": "raw"}

        a, b = C(100), C(28)
        na = memstats.register("comp", a)
        nb = memstats.register("comp", b)   # collision -> suffixed
        assert na == "comp" and nb != "comp"
        snap = memstats.LEDGER.snapshot()
        assert snap["components"][na]["x_bytes"] == 100
        assert snap["totals"]["x_bytes"] == 128   # *_bytes summed
        assert snap["totals"]["pins"] == 2        # count-total key
        assert "note" not in snap["totals"]       # strings never sum
        del b
        gc.collect()
        snap = memstats.LEDGER.snapshot()
        assert nb not in snap["components"]       # dead ref pruned
        assert snap["totals"]["x_bytes"] == 100

    def test_bad_gauge_isolated(self):
        class Bad:
            def memory_stats(self):
                raise RuntimeError("boom")

        class Good:
            def memory_stats(self):
                return {"y_bytes": 7}

        bad, good = Bad(), Good()
        memstats.register("bad", bad)
        memstats.register("good", good)
        snap = memstats.LEDGER.snapshot()
        assert "error" in snap["components"]["bad"]
        assert snap["totals"]["y_bytes"] == 7

    def test_reset_keeps_importtime_registrations(self):
        """reset() (the per-test isolation hook) must NOT unregister
        components: checkpoint.py registers its gauges once at module
        import, and clearing them would leave that plane dark for
        every test after the first."""
        import multiverso_tpu.checkpoint   # noqa: F401 — registers
        assert "checkpoint" in memstats.LEDGER.snapshot()["components"]
        memstats.sample_once()
        memstats.reset()
        assert memstats.LEDGER.samples() == []          # history gone
        snap = memstats.LEDGER.snapshot()
        assert "checkpoint" in snap["components"]       # gauges stay

    def test_sample_and_stats_snapshot_json_safe(self):
        s = memstats.sample_once()
        assert s["rss_mb"] is None or s["rss_mb"] > 0
        blk = memstats.stats_snapshot()
        json.dumps(blk)   # must be wire-safe (MSG_STATS meta)
        assert blk["samples"] >= 1
        assert "totals" in blk and "components" in blk

    def test_read_rss_and_device_census(self):
        rss, hwm = memstats.read_rss()
        if rss is not None:   # /proc present (linux CI)
            assert rss > 0
            # VmHWM can be absent on stripped kernels; when present
            # (or ru_maxrss fell in) it bounds the live reading
            assert hwm is None or hwm >= rss
        import jax.numpy as jnp
        keep = jnp.ones((64, 64), jnp.float32)
        census = memstats.device_census()
        assert census is not None and census["bytes"] >= keep.nbytes
        assert any(g["shape"] == "(64, 64)" for g in census["top"])


# ---------------------------------------------------------------------- #
# shard gauges: pins, retired epochs, queue bytes
# ---------------------------------------------------------------------- #
class TestShardGauges:
    def _shard(self, name="mem_sh"):
        return RowShard(0, 64, 8, np.float32, get_updater("sgd"), name)

    def test_pin_registry_and_retired_bytes(self):
        sh = self._shard()
        g0 = sh.memory_stats()
        assert g0["table_bytes"] > 0 and g0["pins"] == 0
        pin = sh._pin_data()
        g1 = sh.memory_stats()
        assert g1["pins"] == 1 and g1["pinned_epochs"] == 1
        assert g1["retired_epochs"] == 0
        # COW applies while pinned: the pinned buffer retires, and the
        # gauge counts it (deduped by buffer identity — many applies,
        # ONE retired epoch)
        for _ in range(3):
            sh._apply_rows(np.array([1, 2, 3]),
                           np.ones((3, 8), np.float32), AddOption())
        g2 = sh.memory_stats()
        assert g2["retired_epochs"] == 1
        assert g2["retired_bytes"] == g1["table_bytes"]
        assert g2["oldest_pin_age_s"] >= 0.0
        sh._release_data(pin)
        g3 = sh.memory_stats()
        assert g3["pins"] == 0 and g3["retired_bytes"] == 0

    def test_two_pins_same_epoch_dedupe(self):
        sh = self._shard("mem_sh2")
        p1, p2 = sh._pin_data(), sh._pin_data()
        sh._apply_rows(np.array([1]), np.ones((1, 8), np.float32),
                       AddOption())
        g = sh.memory_stats()
        assert g["pins"] == 2 and g["retired_epochs"] == 1
        # same retired buffer under both pins: bytes counted ONCE
        assert g["retired_bytes"] == g["table_bytes"]
        sh._release_data(p1)
        sh._release_data(p2)

    def test_contended_lock_serves_stale_cache_nonblocking(self):
        """The watchdog sweep drives gauge pulls: a pull racing a held
        shard lock (a long/wedged apply) must return the last reading
        marked stale IMMEDIATELY, never block."""
        import threading

        sh = self._shard("mem_stale")
        fresh = sh.memory_stats()
        assert "stale" not in fresh
        holding = threading.Event()
        release = threading.Event()

        def hold():
            with sh._lock:
                holding.set()
                release.wait(10.0)

        th = threading.Thread(target=hold, daemon=True)
        th.start()
        holding.wait(5.0)
        t0 = time.monotonic()
        g = sh.memory_stats()
        assert time.monotonic() - t0 < 1.0   # did not block
        assert g.get("stale") is True
        assert g["table_bytes"] == fresh["table_bytes"]   # cached core
        assert "queue_depth" in g   # queue gauges still live
        release.set()
        th.join(5.0)
        assert "stale" not in sh.memory_stats()

    def test_ledger_sees_shard(self):
        sh = self._shard("mem_sh3")
        snap = memstats.LEDGER.snapshot()
        assert any(k.startswith("shard[mem_sh3:")
                   for k in snap["components"])
        assert snap["totals"]["table_bytes"] >= sh.memory_stats()[
            "table_bytes"]


# ---------------------------------------------------------------------- #
# deliberate-leak suite: the verdicts
# ---------------------------------------------------------------------- #
class TestEpochHoardVerdict:
    def test_hoard_detected_via_watchdog_and_ring(self):
        """Hold a get pin while applies COW: the watchdog sweep must
        call epoch-hoard, with the gauge counting the retired buffers
        and one mem.epoch_hoard event on the ring."""
        sh = RowShard(0, 64, 8, np.float32, get_updater("sgd"), "hoard")
        config.set_flag("memstats_pin_age_s", 0.01)
        pin = sh._pin_data()
        for _ in range(4):
            sh._apply_rows(np.array([0, 1]),
                           np.ones((2, 8), np.float32), AddOption())
        time.sleep(0.03)
        watchdog.check_once()   # the PR-4 sweep drives the verdicts
        verdicts = memstats.LEDGER.verdicts()
        hoard = [v for v in verdicts if v["kind"] == "epoch-hoard"]
        assert hoard and hoard[-1]["component"].startswith(
            "shard[hoard:")
        assert hoard[-1]["retired_bytes"] == sh.memory_stats()[
            "table_bytes"]
        assert hoard[-1]["retired_epochs"] == 1
        assert flightrec.EV_MEM_HOARD in _ring_kinds()
        # one event per episode: a second sweep stays silent
        n = len(memstats.LEDGER.verdicts())
        watchdog.check_once()
        assert len(memstats.LEDGER.verdicts()) == n
        # release clears the episode; a fresh hoard re-fires
        sh._release_data(pin)
        watchdog.check_once()
        pin2 = sh._pin_data()
        sh._apply_rows(np.array([0]), np.ones((1, 8), np.float32),
                       AddOption())
        time.sleep(0.03)
        watchdog.check_once()
        assert len(memstats.LEDGER.verdicts()) == n + 1
        sh._release_data(pin2)


class TestRetentionLeakVerdict:
    def test_growing_retained_tail_with_live_owner(self, two_ranks):
        """Wedge a replay owner's retention: with ps_replay on and NO
        failover checkpointer advancing the durable floor, every acked
        window frame stays retained — monotonic growth across
        RETENTION_K samples with a live owner must call
        retention-leak."""
        config.set_flag("ps_replay", True)
        t0 = AsyncMatrixTable(64, 8, name="ret", ctx=two_ranks[0],
                              send_window_ms=1.0)
        AsyncMatrixTable(64, 8, name="ret", ctx=two_ranks[1])
        series = []
        for i in range(memstats.RETENTION_K):
            # remote-owned rows: rank 1 owns [32, 64)
            t0.add_rows_async([40 + i], np.ones((1, 8), np.float32))
            t0.flush()
            s = memstats.sample_once()
            w = [g for n, g in memstats.LEDGER.snapshot()[
                "components"].items() if n == "window[ret]"][0]
            series.append(w["retained_bytes"])
        assert series[0] > 0
        assert all(a < b for a, b in zip(series, series[1:])), series
        leaks = [v for v in memstats.LEDGER.verdicts()
                 if v["kind"] == "retention-leak"]
        # the verdict judges PER OWNER (rank 1 owns the hoarded tail)
        assert leaks and leaks[-1]["component"] == "window[ret]@1"
        assert flightrec.EV_MEM_LEAK in _ring_kinds(last=64)
        # the sample history carried component AND per-owner series
        assert s["retained"]["window[ret]"] == series[-1]
        assert s["retained"]["window[ret]@1"] == series[-1]

    def test_armed_frames_suppress_the_verdict(self):
        """A dead owner's re-armed tail is failover WORKING: growth
        with armed_frames > 0 must stay verdict-free."""

        class FakeWindow:
            def __init__(self):
                self.rb = 1

            def memory_stats(self):
                self.rb *= 2
                return {"retained_bytes": self.rb, "retained_frames": 1,
                        "armed_frames": 3, "pending_bytes": 0}

        w = FakeWindow()
        memstats.register("window[dead]", w)
        for _ in range(memstats.RETENTION_K + 1):
            memstats.sample_once()
        assert not [v for v in memstats.LEDGER.verdicts()
                    if v["kind"] == "retention-leak"]

    def test_dead_owner_does_not_mask_live_owner(self):
        """Per-owner granularity: owner 1's re-armed tail (dead, being
        failed over) must not suppress the verdict for owner 0, whose
        acked frames are growing with nothing pruning them."""

        class TwoOwnerWindow:
            def __init__(self):
                self.rb = 64

            def memory_stats(self):
                self.rb *= 2
                return {
                    "pending_bytes": 0, "retained_frames": 2,
                    "retained_bytes": 2 * self.rb,
                    "armed_frames": 3,   # window aggregate: nonzero
                    "owners": {
                        "0": {"retained_frames": 1,
                              "retained_bytes": self.rb,
                              "armed_frames": 0},       # live hoarder
                        "1": {"retained_frames": 1,
                              "retained_bytes": self.rb,
                              "armed_frames": 3},       # dead, re-armed
                    }}

        w = TwoOwnerWindow()
        memstats.register("window[mixed]", w)
        for _ in range(memstats.RETENTION_K):
            memstats.sample_once()
        leaks = {v["component"] for v in memstats.LEDGER.verdicts()
                 if v["kind"] == "retention-leak"}
        assert "window[mixed]@0" in leaks
        assert "window[mixed]@1" not in leaks
        assert "window[mixed]" not in leaks   # owners granularity wins


class TestFlagOffNullPath:
    def test_no_sampler_no_samples(self):
        assert config.get_flag("memstats_interval_s") == 0
        assert memstats.maybe_sample() is None
        assert memstats.ensure_started() is None
        assert memstats.LEDGER._thread is None
        assert memstats.LEDGER.samples() == []

    def test_zero_memstats_allocations_on_small_add_hot_path(
            self, two_ranks):
        """The ledger is registration-only: with the sampler flag off
        (the default), the windowed small-add hot path must execute
        ZERO lines of memstats.py — tracemalloc, filtered to the
        module, sees no allocations across 50 windowed adds.

        The probe runs against a quiesced world: the watchdog thread
        is stopped (its 0.5 s sweep legitimately runs memstats'
        verdict code on its OWN thread and would pollute — or, on
        3.10, race — the trace), and the send window is held wide
        open so the probe measures exactly the client enqueue path
        with no concurrent wire traffic."""
        watchdog.stop_global()
        t0 = AsyncMatrixTable(64, 8, name="null", ctx=two_ranks[0],
                              send_window_ms=10_000.0)
        AsyncMatrixTable(64, 8, name="null", ctx=two_ranks[1])
        for i in range(8):   # warm conns/compile outside the probe
            t0.add_rows_async([40], np.ones((1, 8), np.float32))
        t0.flush()
        tracemalloc.start()
        try:
            s1 = tracemalloc.take_snapshot()
            for i in range(50):
                t0.add_rows_async([40 + (i % 8)],
                                  np.ones((1, 8), np.float32))
            s2 = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        t0.flush()
        flt = [tracemalloc.Filter(True, "*memstats.py")]
        stats = s2.filter_traces(flt).compare_to(
            s1.filter_traces(flt), "filename")
        grew = [st for st in stats if st.size_diff > 0
                or st.count_diff > 0]
        assert not grew, f"memstats allocated on the hot path: {grew}"
        assert memstats.LEDGER.samples() == []


# ---------------------------------------------------------------------- #
# window / table / replica gauges
# ---------------------------------------------------------------------- #
class TestComponentGauges:
    def test_window_pending_and_retained_gauges(self, two_ranks):
        config.set_flag("ps_replay", True)
        t0 = AsyncMatrixTable(64, 8, name="wg", ctx=two_ranks[0],
                              send_window_ms=500.0)
        AsyncMatrixTable(64, 8, name="wg", ctx=two_ranks[1])
        t0.add_rows_async([40], np.ones((1, 8), np.float32))
        w = t0._window
        g = w.memory_stats()
        assert g["pending_ops"] == 1 and g["pending_bytes"] > 0
        t0.flush()
        g = w.memory_stats()
        assert g["pending_ops"] == 0
        assert g["retained_frames"] == 1 and g["retained_bytes"] > 0
        assert g["armed_frames"] == 0
        assert g["owners"]["1"]["retained_frames"] == 1

    def test_replica_gauges(self, two_ranks):
        from multiverso_tpu.serving import ReadReplica
        t0 = AsyncMatrixTable(64, 4, name="repm", ctx=two_ranks[0],
                              seed=0, init_scale=0.1)
        AsyncMatrixTable(64, 4, name="repm", ctx=two_ranks[1])
        rep = ReadReplica(t0, start=False, staleness_s=30.0)
        rep.refresh()
        g = rep.memory_stats()
        assert g["snapshot_bytes"] == 64 * 4 * 4
        assert g["staging_bytes"] == 0   # transient, cleared at swap
        rep.close()


# ---------------------------------------------------------------------- #
# MSG_STATS block -> aggregator -> mvtop / exporter / dump_metrics
# ---------------------------------------------------------------------- #
class TestStatsSurface:
    def test_stats_payload_memory_block_and_cluster_merge(
            self, two_ranks):
        from multiverso_tpu.telemetry import aggregator
        t0 = AsyncMatrixTable(64, 8, name="memtab", ctx=two_ranks[0])
        AsyncMatrixTable(64, 8, name="memtab", ctx=two_ranks[1])
        t0.add_rows([40], np.ones((1, 8), np.float32))
        payload = two_ranks[0].service.stats_payload()
        mem = payload["memory"]
        assert mem["totals"]["table_bytes"] > 0
        json.dumps(payload)
        stats = {r: two_ranks[r].service.stats_payload()
                 for r in range(2)}
        health = {r: two_ranks[r].service.health_payload()
                  for r in range(2)}
        rec = aggregator.merge_cluster(stats, health, world=2)
        assert set(rec["memory"]["ranks"]) == {"0", "1"}
        # in-process 2-rank world: ONE process, totals summed once
        assert (rec["memory"]["totals"]["table_bytes"]
                == mem["totals"]["table_bytes"])
        # compact_record keeps the block for bench extra
        assert aggregator.compact_record(rec)["memory"] == rec["memory"]
        # mvtop renders the panel
        from tools import mvtop
        out = mvtop.render(rec)
        assert "memory:" in out and "rss_mb" in out

    def test_mvtop_once_live_memory_panel(self, two_ranks, tmp_path,
                                          capsys):
        """ISSUE 10 acceptance: mvtop --once against a live 2-rank
        world renders the memory panel with nonzero per-rank table
        bytes and RSS."""
        from tools import mvtop
        t0 = AsyncMatrixTable(64, 8, name="mvm", ctx=two_ranks[0])
        AsyncMatrixTable(64, 8, name="mvm", ctx=two_ranks[1])
        t0.add_rows([40], np.ones((1, 8), np.float32))
        # the fixture's FileRendezvous already published <rank>.addr
        rc = mvtop.main(["--rdv", str(tmp_path / "rdv"), "--once",
                         "--json"])
        assert rc == 0
        rec = json.loads(capsys.readouterr().out)
        ranks = rec["memory"]["ranks"]
        assert set(ranks) == {"0", "1"}
        for e in ranks.values():
            assert e["table_bytes"] > 0
            assert e["rss_mb"] is None or e["rss_mb"] > 0
        assert rec["memory"]["totals"]["table_bytes"] > 0
        out = mvtop.render(rec)
        assert "memory:" in out and "pinned epochs" in out

    def test_exporter_prometheus_memory_gauges(self):
        from multiverso_tpu.telemetry.exporter import prometheus_text
        sh = RowShard(0, 64, 8, np.float32, get_updater("sgd"), "prom")
        txt = prometheus_text({"rank": 3,
                               "memory": memstats.stats_snapshot()})
        assert 'mv_mem_total_table_bytes{rank="3"}' in txt
        assert 'component="shard[prom:0-64]"' in txt
        assert 'field="table_bytes"' in txt
        if memstats.read_rss()[0] is not None:
            assert 'mv_mem_rss_mb{rank="3"}' in txt

    def test_dump_metrics_show_and_diff_memory(self):
        from tools import dump_metrics
        sh = RowShard(0, 64, 8, np.float32, get_updater("sgd"), "dmem")
        assert sh is not None   # keep the weakref'd component alive
        a = {"rank": 0, "memory": memstats.stats_snapshot()}
        out = dump_metrics.format_record(a)
        assert "memory: rss" in out and "shard[dmem:0-64]" in out
        b = json.loads(json.dumps(a))
        b["memory"]["rss_mb"] = (a["memory"]["rss_mb"] or 0) + 100
        b["memory"]["totals"] = dict(b["memory"]["totals"])
        b["memory"]["totals"]["table_bytes"] = (
            a["memory"]["totals"]["table_bytes"] + 4096)
        diff = dump_metrics.diff_records(a, b)
        assert "memory deltas" in diff
        assert "totals.table_bytes" in diff
        # cluster records carry the block through format/diff too
        rec = {"kind": "cluster", "ts": 1.0, "world": 1, "ranks": {},
               "memory": {"ranks": {"0": {"rss_mb": 10.0}},
                          "totals": {"table_bytes": 2080}}}
        assert "memory(cluster)" in dump_metrics.format_cluster_record(
            rec)


# ---------------------------------------------------------------------- #
# OOM forensics + postmortem memory timeline
# ---------------------------------------------------------------------- #
class TestOOMForensics:
    def test_rss_soft_limit_trips_fault_dump(self, tmp_path):
        config.set_flag("flightrec_dir", str(tmp_path))
        config.set_flag("memstats_rss_limit_mb", 0.5)   # any RSS trips
        rss, _ = memstats.read_rss()
        if rss is None:
            pytest.skip("no /proc RSS on this platform")
        memstats.sample_once()
        path = tmp_path / "flightrec-rank0.jsonl"
        assert path.exists()
        kinds = [json.loads(ln)["kind"]
                 for ln in path.read_text().splitlines()]
        assert "memory" in kinds and "memsample" in kinds
        assert flightrec.EV_MEM_RSS in _ring_kinds()
        assert flightrec.EV_MEM_DUMP in _ring_kinds()
        # one dump per episode: sampling again does not re-trip
        n = len([v for v in memstats.LEDGER.verdicts()
                 if v["kind"] == "rss-limit"])
        memstats.sample_once()
        assert len([v for v in memstats.LEDGER.verdicts()
                    if v["kind"] == "rss-limit"]) == n
        # and a SAMPLE-LESS sweep (the watchdog path) must not clear
        # the episode either — a sustained over-limit RSS would then
        # re-dump forensics on every sampler tick
        memstats.check_verdicts()
        memstats.sample_once()
        assert len([v for v in memstats.LEDGER.verdicts()
                    if v["kind"] == "rss-limit"]) == n

    def test_postmortem_memory_timeline(self, tmp_path):
        from tools import postmortem
        sh = RowShard(0, 64, 8, np.float32, get_updater("sgd"), "pmort")
        assert sh is not None   # keep the weakref'd component alive
        for _ in range(3):
            memstats.sample_once()
            time.sleep(0.01)
        p = flightrec.RECORDER.dump("test fault", str(tmp_path),
                                    stacks=True)
        d = postmortem.load_dump(p)
        assert d["memory"] and len(d["memsamples"]) == 3
        rep = postmortem.memory_report([d])
        assert "0" in rep["ranks"]
        comp = rep["ranks"]["0"]["components"]
        assert any(k.startswith("shard[pmort:") for k in comp)
        assert len(rep["timeline"]) == 3
        assert rep["timeline"] == sorted(rep["timeline"],
                                         key=lambda s: s["ts"])
        txt = postmortem.render_report([d])
        assert "memory at dump time" in txt
        assert "memory timeline" in txt
        json.dumps(rep)   # --json key shape

    def test_rss_creep_verdict(self):
        config.set_flag("memstats_rss_slope_mb_s", 1.0)
        base = time.time()
        with memstats.LEDGER._lock:
            memstats.LEDGER._history.clear()
            for i in range(3):
                memstats.LEDGER._history.append(
                    {"ts": base + i, "rss_mb": 100.0 + 50.0 * i,
                     "totals": {}, "retained": {}})
        memstats.LEDGER.check_verdicts()
        creeps = [v for v in memstats.LEDGER.verdicts()
                  if v["kind"] == "rss-creep"]
        assert creeps and creeps[-1]["slope_mb_s"] > 1.0
        assert flightrec.EV_MEM_RSS in _ring_kinds()


# ---------------------------------------------------------------------- #
# stats-surface lint + run_bench memory flags + bench extra
# ---------------------------------------------------------------------- #
class TestObsSurfaceStatsRule:
    def test_full_tree_clean(self):
        from tools import check_obs_surface
        assert check_obs_surface.stats_surface_findings() == []

    def test_catches_a_dark_key(self):
        from tools import check_obs_surface
        findings = check_obs_surface.stats_surface_findings(
            keys_by_src={"fake.py:stats()": ["shiny_new_block"]},
            renderer_text='print(rec.get("memory"))')
        assert findings and "shiny_new_block" in findings[0]
        # a rendered key passes either quote style
        assert check_obs_surface.stats_surface_findings(
            keys_by_src={"fake.py:stats()": ["memory"]},
            renderer_text="rec.get('memory')") == []

    def test_key_extraction_sees_all_emission_shapes(self):
        from tools import check_obs_surface
        keys = check_obs_surface.stats_keys(
            "multiverso_tpu/ps/service.py", "stats_payload")
        # update() kwargs, subscript assigns, and the memory block
        for k in ("rank", "world", "shards", "serving", "profile",
                  "memory"):
            assert k in keys, keys
        shard_keys = check_obs_surface.stats_keys(
            "multiverso_tpu/ps/shard.py", "stats")
        for k in ("adds", "gets", "hotkeys", "dirty_rows", "keys"):
            assert k in shard_keys

    def test_check_runs_clean_on_tree(self):
        from tools import check_obs_surface
        assert check_obs_surface.check() == []


class TestRunBenchMemoryFlags:
    def _headline(self, rss, retained):
        return {"extra": {"memory": {"peak_rss_mb": rss,
                                     "peak_retained_bytes": retained}}}

    def test_peak_rss_growth_flagged(self):
        from tools.run_bench import flag_regressions
        out = flag_regressions(self._headline(400.0, 0),
                               self._headline(1000.0, 0))
        assert any("peak RSS" in f for f in out)
        assert not flag_regressions(self._headline(400.0, 0),
                                    self._headline(500.0, 0))

    def test_retained_bytes_floored_baseline(self):
        from tools.run_bench import (_RETAINED_BASELINE_FLOOR_BYTES,
                                     flag_regressions)
        # healthy 0 prior must NOT suppress a real retention spike
        out = flag_regressions(
            self._headline(400.0, 0),
            self._headline(400.0, 4 * _RETAINED_BASELINE_FLOOR_BYTES))
        assert any("retained-frame bytes" in f for f in out)
        # under 2x the floor: no flag
        assert not flag_regressions(
            self._headline(400.0, 0),
            self._headline(400.0, _RETAINED_BASELINE_FLOOR_BYTES))

    def test_missing_memory_keys_skipped(self):
        from tools.run_bench import flag_regressions
        assert flag_regressions({"extra": {}}, {"extra": {}}) == []


class TestBenchExtra:
    def test_peaks_shape_and_json(self):
        sh = RowShard(0, 64, 8, np.float32, get_updater("sgd"), "bx")
        pin = sh._pin_data()
        memstats.sample_once()
        sh._release_data(pin)
        rec = memstats.bench_extra()
        json.dumps(rec)
        assert rec["peak_pinned_epochs"] >= 1
        assert rec["samples"] >= 2
        if memstats.read_rss()[0] is not None:
            assert rec["peak_rss_mb"] >= rec["rss_mb"]
