"""Device-plane observability (ISSUE 12, telemetry/devstats.py): the
transfer chokepoint, collective spans, mesh-keyed compile attribution,
the per-device live-arrays rollup, the SPMD compile-hygiene capture,
the MSG_STATS "devices" block on both wire planes, every renderer's
mixed-version (block-absent) path, the scale harness's E_n oracle, and
the new check_obs_surface coverage rules."""

import json
import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from multiverso_tpu.telemetry import devstats  # noqa: E402
from multiverso_tpu.telemetry import flightrec  # noqa: E402


# ---------------------------------------------------------------------- #
# E_n oracle (tools/bench_scale.efficiency_curve is pure)
# ---------------------------------------------------------------------- #
class TestEfficiencyOracle:
    def test_perfect_linear_scaling_is_all_ones(self):
        from tools.bench_scale import efficiency_curve
        out = efficiency_curve({1: 100.0, 2: 200.0, 4: 400.0, 8: 800.0})
        assert out["efficiency"] == {1: 1.0, 2: 1.0, 4: 1.0, 8: 1.0}
        assert out["efficiency_min"] == 1.0

    def test_hand_computed_curve(self):
        from tools.bench_scale import efficiency_curve
        # E_n = T_n / (n * T_1): 150/(2*100)=0.75, 240/(4*100)=0.6
        out = efficiency_curve({1: 100.0, 2: 150.0, 4: 240.0})
        assert out["efficiency"][2] == pytest.approx(0.75)
        assert out["efficiency"][4] == pytest.approx(0.6)
        assert out["efficiency_min"] == pytest.approx(0.6)

    def test_string_keys_accepted(self):
        # JSON round-trips turn int keys into strings; the oracle must
        # not care which spelling it gets
        from tools.bench_scale import efficiency_curve
        out = efficiency_curve({"1": 100.0, "2": 100.0})
        assert out["efficiency"][2] == pytest.approx(0.5)

    def test_missing_or_zero_baseline_yields_none(self):
        from tools.bench_scale import efficiency_curve
        assert efficiency_curve({2: 100.0})["efficiency_min"] is None
        assert efficiency_curve({1: 0.0, 2: 1.0})["efficiency_min"] is None
        assert efficiency_curve({})["efficiency_min"] is None

    def test_superlinear_points_allowed(self):
        # cache effects can push E_n above 1; the oracle records, the
        # regression flag (higher-is-better) only cares about drops
        from tools.bench_scale import efficiency_curve
        out = efficiency_curve({1: 100.0, 2: 250.0})
        assert out["efficiency"][2] == pytest.approx(1.25)


# ---------------------------------------------------------------------- #
# mesh labels + hygiene classification (pure)
# ---------------------------------------------------------------------- #
class TestMeshLabelAndClassify:
    def test_label_spellings(self):
        assert devstats.mesh_label(None) == "unmeshed"
        assert devstats.mesh_label("{'mv': 4}") == "{'mv': 4}"
        assert devstats.mesh_label({"mv": 4}) == "{'mv': 4}"

    def test_label_of_real_mesh(self):
        import jax
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("mv",))
        assert devstats.mesh_label(mesh) == "{'mv': 2}"

    def test_classification_vocabulary(self):
        cl = devstats.classify_compile_warning
        assert cl("SPMD rematerialization triggered") == "remat"
        assert cl("could not infer sharding for op") == "sharding-fallback"
        assert cl("Falling back to REPLICATED sharding") \
            == "sharding-fallback"
        assert cl("Some donated buffers were not usable") == "donation"
        assert cl("SPMD pipelining note from xla") == "spmd"
        # noise is NOT a finding
        assert cl("DeprecationWarning: jax.tree_map is deprecated") is None
        assert cl("") is None


class TestHygieneCapture:
    def test_synthetic_spmd_warning_becomes_report_entry(self):
        import warnings
        with devstats.capture_hygiene("fn_a", mesh={"mv": 4}) as scope:
            warnings.warn("sharding propagation could not infer "
                          "sharding; falling back to replicated")
        assert len(scope.entries) == 1
        rep = devstats.hygiene_report()
        assert rep["clean"] is False
        (e,) = rep["findings"]
        assert e["fn"] == "fn_a" and e["mesh"] == "{'mv': 4}"
        assert e["category"] == "sharding-fallback"
        assert rep["checked"][0]["captured"] == 1

    def test_clean_compile_yields_empty_report(self):
        import jax
        import jax.numpy as jnp
        with devstats.capture_hygiene("fn_clean", mesh={"mv": 1}):
            jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()
        rep = devstats.hygiene_report()
        assert rep["clean"] is True and rep["findings"] == []
        assert rep["checked"][0]["fn"] == "fn_clean"

    def test_jax_logger_messages_are_captured_too(self):
        import logging
        with devstats.capture_hygiene("fn_log", mesh={"mv": 2}):
            logging.getLogger("jax").warning(
                "spmd partition fell back somewhere")
        rep = devstats.hygiene_report()
        assert rep["clean"] is False
        assert rep["findings"][0]["category"] == "sharding-fallback" \
            or rep["findings"][0]["category"] == "spmd"

    def test_noise_does_not_dirty_the_report(self):
        import warnings
        with devstats.capture_hygiene("fn_noise", mesh={"mv": 2}):
            warnings.warn("user warning about nothing in particular")
        rep = devstats.hygiene_report()
        assert rep["clean"] is True
        assert rep["checked"][0]["captured"] == 1
        assert rep["checked"][0]["findings"] == 0

    def test_dump_hygiene_writes_json(self, tmp_path):
        import warnings
        with devstats.capture_hygiene("fn_d", mesh={"mv": 8}):
            warnings.warn("rematerialization inserted")
        path = devstats.dump_hygiene(str(tmp_path), rank=3)
        assert os.path.basename(path) == "compile-hygiene-rank3.json"
        with open(path) as f:
            rep = json.load(f)
        assert rep["rank"] == 3 and rep["clean"] is False


# ---------------------------------------------------------------------- #
# per-device census rollup (fixture-injected; no live backend needed)
# ---------------------------------------------------------------------- #
class _FakeShard:
    def __init__(self, device, nbytes):
        self.device = device
        self.data = type("D", (), {"nbytes": nbytes})()


class _FakeSharded:
    def __init__(self, shards):
        self.addressable_shards = shards


class _FakeSingle:
    def __init__(self, device, nbytes):
        self.addressable_shards = None
        self.nbytes = nbytes
        self._device = device

    def devices(self):
        return {self._device}


class TestDeviceRollup:
    def test_hand_built_fixture_grouping(self):
        arrays = [
            _FakeSharded([_FakeShard("cpu:0", 100),
                          _FakeShard("cpu:1", 300)]),
            _FakeSingle("cpu:0", 50),
            _FakeSharded([_FakeShard("cpu:1", 7)]),
        ]
        per = devstats.device_rollup(arrays)
        assert per == {"cpu:0": {"bytes": 150, "arrays": 2},
                       "cpu:1": {"bytes": 307, "arrays": 2}}

    def test_broken_entry_skipped_not_fatal(self):
        class Broken:
            @property
            def addressable_shards(self):
                raise RuntimeError("donated mid-walk")

        per = devstats.device_rollup([Broken(),
                                      _FakeSingle("cpu:0", 9)])
        assert per == {"cpu:0": {"bytes": 9, "arrays": 1}}

    def test_live_backend_rollup_charges_devices(self):
        import jax
        import jax.numpy as jnp
        a = jnp.ones((128, 8), jnp.float32) + 1  # keep a live result
        per = devstats.device_rollup()
        assert per, "live rollup found no arrays"
        total = sum(g["bytes"] for g in per.values())
        assert total >= a.nbytes


# ---------------------------------------------------------------------- #
# transfer chokepoint + collective spans
# ---------------------------------------------------------------------- #
class TestTransfersAndSpans:
    def test_per_direction_counters(self):
        devstats.note_transfer(100, "h2d")
        devstats.note_transfer(50, "h2d")
        devstats.note_transfer(7, "d2h")
        snap = devstats.stats_snapshot()
        assert snap["transfers"]["h2d"] == {"ops": 2, "bytes": 150}
        assert snap["transfers"]["d2h"] == {"ops": 1, "bytes": 7}

    def test_unknown_direction_raises(self):
        with pytest.raises(ValueError):
            devstats.note_transfer(1, "sideways")

    def test_span_leaves_one_record_in_the_ring(self):
        # what a step's report counts beside it (trace.step_report)
        from multiverso_tpu.telemetry import trace
        with trace.span("t.step", step=1):
            with devstats.collective_span("test_op", 2048, mesh={"mv": 2}):
                pass
        [rec] = [e for e in trace.events() if e["name"] == "coll.test_op"]
        assert rec["args"] == {"nbytes": 2048} and rec["cat"] == "prog"
        [step] = trace.step_report(trace.events())
        assert step["phases"]["coll.test_op"]["count"] == 1

    def test_span_lands_dashboard_flightrec_and_tally(self):
        from multiverso_tpu.utils.dashboard import Dashboard
        with devstats.collective_span("test_op", 2048, mesh={"mv": 2}):
            pass
        snap = devstats.stats_snapshot()
        assert snap["collectives"]["test_op"]["calls"] == 1
        assert snap["collectives"]["test_op"]["bytes"] == 2048
        assert Dashboard.get("coll[test_op].calls").count == 1
        assert Dashboard.get("coll[test_op].bytes").count == 2048
        # ring slots are (seq, mono, kind, peer, msg_type, msg_id,
        # nbytes, note)
        evs = [r for r in flightrec.RECORDER.snapshot()
               if r[2] in (flightrec.EV_COLL_BEGIN,
                           flightrec.EV_COLL_END)]
        assert len(evs) == 2
        assert all(r[7] == "coll.test_op" for r in evs)
        assert all(r[6] == 2048 for r in evs)

    def test_flag_off_is_null_context_and_dark_counters(self):
        from multiverso_tpu.utils import config
        config.set_flag("devstats", False)
        devstats.configure()
        try:
            assert not devstats.enabled()
            ctx = devstats.collective_span("off_op", 1)
            assert ctx is devstats._NULL
            with ctx:
                pass
            devstats.note_transfer(5, "d2h")   # counters stay dark
            snap = devstats.stats_snapshot()
            assert snap is None
        finally:
            config.set_flag("devstats", True)
            devstats.configure()

    def test_snapshot_none_when_nothing_happened(self):
        # fresh state, no transfers/collectives/compiles: the block is
        # OMITTED from payloads, not emitted empty (device_rollup may
        # still see live arrays from neighbors — tolerate that shape)
        snap = devstats.stats_snapshot()
        if snap is not None:
            assert set(snap) >= {"per_device"} or snap.get("per_device")


# ---------------------------------------------------------------------- #
# collectives integration: spans + the mapped-callable cache
# ---------------------------------------------------------------------- #
class TestCollectivesRecord:
    def test_all_ops_record_spans_and_results_hold(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from multiverso_tpu.parallel import collectives as C
        n = 2
        mesh = Mesh(np.asarray(jax.devices()[:n]), ("mv",))
        x = jnp.arange(n * 4, dtype=jnp.float32)
        out = np.asarray(C.all_reduce(x, mesh=mesh))
        np.testing.assert_allclose(out, np.arange(8.).reshape(2, 4)
                                    .sum(axis=0))
        np.testing.assert_allclose(np.asarray(C.all_gather(x, mesh=mesh)),
                                    np.arange(8.))
        np.testing.assert_allclose(
            np.asarray(C.reduce_scatter(x, mesh=mesh)), np.arange(8.))
        np.testing.assert_allclose(
            np.asarray(C.broadcast(x, root=1, mesh=mesh)),
            np.arange(8.)[4:])
        snap = devstats.stats_snapshot()
        for op in ("all_reduce", "all_gather", "reduce_scatter",
                   "broadcast"):
            assert snap["collectives"][op]["calls"] == 1, op
            assert snap["collectives"][op]["bytes"] == x.nbytes

    def test_mapped_cache_stops_percall_recompiles(self):
        # the bug devstats caught: rebuilding the shard_map closure per
        # call recompiled EVERY collective call. With the cache, calls
        # 2..k add zero compiles for an unchanged (op, mesh, shape).
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from multiverso_tpu.parallel import collectives as C
        devstats.configure(0)   # install the mesh-keyed listener
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("mv",))
        x = jnp.ones(64, jnp.float32)
        C.all_reduce(x, mesh=mesh).block_until_ready()   # compile once

        def compiles():
            snap = devstats.stats_snapshot() or {}
            return sum(c.get("compiles", 0) for c in
                       (snap.get("compiles_by_mesh") or {}).values())

        before = compiles()
        for _ in range(3):
            C.all_reduce(x, mesh=mesh).block_until_ready()
        assert compiles() == before, \
            "steady-state collective calls recompiled"


# ---------------------------------------------------------------------- #
# MSG_STATS "devices" block: local + over-socket on both wire planes
# ---------------------------------------------------------------------- #
class TestStatsBlock:
    def test_local_payload_carries_block_after_activity(self, two_ranks):
        devstats.note_transfer(640, "h2d")
        payload = two_ranks[0].service.stats_payload()
        assert payload["devices"]["transfers"]["h2d"]["bytes"] == 640

    def test_over_socket_both_planes(self, two_ranks):
        # two_ranks is parametrized native/python — one test body
        # covers both wire planes. DevStats is process-global, so the
        # in-process peer reports the same block (the documented
        # collapse, deduped by (host, pid) in the cluster merge).
        with devstats.collective_span("sock_op", 96, mesh={"mv": 2}):
            pass
        st = two_ranks[0].service.stats_oneshot(1)
        assert st["devices"]["collectives"]["sock_op"]["bytes"] == 96

    def test_mvtop_live_world_shows_device_panel(self, two_ranks,
                                                 tmp_path):
        # the ISSUE-12 acceptance shape: collectives visible in mvtop
        # from a LIVE world — real one-shot probe sockets, both wire
        # planes (two_ranks param), no fixture payloads
        from tools import mvtop
        devstats.note_transfer(2048, "h2d")
        with devstats.collective_span("live_op", 4096, mesh={"mv": 2}):
            pass
        addrs = mvtop.read_addrs(str(tmp_path / "rdv"))
        assert sorted(addrs) == [0, 1]
        rec = mvtop.poll(addrs, timeout=5.0)
        assert rec["devices"]["totals"]["coll_calls"] >= 1
        out = mvtop.render(rec)
        assert "devices:" in out and "live_op:1" in out
        # ...and in mv_dev_* Prometheus text from the same live payload
        from multiverso_tpu.telemetry.exporter import prometheus_text
        st = two_ranks[0].service.stats_oneshot(0)
        text = prometheus_text(st)
        assert 'mv_dev_collective_calls{op="live_op"' in text

    def test_absent_block_stays_absent(self, two_ranks):
        # a rank with devstats off emits NO devices key — the
        # mixed-version shape every consumer must render
        from multiverso_tpu.utils import config
        config.set_flag("devstats", False)
        devstats.configure()
        try:
            payload = two_ranks[0].service.stats_payload()
            assert "devices" not in payload
        finally:
            config.set_flag("devstats", True)
            devstats.configure()


# ---------------------------------------------------------------------- #
# cluster merge + renderers (incl. the mixed-version/absent paths)
# ---------------------------------------------------------------------- #
def _stats(rank, pid, devices=None):
    st = {"rank": rank, "addr": f"127.0.0.1:90{rank}", "pid": pid,
          "monitors": {}, "shards": {}}
    if devices is not None:
        st["devices"] = devices
    return st


_DEV_A = {
    "transfers": {"h2d": {"ops": 3, "bytes": 3000},
                  "d2h": {"ops": 1, "bytes": 100}},
    "collectives": {"all_reduce": {"calls": 4, "bytes": 4096,
                                   "ms": 12.5}},
    "compiles_by_mesh": {"{'mv': 2}": {"compiles": 2,
                                       "compile_s": 1.25}},
    "per_device": {"cpu:0": {"bytes": 512, "arrays": 2}},
}


class TestMergeAndRender:
    def test_merge_cluster_devices_ranks_and_totals(self):
        from multiverso_tpu.telemetry import aggregator
        health = {0: {"status": "ok"}, 1: {"status": "ok"}}
        stats = {0: _stats(0, pid=10, devices=_DEV_A),
                 1: _stats(1, pid=11, devices=_DEV_A)}
        rec = aggregator.merge_cluster(stats, health, world=2)
        assert set(rec["devices"]["ranks"]) == {"0", "1"}
        t = rec["devices"]["totals"]
        # two distinct processes: summed
        assert t["h2d_bytes"] == 6000 and t["d2h_bytes"] == 200
        assert t["coll_calls"] == 8 and t["coll_bytes"] == 8192
        assert t["compiles"] == 4 and t["device_bytes"] == 1024

    def test_merge_dedupes_same_process(self):
        from multiverso_tpu.telemetry import aggregator
        health = {0: {"status": "ok"}, 1: {"status": "ok"}}
        stats = {0: _stats(0, pid=10, devices=_DEV_A),
                 1: _stats(1, pid=10, devices=_DEV_A)}  # same pid
        rec = aggregator.merge_cluster(stats, health, world=2)
        t = rec["devices"]["totals"]
        assert t["h2d_bytes"] == 3000 and t["coll_calls"] == 4

    def test_merge_without_blocks_has_no_devices_key(self):
        from multiverso_tpu.telemetry import aggregator
        health = {0: {"status": "ok"}}
        rec = aggregator.merge_cluster({0: _stats(0, pid=10)}, health,
                                       world=1)
        assert "devices" not in rec

    def test_mvtop_renders_device_panel(self):
        from multiverso_tpu.telemetry import aggregator
        from tools import mvtop
        health = {0: {"status": "ok"}, 1: {"status": "ok"}}
        stats = {0: _stats(0, pid=10, devices=_DEV_A),
                 1: _stats(1, pid=11)}       # rank 1: NO block
        rec = aggregator.merge_cluster(stats, health, world=2)
        out = mvtop.render(rec)
        assert "devices:" in out and "all_reduce:4" in out
        assert "{'mv': 2}" in out

    def test_mvtop_renders_without_devices_block(self):
        # mixed-version cluster: NO rank carries the block — the
        # explicit no-KeyError-panels satellite
        from multiverso_tpu.telemetry import aggregator
        from tools import mvtop
        health = {0: {"status": "ok"}, 1: {"status": "ok"}}
        stats = {0: _stats(0, pid=10), 1: _stats(1, pid=11)}
        rec = aggregator.merge_cluster(stats, health, world=2)
        out = mvtop.render(rec)
        assert "devices:" not in out
        assert "rank" in out   # the health table still rendered

    def test_dump_metrics_renders_rank_and_cluster_devices(self):
        from multiverso_tpu.telemetry import aggregator
        from tools import dump_metrics
        rank_rec = dict(_stats(0, pid=10, devices=_DEV_A), ts=1.0)
        out = dump_metrics.format_record(rank_rec)
        assert "devices.transfers" in out and "all_reduce" in out
        health = {0: {"status": "ok"}}
        rec = aggregator.merge_cluster(
            {0: _stats(0, pid=10, devices=_DEV_A)}, health, world=1)
        out = dump_metrics.format_record(rec)
        assert "devices(cluster):" in out

    def test_dump_metrics_renders_without_devices(self):
        from tools import dump_metrics
        out = dump_metrics.format_record(dict(_stats(0, pid=10), ts=1.0))
        assert "devices" not in out
        from multiverso_tpu.telemetry import aggregator
        rec = aggregator.merge_cluster({0: _stats(0, pid=10)},
                                       {0: {"status": "ok"}}, world=1)
        assert "devices" not in dump_metrics.format_record(rec)

    def test_exporter_emits_mv_dev_gauges(self):
        from multiverso_tpu.telemetry.exporter import prometheus_text
        text = prometheus_text({"rank": 0, "monitors": {}, "shards": {},
                                "devices": _DEV_A})
        assert 'mv_dev_transfer_bytes{direction="h2d",rank="0"} 3000' \
            in text
        assert 'mv_dev_collective_calls{op="all_reduce",rank="0"} 4' \
            in text
        assert "mv_dev_compiles{mesh=\"{'mv': 2}\",rank=\"0\"} 2" in text
        assert 'mv_dev_live_bytes{device="cpu:0",rank="0"} 512' in text
        # absent block: no mv_dev_ series at all, no error
        text = prometheus_text({"rank": 0, "monitors": {}, "shards": {}})
        assert "mv_dev_" not in text

    def test_mvprof_hygiene_report_render(self, tmp_path):
        import warnings
        from tools import mvprof
        with devstats.capture_hygiene("fn_x", mesh={"mv": 4}):
            warnings.warn("remat triggered by spmd partitioner")
        devstats.dump_hygiene(str(tmp_path), rank=0)
        reports = mvprof.collect_hygiene([str(tmp_path)])
        assert len(reports) == 1 and reports[0]["clean"] is False
        out = mvprof.render_hygiene(reports)
        assert "FINDING [remat]" in out and "fn_x" in out
        # main() renders hygiene even with no step records
        assert mvprof.main([str(tmp_path)]) == 0


# ---------------------------------------------------------------------- #
# run_bench: efficiency regression flags + BENCH_HISTORY trajectory
# ---------------------------------------------------------------------- #
class TestRunBenchScale:
    def test_synthetic_efficiency_regression_flagged(self):
        from tools.run_bench import flag_regressions
        prev = {"extra": {"scale": {"efficiency_min": 0.8,
                                    "t1_rows_per_s": 4000}}}
        worse = {"extra": {"scale": {"efficiency_min": 0.3,
                                     "t1_rows_per_s": 3900}}}
        flags = flag_regressions(prev, worse)
        assert len(flags) == 1
        assert "mesh scaling efficiency" in flags[0]
        # a baseline drop flags on its own key
        t1_drop = {"extra": {"scale": {"efficiency_min": 0.78,
                                       "t1_rows_per_s": 1200}}}
        flags = flag_regressions(prev, t1_drop)
        assert len(flags) == 1
        assert "single-shard baseline" in flags[0]
        # same record: clean; missing scale block: skipped
        assert flag_regressions(prev, prev) == []
        assert flag_regressions({"extra": {}}, worse) == []

    def test_per_point_efficiency_regression_flagged(self):
        """ISSUE 15: E_2 / E_4 are tracked as their OWN keys — a drop
        at one point must flag even when the curve's min (a different
        point) holds."""
        from tools.run_bench import flag_regressions
        prev = {"extra": {"scale": {"efficiency_min": 0.1,
                                    "e2": 0.8, "e4": 0.4,
                                    "t1_rows_per_s": 4000}}}
        e2_drop = {"extra": {"scale": {"efficiency_min": 0.1,
                                       "e2": 0.3, "e4": 0.4,
                                       "t1_rows_per_s": 4000}}}
        flags = flag_regressions(prev, e2_drop)
        assert len(flags) == 1 and "E_2" in flags[0]
        e4_drop = {"extra": {"scale": {"efficiency_min": 0.1,
                                       "e2": 0.8, "e4": 0.15,
                                       "t1_rows_per_s": 4000}}}
        flags = flag_regressions(prev, e4_drop)
        assert len(flags) == 1 and "E_4" in flags[0]

    def test_history_entry_and_append(self, tmp_path):
        from tools.run_bench import append_history, history_entry
        rec = {"complete": True, "truncated": False,
               "regressions": ["x regressed"],
               "headline": {"value": 123.4, "unit": "w/s",
                            "vs_baseline": 1.01,
                            "extra": {"scale": {"efficiency_min": 0.7,
                                                "t1_rows_per_s": 100},
                                      "we": {"words_per_s": 5.0}}}}
        ent = history_entry(rec, "/x/BENCH_r07.json", ts=1000.0)
        assert ent["record"] == "BENCH_r07.json"
        assert ent["metrics"]["scale.efficiency_min"] == 0.7
        assert ent["metrics"]["scale.t1_rows_per_s"] == 100
        assert ent["metrics"]["we.words_per_s"] == 5.0
        assert ent["regressions"] == ["x regressed"]
        hist = tmp_path / "BENCH_HISTORY.jsonl"
        append_history(ent, str(hist))
        append_history(dict(ent, ts=2000.0), str(hist))
        lines = [json.loads(ln) for ln in
                 hist.read_text().splitlines()]
        assert len(lines) == 2 and lines[0]["ts"] == 1000.0

    def test_dump_metrics_history_render_and_diff(self, tmp_path):
        from tools import dump_metrics
        hist = tmp_path / "BENCH_HISTORY.jsonl"
        a = {"ts": 1.0, "record": "BENCH_r06.json", "complete": True,
             "truncated": False, "value": 100.0, "unit": "w/s",
             "vs_baseline": 1.0, "regressions": [],
             "metrics": {"scale.efficiency_min": 0.8}}
        b = dict(a, ts=2.0, record="BENCH_r07.json",
                 metrics={"scale.efficiency_min": 0.4},
                 regressions=["mesh scaling efficiency (min E_n): ..."])
        hist.write_text(json.dumps(a) + "\n" + json.dumps(b) + "\n")
        recs = dump_metrics.load_records(str(hist))
        assert all(dump_metrics.is_history_record(r) for r in recs)
        table = dump_metrics.format_history_records(recs)
        assert "BENCH_r06.json" in table and "BENCH_r07.json" in table
        assert "FLAG:" in table
        diff = dump_metrics.diff_history_records(recs[0], recs[1])
        assert "scale.efficiency_min" in diff
        assert "0.8" in diff and "0.4" in diff
        # a non-history record is NOT misdetected
        assert not dump_metrics.is_history_record(
            {"rank": 0, "monitors": {}})


# ---------------------------------------------------------------------- #
# check_obs_surface: the two new rules
# ---------------------------------------------------------------------- #
class TestObsSurfaceRules:
    def test_repo_collective_coverage_clean(self):
        from tools.check_obs_surface import collective_coverage_findings
        assert collective_coverage_findings() == []

    def test_dark_collective_op_caught(self):
        from tools.check_obs_surface import collective_coverage_findings
        dark = ("def new_collective(x, mesh=None):\n"
                "    return _shard_map(lambda v: v, mesh=mesh,\n"
                "                      in_specs=None, out_specs=None)(x)\n")
        finds = collective_coverage_findings(
            sources=(("multiverso_tpu/parallel/collectives.py", "all"),),
            source_text={"multiverso_tpu/parallel/collectives.py": dark})
        assert len(finds) == 1 and "new_collective" in finds[0]

    def test_host_helper_without_shard_map_is_exempt(self):
        from tools.check_obs_surface import collective_coverage_findings
        helper = "def shape_helper(x):\n    return x.shape\n"
        finds = collective_coverage_findings(
            sources=(("multiverso_tpu/parallel/ring.py", "shard_map"),),
            source_text={"multiverso_tpu/parallel/ring.py": helper})
        assert finds == []

    def test_repo_regression_keys_all_produced(self):
        from tools.check_obs_surface import (regression_key_findings,
                                             regression_paths)
        paths = regression_paths()
        # the tables parsed: the scale keys this PR added are present
        assert ("scale", "efficiency_min") in paths
        assert regression_key_findings() == []

    def test_disarmed_regression_key_caught(self):
        from tools.check_obs_surface import regression_key_findings
        finds = regression_key_findings(
            paths=[("scale", "renamed_away_key")],
            producer_text='extra["scale"] = {"efficiency_min": 1}')
        assert len(finds) == 1
        assert "renamed_away_key" in finds[0]
        # a produced path passes
        assert regression_key_findings(
            paths=[("scale", "efficiency_min")],
            producer_text='x = {"scale": {"efficiency_min": 1}}') == []


# ---------------------------------------------------------------------- #
# the scale harness itself: tier-1 smoke at 1->2 shards
# ---------------------------------------------------------------------- #
def test_bench_scale_smoke_two_points():
    """ISSUE 12 acceptance smoke: the harness records T_1/T_2 with E_2
    computed in-run, per-point skew/stall from the aggregator/profiler,
    quiesced collective cost, mesh-keyed compile attribution, and the
    SPMD hygiene gate asserted CLEAN — all through the real subprocess
    spawn path bench.py uses."""
    import bench
    r = bench.bench_scale_curve(seconds=0.8, shards="1,2")
    assert r["shards"] == [1, 2]
    c1, c2 = r["curve"]["1"], r["curve"]["2"]
    assert c1["rows_per_s"] > 0 and c2["rows_per_s"] > 0
    assert c1["skew"] == pytest.approx(1.0, abs=0.5)
    assert r["efficiency"]["1"] == 1.0
    assert 0 < r["efficiency"]["2"] == r["efficiency_min"]
    assert r["t1_rows_per_s"] == c1["rows_per_s"]
    # ISSUE 15: constant offered load at every point, the per-point
    # E_n scalars feeding run_bench, and the mesh-data-plane gates —
    # bit-parity vs the 1-shard oracle and zero steady recompiles —
    # asserted through the real subprocess path
    assert c1["workers"] == c2["workers"] == r["workers"]
    assert r["e2"] == r["efficiency"]["2"]
    assert r["fanout"] is True and r["spmd_stack"] is True
    assert r["parity_bit_for_bit"] is True
    assert r["steady_recompiles"] == 0
    # the stacked SPMD plane compiled under its own mesh label
    assert any(k.startswith("{'shards':")
               for k in r["compiles_by_mesh"])
    # the hygiene gate RAN and passed for both mesh shapes
    assert r["hygiene_clean"] is True and r["hygiene_checked"] >= 2
    # device-plane attribution came back mesh-keyed
    assert "{'mv': 2}" in r["compiles_by_mesh"]
    assert r["collectives"]["all_reduce"]["calls"] > 0
    assert c2["all_reduce_ms"] > 0
    # the h2d upload of the model delta crossed the chokepoint
    assert r["transfers"]["h2d"]["bytes"] > 0
