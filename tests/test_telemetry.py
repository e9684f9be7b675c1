"""Telemetry plane (PR 3): histogram math vs a numpy reference, the
Monitor upgrade (percentiles, thread-safe begin/end, immutable
snapshots, functools.wraps), trace-ID round-trips through the wire
(including MSG_BATCH inner frames), the MSG_STATS remote-dashboard RPC
against a live 2-rank PS, and the exporter file formats. All tier-1
(CPU, seconds)."""

import json
import os
import threading
import time

import numpy as np
import pytest

from multiverso_tpu.ps import service as svc
from multiverso_tpu.ps import wire
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.telemetry.exporter import (MetricsExporter,
                                               prometheus_text)
from multiverso_tpu.telemetry.histogram import (BOUNDS, NBUCKETS,
                                                Histogram, bucket_index)
from multiverso_tpu.utils import config
from multiverso_tpu.utils.dashboard import (Dashboard, Monitor,
                                            MonitorSnapshot, monitor,
                                            monitored)


# ---------------------------------------------------------------------- #
# histogram math
# ---------------------------------------------------------------------- #
class TestHistogram:
    def test_bucket_index_monotone_and_bounded(self):
        idxs = [bucket_index(ms) for ms in
                (0.0, 1e-9, 1e-5, 0.001, 0.1, 1.0, 42.0, 1e4, 1e9)]
        assert idxs == sorted(idxs)
        assert all(0 <= i < NBUCKETS for i in idxs)
        # every bound maps inside its own bucket's range
        for i in (0, 7, NBUCKETS // 2, NBUCKETS - 1):
            assert bucket_index(BOUNDS[i] * 0.999) == i

    @pytest.mark.parametrize("sigma", [0.5, 1.5])
    def test_percentiles_vs_numpy(self, sigma):
        """Bucket-interpolated quantiles vs np.percentile on the raw
        samples: within one bucket width (~19% relative) everywhere, and
        min/max exact."""
        rng = np.random.default_rng(7)
        samples = rng.lognormal(mean=0.0, sigma=sigma, size=20_000)
        h = Histogram()
        for s in samples:
            h.observe(float(s))
        assert h.count == samples.size
        assert h.max == samples.max() and h.min == samples.min()
        np.testing.assert_allclose(h.sum, samples.sum(), rtol=1e-9)
        for q in (1, 25, 50, 90, 99, 99.9):
            ref = float(np.percentile(samples, q))
            assert abs(h.percentile(q) - ref) / ref < 0.19, q

    def test_merge_equals_union(self):
        rng = np.random.default_rng(8)
        a, b = Histogram(), Histogram()
        sa = rng.exponential(2.0, 500)
        sb = rng.exponential(0.1, 700)
        for s in sa:
            a.observe(float(s))
        for s in sb:
            b.observe(float(s))
        a.merge(b)
        u = Histogram()
        for s in np.concatenate([sa, sb]):
            u.observe(float(s))
        assert a.counts == u.counts
        assert a.count == u.count and a.max == u.max and a.min == u.min

    def test_sparse_round_trip(self):
        h = Histogram()
        for s in (0.01, 0.02, 5.0, 5.1, 900.0):
            h.observe(s)
        d = h.as_dict()
        back = Histogram.from_nonzero(d["buckets"], count=d["count"],
                                      total=d["sum_ms"],
                                      min_ms=d["min_ms"],
                                      max_ms=d["max_ms"])
        assert back.counts == h.counts
        assert back.count == h.count and back.max == h.max

    def test_out_of_range_clamps(self):
        h = Histogram()
        h.observe(0.0)       # below range -> bucket 0, still counted
        h.observe(1e12)      # above range -> last bucket
        assert h.count == 2
        assert h.counts[0] == 1 and h.counts[-1] == 1


# ---------------------------------------------------------------------- #
# Monitor upgrade
# ---------------------------------------------------------------------- #
class TestMonitor:
    def test_percentiles_in_info_string(self):
        m = Monitor("t")
        for ms in (1.0, 2.0, 100.0):
            m.observe_ms(ms)
        s = m.info_string()
        assert "p50 =" in s and "p99 =" in s and "max =" in s
        assert m.p99_ms >= m.p50_ms > 0
        assert m.max_ms == 100.0

    def test_incr_does_not_pollute_histogram(self):
        """Counter-style monitors (window flushes etc.) bump count only;
        the percentile line must not appear for pure counters."""
        m = Monitor("c")
        m.incr(5)
        assert m.count == 5
        assert m.snapshot().timed == 0
        assert "p50" not in m.info_string()

    def test_begin_end_thread_safe(self):
        """Regression (satellite): the paired begin/end API used one
        shared slot — two threads interleaving begin/end dropped or
        corrupted samples. Per-thread stamps must give exactly one
        sample per begin/end pair, each with ITS thread's duration."""
        m = Monitor("r")
        n_per = 200
        barrier = threading.Barrier(2)

        def worker(sleep_s):
            barrier.wait()
            for _ in range(n_per):
                m.begin()
                if sleep_s:
                    time.sleep(sleep_s)
                m.end()

        t1 = threading.Thread(target=worker, args=(0.0,))
        t2 = threading.Thread(target=worker, args=(0.001,))
        t1.start(); t2.start(); t1.join(); t2.join()
        assert m.count == 2 * n_per
        # the slow thread's ~1ms samples survive interleaving: the p90
        # of the pooled distribution must see them (the old shared slot
        # lost/mixed them)
        assert m.percentile(90) >= 0.5

    def test_end_without_begin_is_noop(self):
        m = Monitor("x")
        m.end()
        assert m.count == 0

    def test_monitored_preserves_metadata(self):
        @monitored("api.fn")
        def fn(a, b=2):
            """the docstring"""
            return a + b

        assert fn.__name__ == "fn"
        assert fn.__doc__ == "the docstring"
        assert fn.__wrapped__ is not None
        assert fn(1) == 3
        assert Dashboard.get("api.fn").count == 1

    def test_snapshot_is_immutable_and_detached(self):
        with monitor("s"):
            pass
        snap = Dashboard.snapshot()["s"]
        assert isinstance(snap, MonitorSnapshot)
        with pytest.raises(Exception):   # frozen dataclass
            snap.count = 99
        before = snap.count
        with monitor("s"):
            pass
        assert snap.count == before          # detached from the live mon
        assert Dashboard.get("s").count == before + 1
        d = snap.hist_dict()
        json.dumps(d)                        # JSON-safe
        assert d["count"] == before


# ---------------------------------------------------------------------- #
# trace IDs: wire round-trip
# ---------------------------------------------------------------------- #
class TestTraceWire:
    def test_meta_round_trip(self):
        tid = 0x1234_5678_9ABC
        meta = wire.with_trace({"table": "t"}, tid)
        frame = wire.encode(svc.MSG_ADD_ROWS, 7, meta,
                            [np.arange(3, dtype=np.int64)])
        mt, mid, m, arrs = wire.parse_frame(frame)
        assert m[wire.TRACE_META_KEY] == tid
        assert mt == svc.MSG_ADD_ROWS and mid == 7

    def test_with_trace_none_is_passthrough(self):
        meta = {"table": "t"}
        assert wire.with_trace(meta, None) is meta

    def test_batch_inner_frames_keep_per_op_trace(self):
        """Every MSG_BATCH sub-op carries its OWN trace ID through
        pack/unpack — per-logical-op correlation survives windowing."""
        tids = [ttrace.TRACER.new_id() for _ in range(4)]
        blobs = [wire.encode(svc.MSG_ADD_ROWS, i,
                             wire.with_trace({"table": "t"}, tid),
                             [np.array([i], np.int64),
                              np.ones((1, 2), np.float32)])
                 for i, tid in enumerate(tids)]
        subs = wire.unpack_batch(wire.pack_batch(blobs))
        assert [m[wire.TRACE_META_KEY] for _, m, _ in subs] == tids
        assert len(set(tids)) == 4   # IDs are distinct

    def test_new_id_embeds_rank(self):
        tr = ttrace.Tracer()
        tr.rank = 5
        a, b = tr.new_id(), tr.new_id()
        assert a != b
        assert (a >> 32) & 0xFFFF == 5


class TestTracer:
    def test_disabled_records_no_fine_span(self):
        # the two classes: add_span (per PS request) is behind trace_ids,
        # span() (per call/block/build) is always recorded
        tr = ttrace.Tracer()
        tr.add_span("x", 0.0, 1.0, trace=1)
        assert tr.events() == []
        with tr.span("y"):
            pass
        assert [e["name"] for e in tr.events()] == ["y"]

    def test_span_shape_and_dump(self, tmp_path):
        tr = ttrace.Tracer()
        tr.enabled = True
        tr.rank = 3
        t0 = time.time()
        tr.add_span("op", t0, t0 + 0.001, trace=42, args={"k": "v"})
        [e] = tr.events()
        assert e["ph"] == "X" and e["pid"] == 3
        assert e["args"]["trace"] == 42 and e["args"]["k"] == "v"
        assert e["dur"] >= 900   # us
        path = str(tmp_path / "t.jsonl")
        assert tr.dump(path) == 1
        assert tr.dump(path) == 0      # buffer drained
        with open(path) as f:
            lines = [json.loads(x) for x in f if x.strip()]
        assert lines == [e]


# ---------------------------------------------------------------------- #
# MSG_STATS against a live 2-rank PS (in-process, real sockets)
# ---------------------------------------------------------------------- #
class TestMsgStats:
    def test_remote_dashboard_pull(self, two_ranks):
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        t0 = AsyncMatrixTable(16, 4, name="st", ctx=two_ranks[0])
        AsyncMatrixTable(16, 4, name="st", ctx=two_ranks[1])
        t0.add_rows([9], np.ones((1, 4), np.float32))   # remote-owned
        st = t0.server_stats(1)
        assert st["rank"] == 1 and st["world"] == 2
        sh = st["shards"]["st"]
        assert sh["kind"] == "row" and sh["rows"] == 8 and sh["lo"] == 8
        assert sh["adds"] >= 1 and sh["applies"] >= 1
        assert sh["version"] >= 1
        assert sh["queue_depth"] == 0 and sh["pending_bytes"] == 0
        json.dumps(st)   # whole payload is wire/JSON-safe
        # local short-circuit returns this rank's own registry
        local = t0.server_stats()
        assert local["rank"] == 0 and "st" in local["shards"]

    def test_windowed_adds_tick_wave_stats(self, two_ranks):
        """MSG_BATCH frames apply as python-side waves (the native
        server punts them), so the wave-size distribution and apply
        histogram must tick — the server-side view of the send window's
        realized batching."""
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        t0 = AsyncMatrixTable(16, 4, name="wv", send_window_ms=30_000.0,
                              ctx=two_ranks[0])
        AsyncMatrixTable(16, 4, name="wv", ctx=two_ranks[1])
        for _ in range(3):   # same row: conflicting ops -> 3 sub-ops
            t0.add_rows_async([9], np.ones((1, 4), np.float32))
        t0.flush()
        sh = t0.server_stats(1)["shards"]["wv"]
        assert sh["adds"] >= 3
        assert sh["wave_max_ops"] >= 1
        assert sum(sh["wave_ops"].values()) >= 3
        assert sh["apply"]["count"] >= 3
        assert sh["apply"]["p50_ms"] > 0

    def test_stats_of_dead_rank_raises_typed(self, two_ranks):
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        t0 = AsyncMatrixTable(16, 4, name="sd", ctx=two_ranks[0])
        AsyncMatrixTable(16, 4, name="sd", ctx=two_ranks[1])
        config.set_flag("ps_timeout", 4.0)
        config.set_flag("ps_connect_timeout", 2.0)
        two_ranks[1].service.close()
        with pytest.raises(svc.PSPeerError):
            t0.server_stats(1)

    def test_hash_and_kv_shards_report(self, two_ranks):
        from multiverso_tpu.ps.tables import (AsyncKVTable,
                                              AsyncSparseKVTable)
        t = AsyncSparseKVTable(4, name="hk", ctx=two_ranks[0])
        AsyncSparseKVTable(4, name="hk", ctx=two_ranks[1])
        kv = AsyncKVTable(name="kvt", ctx=two_ranks[0])
        AsyncKVTable(name="kvt", ctx=two_ranks[1])
        t.add_rows([3], np.ones((1, 4), np.float32))   # key 3 -> rank 1
        kv.add([0, 1], [1.0, 2.0])
        st = t.server_stats(1)
        assert st["shards"]["hk"]["kind"] == "hash"
        assert st["shards"]["hk"]["keys"] >= 1
        assert st["shards"]["kvt"]["kind"] == "kv"
        assert st["shards"]["kvt"]["keys"] >= 1


# ---------------------------------------------------------------------- #
# exporter file formats
# ---------------------------------------------------------------------- #
class TestExporter:
    def _payload(self):
        with monitor("e.op"):
            time.sleep(0.001)
        return {
            "rank": 0,
            "monitors": {n: s.hist_dict()
                         for n, s in Dashboard.snapshot().items()},
            "notes": Dashboard.notes(),
            "shards": {"t": {"kind": "row", "adds": 3, "queue_depth": 0}},
        }

    def test_jsonl_and_prom_files(self, tmp_path):
        exp = MetricsExporter(0, str(tmp_path), 0.0, self._payload)
        rec = exp.export_once()
        assert rec["monitors"]["e.op"]["count"] == 1
        jpath = tmp_path / "metrics-rank0.jsonl"
        ppath = tmp_path / "metrics-rank0.prom"
        assert jpath.exists() and ppath.exists()
        exp.export_once()   # JSONL appends; prom replaces
        with open(jpath) as f:
            recs = [json.loads(x) for x in f if x.strip()]
        assert len(recs) == 2
        assert recs[0]["ts"] <= recs[1]["ts"]
        assert recs[1]["monitors"]["e.op"]["p50_ms"] > 0
        prom = ppath.read_text()
        assert 'mv_monitor_count{name="e.op",rank="0"} ' in prom
        assert "mv_monitor_p50_ms" in prom
        assert 'mv_shard_adds{table="t",rank="0"} 3' in prom

    def test_stop_writes_final_snapshot(self, tmp_path):
        exp = MetricsExporter(1, str(tmp_path), 0.0, self._payload)
        exp.start()       # interval 0: no thread
        assert exp._thread is None
        exp.stop()
        assert (tmp_path / "metrics-rank1.jsonl").exists()

    def test_interval_thread_exports(self, tmp_path):
        exp = MetricsExporter(2, str(tmp_path), 0.05, self._payload)
        exp.start()
        deadline = time.monotonic() + 5.0
        jpath = tmp_path / "metrics-rank2.jsonl"
        while time.monotonic() < deadline and not jpath.exists():
            time.sleep(0.02)
        exp.stop()
        assert jpath.exists()

    def test_prometheus_text_escapes_quotes(self):
        txt = prometheus_text({"rank": 0, "monitors": {
            'bad"name': {"count": 1, "sum_ms": 1.0}}, "shards": {}})
        assert '"bad\'name"' in txt


# ---------------------------------------------------------------------- #
# exporter wiring: the service starts it from flags
# ---------------------------------------------------------------------- #
def test_service_flag_gated_exporter(tmp_path):
    from multiverso_tpu.ps.service import PSContext, PSService
    from multiverso_tpu.ps.tables import AsyncMatrixTable
    mdir = str(tmp_path / "m")
    config.set_flag("metrics_dir", mdir)
    config.set_flag("metrics_interval_s", 0.0)   # final snapshot only
    ctx = PSContext(0, 1, PSService(0, 1))
    t = AsyncMatrixTable(8, 2, name="exp", ctx=ctx)
    t.add_rows([1], np.ones((1, 2), np.float32))
    ctx.close()
    path = os.path.join(mdir, "metrics-rank0.jsonl")
    assert os.path.exists(path)
    with open(path) as f:
        rec = json.loads(f.readlines()[-1])
    assert "exp" in rec["shards"]
    assert rec["shards"]["exp"]["adds"] >= 1
    assert any(n.startswith("table[exp]") for n in rec["monitors"])
