"""Cluster observability plane (PR 6): the Space-Saving heavy-hitter
sketch (recall/overestimate/memory properties), the stats aggregator's
exact cross-rank merge + skew + rates on a live 2-rank PS (both wire
planes — the native server punts MSG_STATS), the one-shot stats probe,
and the ``mvtop --once`` operator view. All tier-1 (CPU, seconds)."""

import json
import os
import sys
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from multiverso_tpu.telemetry import aggregator  # noqa: E402
from multiverso_tpu.telemetry import hotkeys  # noqa: E402
from multiverso_tpu.telemetry.histogram import Histogram  # noqa: E402
from multiverso_tpu.utils import config  # noqa: E402


# ---------------------------------------------------------------------- #
# Space-Saving sketch properties
# ---------------------------------------------------------------------- #
class TestSpaceSaving:
    def test_exact_below_capacity(self):
        sk = hotkeys.SpaceSaving(16)
        for k in [1, 1, 1, 2, 2, 7]:
            sk.offer(k)
        assert sk.items()[0] == (1, 3, 0)
        assert dict((k, c) for k, c, _ in sk.items()) == {1: 3, 2: 2, 7: 1}
        assert all(e == 0 for _, _, e in sk.items())
        assert sk.total == 6

    def test_zipf_topk_recall_and_bounded_memory(self):
        """ISSUE 6 acceptance: top-K recall >= 0.9 vs exact counts on a
        zipf stream, with memory bounded at capacity entries."""
        rng = np.random.default_rng(42)
        stream = rng.zipf(1.3, size=60_000)
        capacity, k = 256, 20
        sk = hotkeys.SpaceSaving(capacity)
        for v in stream.tolist():
            sk.offer(int(v))
        # bounded memory: exactly one dict entry + one heap entry per
        # tracked key, never more than capacity
        assert len(sk) <= capacity
        assert len(sk._heap) <= capacity
        keys, counts = np.unique(stream, return_counts=True)
        order = np.argsort(-counts, kind="stable")
        exact_top = set(int(keys[i]) for i in order[:k])
        sketch_top = set(key for key, _, _ in sk.top(k))
        recall = len(exact_top & sketch_top) / k
        assert recall >= 0.9, (recall, sorted(exact_top),
                               sorted(sketch_top))
        # Space-Saving guarantee: count - err <= true freq <= count
        true = {int(kk): int(c) for kk, c in zip(keys, counts)}
        for key, count, err in sk.items():
            assert count >= true.get(key, 0), (key, count)
            assert count - err <= true.get(key, 0), (key, count, err)

    def test_batch_observe_samples_big_batches(self):
        sk = hotkeys.SpaceSaving(8)
        big = np.arange(100_000, dtype=np.int64)
        t0 = time.perf_counter()
        sk.observe(big)
        assert time.perf_counter() - t0 < 0.5   # sampled, not 100k offers
        assert sk.observed == 100_000
        # sampled offers carry the STRIDE's weight: total stays on the
        # raw-traffic scale (within one stride of rounding)
        assert abs(sk.total - 100_000) <= hotkeys.BATCH_SAMPLE
        # offset turns shard-local ids into global ones
        sk2 = hotkeys.SpaceSaving(8)
        sk2.observe(np.array([0, 1, 0]), offset=100)
        assert sk2.items()[0][0] == 100

    def test_mixed_batch_sizes_rank_on_one_scale(self):
        """A key served through big sampled batches must rank against a
        key served through 1-row ops on the same count scale — inc=1
        sampling would undercount the batched key ~n/BATCH_SAMPLE x."""
        sk = hotkeys.SpaceSaving(8)
        sk.observe(np.full(50_000, 7, dtype=np.int64))   # sampled batch
        for _ in range(1000):                            # 1-row ops
            sk.offer(3)
        items = dict((k, c) for k, c, _ in sk.items())
        assert items[7] > items[3]                       # 50k >> 1k
        assert items[7] == pytest.approx(50_000, rel=0.02)

    def test_repeated_batches_rotate_sampling_phase(self):
        """A workload re-issuing the SAME big caller-ordered batch must
        not alias: an off-stride hot key is eventually sampled (fixed
        phase-0 striding would miss it forever)."""
        n = 4 * hotkeys.BATCH_SAMPLE          # stride 4
        batch = np.arange(n, dtype=np.int64)
        hot = 1                               # off phase-0 stride
        sk = hotkeys.SpaceSaving(4096)
        for _ in range(8):                    # phases cycle 1,2,3,0,...
            sk.observe(batch)
        items = dict((k, c) for k, c, _ in sk.items())
        assert hot in items, "off-stride key never sampled"
        # weighted back to the raw scale: ~2 of 8 batches sample index 1
        # at stride weight 4 -> ~8 == its true count across the repeats
        assert items[hot] == 8

    def test_merge_and_hit_rate_curve(self):
        a, b = hotkeys.SpaceSaving(8), hotkeys.SpaceSaving(8)
        for _ in range(30):
            a.offer(1)
        for _ in range(20):
            b.offer(2)
        b.offer(1)   # overlapping key: counts sum
        merged = hotkeys.merge_sketches([a.to_dict(), b.to_dict(), None])
        assert merged["items"][0] == [1, 31, 0]
        assert merged["items"][1] == [2, 20, 0]
        assert merged["total"] == 51
        curve = hotkeys.hit_rate_curve(merged)
        assert curve[0] == [1, round(31 / 51, 4)]
        assert curve[-1][1] == 1.0
        rates = [r for _, r in curve]
        assert rates == sorted(rates)   # monotone nondecreasing
        assert hotkeys.hit_rate_curve({"items": [], "total": 0}) == []

    def test_to_dict_json_safe(self):
        sk = hotkeys.SpaceSaving(4)
        sk.observe(np.array([5, 5, 9], dtype=np.int64))
        d = sk.to_dict()
        json.dumps(d)
        assert d["items"][0][:2] == [5, 2]
        assert d["capacity"] == 4 and d["observed"] == 3


# ---------------------------------------------------------------------- #
# pure merge math
# ---------------------------------------------------------------------- #
class TestMergeMath:
    def test_hist_merge_is_exact(self):
        """Merging two ranks' hist-dicts equals the histogram of the
        pooled samples — identical fixed buckets make it elementwise."""
        rng = np.random.default_rng(3)
        sa = rng.lognormal(0.0, 1.0, 400)
        sb = rng.lognormal(1.0, 0.5, 300)
        ha, hb, hu = Histogram(), Histogram(), Histogram()
        for s in sa:
            ha.observe(float(s))
        for s in sb:
            hb.observe(float(s))
        for s in np.concatenate([sa, sb]):
            hu.observe(float(s))
        merged = aggregator.merge_hist_dicts([ha.as_dict(), hb.as_dict()])
        union = hu.as_dict()
        assert merged["count"] == union["count"] == 700
        assert merged["timed"] == 700
        assert merged["buckets"] == union["buckets"]
        assert merged["p50_ms"] == union["p50_ms"]
        assert merged["p99_ms"] == union["p99_ms"]
        assert merged["max_ms"] == union["max_ms"]
        assert merged["min_ms"] == union["min_ms"]

    def test_hist_merge_keeps_incr_only_counts(self):
        d = {"count": 5, "timed": 0, "sum_ms": 0.0, "min_ms": 0.0,
             "max_ms": 0.0, "buckets": []}
        merged = aggregator.merge_hist_dicts([d, d])
        assert merged["count"] == 10 and merged["timed"] == 0
        assert merged["min_ms"] == 0.0   # no fake latency reconstructed

    def test_skew_metric(self):
        assert aggregator._skew([]) == 1.0
        assert aggregator._skew([0, 0]) == 1.0
        assert aggregator._skew([10, 10]) == 1.0
        assert aggregator._skew([30, 10]) == pytest.approx(1.5)
        assert aggregator._skew([40, 0, 0, 0]) == pytest.approx(4.0)

    def test_merge_cluster_with_dead_rank(self):
        st0 = {"rank": 0, "monitors": {}, "notes": {},
               "shards": {"t": {"kind": "row", "adds": 4, "gets": 2,
                                "applies": 4, "queue_depth": 0,
                                "get_bytes": 10, "add_bytes": 20,
                                "rows": 8}}}
        err = RuntimeError("boom")
        rec = aggregator.merge_cluster(
            {0: st0, 1: err},
            {0: {"status": "ok", "addr": "a:1"}, 1: err}, world=2)
        assert rec["polled"] == 1 and rec["world"] == 2
        assert rec["ranks"]["0"]["status"] == "ok"
        assert rec["ranks"]["1"]["status"] == "unreachable"
        assert "RuntimeError" in rec["ranks"]["1"]["error"]
        assert rec["tables"]["t"]["adds"] == 4
        json.dumps(rec)

    def test_probe_all_concurrent_and_deadline(self):
        """Probes fan out concurrently (N slow ranks cost ~one timeout,
        not N) and an overrunning probe becomes a per-rank TimeoutError
        placeholder instead of stalling the poll."""
        def probe_one(r, stats, health):
            if r == 2:
                time.sleep(30)   # wedged rank: never finishes
                return
            time.sleep(0.2)
            stats[r] = {"rank": r, "monitors": {}, "shards": {}}
            health[r] = {"status": "ok"}

        t0 = time.perf_counter()
        stats, health = aggregator.probe_all(range(3), probe_one,
                                             deadline_s=1.0)
        assert time.perf_counter() - t0 < 2.0   # concurrent + bounded
        assert stats[0]["rank"] == 0 and stats[1]["rank"] == 1
        assert isinstance(stats[2], TimeoutError)
        assert isinstance(health[2], TimeoutError)
        rec = aggregator.merge_cluster(stats, health, world=3)
        assert rec["ranks"]["2"]["status"] == "unreachable"
        assert rec["polled"] == 2

    def test_derive_rates(self):
        mk = lambda ts, adds, gets, q: {  # noqa: E731
            "kind": "cluster", "ts": ts, "tables": {"t": {
                "adds": adds, "gets": gets, "applies": adds,
                "add_bytes": adds * 100, "get_bytes": gets * 100,
                "queue_depth": q,
                "shards": {"0": {"adds": adds, "gets": 0,
                                 "applies": adds,
                                 "add_bytes": adds * 100,
                                 "get_bytes": 0, "queue_depth": q},
                           "1": {"adds": 0, "gets": gets, "applies": 0,
                                 "add_bytes": 0,
                                 "get_bytes": gets * 100,
                                 "queue_depth": 0}}}}}
        prev, cur = mk(100.0, 10, 10, 2), mk(102.0, 50, 10, 5)
        rates = aggregator.derive_rates(prev, cur)
        t = rates["t"]
        assert t["adds_per_s"] == pytest.approx(20.0)
        assert t["gets_per_s"] == 0.0
        assert t["wire_bytes_per_s"] == pytest.approx(2000.0)
        assert t["queue_depth_delta"] == 3
        # windowed skew: ALL interval traffic landed on shard 0
        assert t["skew_window"] == pytest.approx(2.0)
        assert cur["rates"] is rates
        assert aggregator.derive_rates(None, cur) is None

    def test_derive_rates_skips_recovered_shard_history(self):
        """A rank whose stats probe failed last poll and answered this
        one must sit the interval out — its whole cumulative history
        landing in one window would be a phantom rate/skew burst at
        exactly the degraded moment the plane observes."""
        prev = {"kind": "cluster", "ts": 100.0, "tables": {"t": {
            "adds": 10, "gets": 0, "applies": 10,
            "add_bytes": 1000, "get_bytes": 0, "queue_depth": 0,
            "shards": {"0": {"adds": 10, "gets": 0, "applies": 10,
                             "add_bytes": 1000, "get_bytes": 0,
                             "queue_depth": 0}}}}}   # rank 1 missing
        cur = {"kind": "cluster", "ts": 101.0, "tables": {"t": {
            "adds": 1_000_012, "gets": 0, "applies": 1_000_012,
            "add_bytes": 9_999_000, "get_bytes": 0, "queue_depth": 0,
            "shards": {
                "0": {"adds": 12, "gets": 0, "applies": 12,
                      "add_bytes": 1200, "get_bytes": 0,
                      "queue_depth": 0},
                # recovered rank: lifetime counters, no prev entry
                "1": {"adds": 1_000_000, "gets": 0,
                      "applies": 1_000_000, "add_bytes": 9_997_800,
                      "get_bytes": 0, "queue_depth": 0}}}}}
        rates = aggregator.derive_rates(prev, cur)
        t = rates["t"]
        assert t["adds_per_s"] == pytest.approx(2.0)     # shard 0 only
        assert t["wire_bytes_per_s"] == pytest.approx(200.0)
        assert t["skew_window"] == 1.0                   # one clean shard
        # a shard that errored in the PREVIOUS record is excluded too
        prev["tables"]["t"]["shards"]["1"] = {"error": "boom"}
        cur["tables"]["t"]["shards"]["1"]["adds"] = 1_000_000
        rates = aggregator.derive_rates(prev, cur)
        assert rates["t"]["adds_per_s"] == pytest.approx(2.0)


# ---------------------------------------------------------------------- #
# live 2-rank PS: poll, exact merge, skew, hot keys, probes
# ---------------------------------------------------------------------- #
def _zipf_workload(t0, num_row, hot_row, n=40):
    """Gets/adds against both shards with ``hot_row`` dominating —
    the known-head zipf stand-in (deterministic, no huge tail)."""
    rng = np.random.default_rng(7)
    for i in range(n):
        row = hot_row if i % 2 == 0 else int(rng.integers(0, num_row))
        t0.get_rows([row])
        t0.add_rows([row], np.ones((1, 4), np.float32))


class TestClusterLive:
    def test_poll_merges_exactly_and_finds_hot_rows(self, two_ranks):
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        # adagrad: never natively registered, so every op serves on the
        # python plane and the sketch/byte counters are deterministic on
        # BOTH fixture parametrizations (MSG_STATS itself still punts
        # through the native server on the "native" one)
        t0 = AsyncMatrixTable(32, 4, updater="adagrad", name="cl",
                              ctx=two_ranks[0])
        AsyncMatrixTable(32, 4, updater="adagrad", name="cl",
                         ctx=two_ranks[1])
        hot = 19   # rank 1 owns [16, 32): remote-owned hot row
        _zipf_workload(t0, 32, hot)
        agg = aggregator.ClusterAggregator(two_ranks[0].service)
        rec = agg.poll_once()
        assert rec["kind"] == "cluster" and rec["polled"] == 2
        assert set(rec["ranks"]) == {"0", "1"}
        assert all(e["status"] == "ok" for e in rec["ranks"].values())
        table = rec["tables"]["cl"]
        assert set(table["shards"]) == {"0", "1"}
        # exact merge: cluster sums equal the per-rank payload sums
        st0 = two_ranks[0].service.stats_payload()["shards"]["cl"]
        st1 = two_ranks[1].service.stats_payload()["shards"]["cl"]
        for k in ("adds", "gets", "applies", "get_bytes", "add_bytes"):
            assert table[k] == st0[k] + st1[k], k
        assert table["adds"] == 40 and table["gets"] == 40
        assert table["get_bytes"] > 0 and table["add_bytes"] > 0
        # apply histogram: ps[cl].apply is a PROCESS-global monitor, so
        # both in-process ranks report the same pooled distribution —
        # the merge must count it once and agree with the applies
        # scalar beside it (summing per rank would report 2x)
        assert table["apply"]["count"] == st0["apply"]["count"]
        assert table["apply"]["count"] == table["applies"]
        # skew: the hot row drags traffic onto rank 1's shard
        assert table["skew"] > 1.1
        # cluster top-K head is the known hot row
        hk = rec["hotkeys"]["cl"]
        assert hk["top"][0][0] == hot
        assert hk["total"] == 80   # every get + add recorded once
        curve = hk["hit_rate_curve"]
        assert curve[0][0] == 1 and curve[0][1] >= 0.4
        json.dumps(rec)

    def test_rates_between_polls(self, two_ranks):
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        t0 = AsyncMatrixTable(32, 4, updater="adagrad", name="rt",
                              ctx=two_ranks[0])
        AsyncMatrixTable(32, 4, updater="adagrad", name="rt",
                         ctx=two_ranks[1])
        t0.add_rows([20], np.ones((1, 4), np.float32))
        agg = aggregator.ClusterAggregator(two_ranks[0].service)
        agg.poll_once()
        time.sleep(0.05)
        for _ in range(10):
            t0.get_rows([20])
        rec = agg.poll_once()
        r = rec["rates"]["rt"]
        assert r["gets_per_s"] > 0
        assert r["adds_per_s"] == 0.0
        assert rec["rates"]["_interval_s"] > 0
        # interval traffic was all gets on rank 1's shard
        assert r["skew_window"] == pytest.approx(2.0)
        assert len(agg.history()) == 2

    def test_stats_oneshot_probe_and_dead_rank_entry(self, two_ranks):
        from multiverso_tpu.ps import service as svc
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        t0 = AsyncMatrixTable(16, 2, updater="adagrad", name="os",
                              ctx=two_ranks[0])
        AsyncMatrixTable(16, 2, updater="adagrad", name="os",
                         ctx=two_ranks[1])
        t0.add_rows([9], np.ones((1, 2), np.float32))
        # one-shot MSG_STATS probe (never the shared data conn)
        st = two_ranks[0].service.stats_oneshot(1)
        assert st["rank"] == 1 and "os" in st["shards"]
        # local short-circuit
        assert two_ranks[0].service.stats_oneshot(0)["rank"] == 0
        # a dead rank becomes a per-rank error entry, not a failed poll
        config.set_flag("ps_connect_timeout", 2.0)
        two_ranks[1].service.close()
        agg = aggregator.ClusterAggregator(two_ranks[0].service)
        rec = agg.poll_once(timeout=2.0)
        assert rec["ranks"]["0"]["status"] == "ok"
        assert rec["ranks"]["1"]["status"] == "unreachable"
        assert rec["polled"] == 1
        assert "os" in rec["tables"]   # rank 0's shard still reported

    def test_writes_jsonl_and_prom(self, two_ranks, tmp_path):
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        t0 = AsyncMatrixTable(16, 2, updater="adagrad", name="wf",
                              ctx=two_ranks[0])
        AsyncMatrixTable(16, 2, updater="adagrad", name="wf",
                         ctx=two_ranks[1])
        t0.add_rows([9], np.ones((1, 2), np.float32))
        agg = aggregator.ClusterAggregator(
            two_ranks[0].service, directory=str(tmp_path))
        agg.poll_once()
        agg.poll_once()
        sys.path.insert(0, _REPO)
        from tools.dump_metrics import load_records
        recs = load_records(str(tmp_path / "cluster.jsonl"))
        assert len(recs) == 2
        assert recs[1]["kind"] == "cluster"
        assert "rates" in recs[1]   # second record chains off the first
        prom = (tmp_path / "cluster.prom").read_text()
        assert 'rank="cluster"' in prom
        assert 'mv_shard_skew{table="wf",rank="cluster"}' in prom

    def test_flag_gated_lifecycle(self, two_ranks):
        """ensure_started gates on the flag + controller rank; close
        stops an aggregator bound to the closing service."""
        assert aggregator.ensure_started(two_ranks[0].service) is None
        config.set_flag("stats_poll_interval_s", 30.0)
        assert aggregator.ensure_started(two_ranks[1].service) is None
        agg = aggregator.ensure_started(two_ranks[0].service)
        assert agg is not None
        assert aggregator.ensure_started(two_ranks[0].service) is agg
        assert aggregator.global_aggregator() is agg
        two_ranks[0].service.close()
        assert aggregator.global_aggregator() is None
        # the final flush left a record
        assert len(agg.history()) >= 1


# ---------------------------------------------------------------------- #
# mvtop
# ---------------------------------------------------------------------- #
class TestMvtop:
    def test_once_smoke(self, two_ranks, tmp_path, capsys):
        """ISSUE 6 acceptance: on a 2-rank zipf get_rows workload,
        ``mvtop --once`` shows both ranks' health, merged percentiles,
        per-shard skew, and a cluster top-K headed by the hot row."""
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        from tools import mvtop
        t0 = AsyncMatrixTable(32, 4, updater="adagrad", name="mt",
                              ctx=two_ranks[0])
        AsyncMatrixTable(32, 4, updater="adagrad", name="mt",
                         ctx=two_ranks[1])
        hot = 21
        _zipf_workload(t0, 32, hot)
        rdv_dir = str(tmp_path / "rdv")   # the two_ranks rendezvous dir
        rc = mvtop.main(["--rdv", rdv_dir, "--once"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ranks 2/2 up" in out
        lines = out.splitlines()
        assert any(line.startswith("0") and " ok " in line
                   for line in lines)
        assert any(line.startswith("1") and " ok " in line
                   for line in lines)
        assert "table[mt]" in out and "skew=" in out
        assert "p50" in out and "p99" in out
        assert f"hot rows" in out and f"{hot}:" in out
        # the hot row leads the rendered top-K
        hotline = next(line for line in lines if "hot rows" in line)
        assert hotline.split(": ", 1)[1].split(":")[0] == str(hot)
        assert "cache-hit-if-cached" in out

    def test_once_json(self, two_ranks, tmp_path, capsys):
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        from tools import mvtop
        t0 = AsyncMatrixTable(16, 2, updater="adagrad", name="mj",
                              ctx=two_ranks[0])
        AsyncMatrixTable(16, 2, updater="adagrad", name="mj",
                         ctx=two_ranks[1])
        t0.add_rows([9], np.ones((1, 2), np.float32))
        rc = mvtop.main(["--rdv", str(tmp_path / "rdv"), "--once",
                         "--json"])
        rec = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert rec["kind"] == "cluster" and rec["polled"] == 2

    def test_read_addrs_and_empty_dir(self, tmp_path):
        from tools import mvtop
        d = tmp_path / "rdv"
        assert mvtop.read_addrs(str(d)) == {}
        d.mkdir()
        (d / "0.addr").write_text("127.0.0.1:1234")
        (d / "1.addr").write_text("127.0.0.1:1235")
        (d / ".0.addr.tmp").write_text("x")
        (d / "ps_quiesce.0").write_text("x")
        assert mvtop.read_addrs(str(d)) == {0: "127.0.0.1:1234",
                                            1: "127.0.0.1:1235"}
        assert mvtop.read_addrs(str(d), world=1) == {0: "127.0.0.1:1234"}

    def test_render_unreachable_rank(self):
        from tools import mvtop
        rec = aggregator.merge_cluster(
            {0: RuntimeError("refused")}, {0: RuntimeError("refused")},
            world=1)
        out = mvtop.render(rec)
        assert "unreachable" in out and "ranks 0/1 up" in out


# ---------------------------------------------------------------------- #
# dump_metrics: cluster records
# ---------------------------------------------------------------------- #
class TestDumpMetricsCluster:
    def _rec(self, ts, adds, skew, rate=None):
        rec = {"kind": "cluster", "ts": ts, "world": 2, "polled": 2,
               "ranks": {"0": {"status": "ok"}, "1": {"status": "ok"}},
               "monitors": {"m.op": {"count": adds, "sum_ms": 1.0,
                                     "timed": adds, "p50_ms": 0.5,
                                     "p90_ms": 0.8, "p99_ms": 0.9,
                                     "max_ms": 1.0, "min_ms": 0.1,
                                     "buckets": []}},
               "tables": {"t": {"shards": {"0": {}, "1": {}},
                                "adds": adds, "gets": adds * 2,
                                "applies": adds, "queue_depth": 0,
                                "rows": 8, "get_bytes": 1, "add_bytes": 1,
                                "apply": {"count": adds, "p50_ms": 0.1,
                                          "p99_ms": 0.2, "max_ms": 0.3},
                                "skew": skew}},
               "hotkeys": {"t": {"total": 10,
                                 "top": [[5, 6, 0], [1, 4, 0]],
                                 "hit_rate_curve": [[1, 0.6], [2, 1.0]]}}}
        if rate is not None:
            rec["rates"] = {"_interval_s": 1.0,
                            "t": {"adds_per_s": rate, "gets_per_s": 0.0,
                                  "applies_per_s": rate,
                                  "wire_bytes_per_s": 0.0,
                                  "queue_depth_delta": 0,
                                  "skew_window": skew}}
        return rec

    def test_show_cluster(self):
        from tools.dump_metrics import format_record
        out = format_record(self._rec(100.0, 4, 1.5, rate=4.0))
        assert "cluster" in out and "rank 0:" in out and "rank 1:" in out
        assert "table[t]:" in out and "skew=1.5" in out
        assert "rates:" in out and "adds_per_s=4.0" in out
        assert "hot[t]" in out and "5:6" in out
        assert "cache-hit-if-cached" in out
        assert "m.op" in out   # merged monitor table rides along

    def test_diff_cluster_prints_rate_and_skew_deltas(self):
        from tools.dump_metrics import diff_records
        a = self._rec(100.0, 4, 1.2, rate=4.0)
        b = self._rec(200.0, 40, 3.0, rate=40.0)
        out = diff_records(a, b)
        assert "skew b/a" in out
        assert "2.50" in out            # 3.0 / 1.2
        assert "adds_per_s: 4.0 -> 40.0" in out
        # monitor comparison still present
        assert "m.op" in out

    def test_show_per_rank_record_with_hotkeys(self):
        """Per-rank records grew a hotkeys blob; show must render its
        head, not dump the raw dict into the shard line."""
        from tools.dump_metrics import format_record
        rec = {"rank": 0, "ts": 1.0, "monitors": {},
               "shards": {"t": {"kind": "row", "adds": 3,
                                "hotkeys": {"capacity": 4, "total": 3,
                                            "observed": 3,
                                            "items": [[7, 3, 0]]}}}}
        out = format_record(rec)
        assert "hot rows (of 3): 7:3" in out
        assert "hotkeys=" not in out


# ---------------------------------------------------------------------- #
# shard stats growth
# ---------------------------------------------------------------------- #
class TestShardStatsGrowth:
    def test_row_shard_hotkeys_and_bytes(self, two_ranks):
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        t0 = AsyncMatrixTable(16, 4, updater="adagrad", name="sg",
                              ctx=two_ranks[0])
        AsyncMatrixTable(16, 4, updater="adagrad", name="sg",
                         ctx=two_ranks[1])
        for _ in range(3):
            t0.get_rows([9])                       # remote-owned
        t0.add_rows([9], np.ones((1, 4), np.float32))
        sh = t0.server_stats(1)["shards"]["sg"]
        assert sh["get_bytes"] == 3 * 4 * 4        # 3 gets x 4 cols f32
        assert sh["add_bytes"] == 4 * 4
        hk = sh["hotkeys"]
        assert hk["items"][0][0] == 9              # GLOBAL row id
        assert hk["items"][0][1] == 4              # 3 gets + 1 add
        assert hk["capacity"] == config.get_flag("hotkeys_capacity")

    def test_byte_counters_use_encoded_wire_size(self, two_ranks):
        """wire='bf16' tables ship/receive 2-byte payloads: the byte
        counters must reflect the ENCODED blobs (what crossed the
        wire), not the decoded f32 arrays — an operator sizing network
        capacity off wire_bytes_per_s would otherwise read 2x the real
        traffic."""
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        t0 = AsyncMatrixTable(16, 4, updater="adagrad", name="bw",
                              wire="bf16", ctx=two_ranks[0])
        AsyncMatrixTable(16, 4, updater="adagrad", name="bw",
                         wire="bf16", ctx=two_ranks[1])
        t0.add_rows([9], np.ones((1, 4), np.float32))
        t0.get_rows([9])
        sh = t0.server_stats(1)["shards"]["bw"]
        assert sh["add_bytes"] == 4 * 2   # 4 cols x bf16
        assert sh["get_bytes"] == 4 * 2

    def test_hotkeys_flag_off_disables(self, two_ranks):
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        config.set_flag("hotkeys_capacity", 0)
        t0 = AsyncMatrixTable(16, 4, updater="adagrad", name="hf",
                              ctx=two_ranks[0])
        AsyncMatrixTable(16, 4, updater="adagrad", name="hf",
                         ctx=two_ranks[1])
        t0.add_rows([9], np.ones((1, 4), np.float32))
        sh = t0.server_stats(1)["shards"]["hf"]
        assert "hotkeys" not in sh

    def test_add_bytes_counts_requests_not_merged_applies(self):
        """Server-side queue coalescing merges K overlapping adds into
        ONE deduped apply; add_bytes must still count the K requests'
        payloads (the wire traffic), not the merged array's."""
        from multiverso_tpu.ps.shard import RowShard
        from multiverso_tpu.updaters import AddOption, get_updater
        sh = RowShard(0, 8, 4, np.float32, get_updater("sgd"), "ab")
        opt = AddOption(learning_rate=1.0)
        entries = [sh._prep_add_entry(
            {"opt": {"learning_rate": 1.0}},
            [np.array([2], np.int64), np.ones((1, 4), np.float32)])
            for _ in range(3)]
        with sh._lock:
            applies = sh._apply_add_group(entries, opt)
        assert applies == 1                       # merged into one apply
        assert sh.stats()["add_bytes"] == 3 * 4 * 4   # but 3 requests

    def test_monitor_merge_dedupes_shared_process(self, two_ranks):
        """Two ranks served from ONE OS process share the process-global
        Dashboard; the cluster merge must pool it once, not double every
        monitor count (the in-process fixture/bench shape)."""
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        from multiverso_tpu.utils.dashboard import Dashboard
        t0 = AsyncMatrixTable(16, 4, updater="adagrad", name="dd",
                              ctx=two_ranks[0])
        AsyncMatrixTable(16, 4, updater="adagrad", name="dd",
                         ctx=two_ranks[1])
        for _ in range(4):
            t0.add_rows([9], np.ones((1, 4), np.float32))
        agg = aggregator.ClusterAggregator(two_ranks[0].service)
        rec = agg.poll_once()
        local = Dashboard.get("table[dd].add_rows").snapshot()
        merged = rec["monitors"]["table[dd].add_rows"]
        assert merged["count"] == local.count   # once, not 2x

    def test_hash_shard_records_keys_not_slots(self, two_ranks):
        """A hash shard's sketch must rank the workload's KEYS: key
        4242 lands in slot 0, and slot-id recording would report 0."""
        from multiverso_tpu.ps.tables import AsyncSparseKVTable
        t = AsyncSparseKVTable(4, name="hs", ctx=two_ranks[0])
        AsyncSparseKVTable(4, name="hs", ctx=two_ranks[1])
        key = 4243 if (4243 % 2) == 1 else 4242    # owned by rank 1
        for _ in range(3):
            t.add_rows([key], np.ones((1, 4), np.float32))
        t.get_rows([key])
        sh = t.server_stats(1)["shards"]["hs"]
        items = sh["hotkeys"]["items"]
        assert items[0][0] == key
        assert items[0][1] >= 3


# ---------------------------------------------------------------------- #
# exporter label scheme (satellite)
# ---------------------------------------------------------------------- #
def test_prometheus_table_labels():
    from multiverso_tpu.telemetry.exporter import prometheus_text
    txt = prometheus_text({
        "rank": 3,
        "monitors": {
            "table[we].add_rows": {"count": 2, "sum_ms": 1.0, "timed": 2,
                                   "p50_ms": 0.5, "p99_ms": 0.9,
                                   "max_ms": 1.0},
            "ps[we].serve": {"count": 1, "sum_ms": 1.0, "timed": 1,
                             "p50_ms": 1.0, "p99_ms": 1.0, "max_ms": 1.0},
            "zoo.barrier": {"count": 1, "sum_ms": 0.1}},
        "shards": {"we": {"adds": 2}}})
    assert ('mv_monitor_count{name="table[we].add_rows",table="we",'
            'rank="3"} 2') in txt
    assert ('mv_monitor_count{name="ps[we].serve",table="we",rank="3"} 1'
            ) in txt
    # table-less monitors keep the two-label form
    assert 'mv_monitor_count{name="zoo.barrier",rank="3"} 1' in txt
    assert 'mv_shard_adds{table="we",rank="3"} 2' in txt
