"""ISSUE 11: the pipelined WordEmbedding training path.

Three layers under test:

* ``io/sample_reader.BlockPrepareQueue`` — the K-deep ordered producer
  queue: in-order delivery regardless of thread scheduling, depth
  bounding, ordered exception delivery.
* ``ops/row_assemble`` + ``serving/hotcache`` — bit-parity of the jitted
  gather/pad/scatter kernels with their numpy equivalents, and the
  TrainRowCache's write-through / invalidate / fill_since reconciliation
  contracts (including the device-mirror aliasing regression: the mirror
  must be a private copy, or in-place host mutations show through into
  lazily-evaluated device serves).
* ``apps/word_embedding.train_ps_blocks`` — the acceptance gate: the
  producer-thread pipelined path (with and without the hot-row training
  cache, both push disciplines) yields BIT-IDENTICAL training results to
  the inline prepare path, on both wire planes (sync collective tables
  and the uncoordinated async plane).
"""

import tempfile
import threading
import time

import numpy as np
import pytest

from multiverso_tpu.io.sample_reader import BlockPrepareQueue
from multiverso_tpu.ops import row_assemble
from multiverso_tpu.serving.hotcache import (HotRowCache, TrainRowCache,
                                             make_train_cache,
                                             match_positions)
from multiverso_tpu.utils import config
from multiverso_tpu.utils.dashboard import Dashboard


# ---------------------------------------------------------------------- #
# BlockPrepareQueue
# ---------------------------------------------------------------------- #
class TestBlockPrepareQueue:
    def test_ordered_delivery_under_contention(self):
        rng = np.random.default_rng(0)
        delays = rng.uniform(0, 0.003, 40)

        def fn(item, i):
            time.sleep(delays[i])      # scramble completion order
            return item * item

        with BlockPrepareQueue(list(range(40)), fn, depth=6,
                               threads=4) as q:
            assert list(q) == [i * i for i in range(40)]

    def test_depth_bounds_outstanding_production(self):
        lock = threading.Lock()
        live = {"now": 0, "peak": 0}
        consumed = threading.Event()

        def fn(item, i):
            with lock:
                live["now"] += 1
                live["peak"] = max(live["peak"], live["now"])
            # block production until the consumer starts draining, so a
            # depth violation would have every producer pile in here
            consumed.wait(2.0)
            time.sleep(0.001)
            with lock:
                live["now"] -= 1
            return item

        with BlockPrepareQueue(list(range(12)), fn, depth=3,
                               threads=8) as q:
            time.sleep(0.1)            # let producers run to the bound
            consumed.set()
            out = list(q)
        assert out == list(range(12))
        # claimed-but-unconsumed is capped at depth: with the consumer
        # parked, at most `depth` productions may ever be in flight
        assert live["peak"] <= 3, live["peak"]

    def test_exception_delivered_in_order(self):
        def fn(item, i):
            if item == 3:
                raise ValueError("boom at 3")
            return item

        q = BlockPrepareQueue(list(range(8)), fn, depth=4, threads=3)
        assert [q.next() for _ in range(3)] == [0, 1, 2]
        with pytest.raises(ValueError, match="boom at 3"):
            q.next()
        # the failure closes the queue AND purges produced-ahead items:
        # later indices deterministically surface the close (never a
        # leftover payload won in a race against the producers)
        with pytest.raises(RuntimeError, match="closed"):
            q.next()
        with pytest.raises(RuntimeError, match="closed"):
            q.next()

    def test_validates_depth_and_exhaustion(self):
        with pytest.raises(ValueError):
            BlockPrepareQueue([1], lambda x, i: x, depth=0)
        with BlockPrepareQueue([], lambda x, i: x) as q:
            with pytest.raises(StopIteration):
                q.next()


# ---------------------------------------------------------------------- #
# ops/row_assemble: numpy bit-parity
# ---------------------------------------------------------------------- #
class TestRowAssemble:
    def test_pad_rows_matches_np_pad(self):
        rows = np.random.default_rng(1).normal(
            size=(13, 8)).astype(np.float32)
        got = np.asarray(row_assemble.pad_rows(rows, 16))
        want = np.pad(rows, [(0, 3), (0, 0)])
        assert np.array_equal(got, want)
        # exact-fit block: no pad program, values untouched
        assert np.array_equal(np.asarray(row_assemble.pad_rows(rows, 13)),
                              rows)
        with pytest.raises(ValueError):
            row_assemble.pad_rows(rows, 4)

    def test_gather_pad_matches_numpy(self):
        import jax.numpy as jnp
        store = np.random.default_rng(2).normal(
            size=(50, 6)).astype(np.float32)
        pos = np.array([4, 0, 49, 17])
        got = np.asarray(row_assemble.gather_pad_rows(
            jnp.asarray(store), pos, 8))
        want = np.zeros((8, 6), np.float32)
        want[:4] = store[pos]
        assert np.array_equal(got, want)
        with pytest.raises(ValueError):
            row_assemble.gather_pad_rows(jnp.asarray(store), pos, 3)

    def test_scatter_add_bit_parity_with_numpy(self):
        import jax.numpy as jnp
        store = np.random.default_rng(3).normal(
            size=(30, 5)).astype(np.float32)
        pos = np.array([2, 29, 11])
        delta = np.random.default_rng(4).normal(
            size=(3, 5)).astype(np.float32)
        got = np.asarray(row_assemble.scatter_add_rows(
            jnp.asarray(store), pos, delta))
        want = store.copy()
        want[pos] += delta           # unique pos: one IEEE add per row
        assert np.array_equal(got, want)


# ---------------------------------------------------------------------- #
# TrainRowCache semantics
# ---------------------------------------------------------------------- #
def _rows(n, d, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)) \
        .astype(np.float32)


class TestTrainRowCache:
    def test_fill_lookup_gather_capacity(self):
        c = TrainRowCache("t", 4, capacity=3)
        r = _rows(5, 4)
        assert c.fill(np.arange(5), r) == 3      # capacity-clipped
        pos, ok = c.lookup([0, 1, 2, 3, 4])
        assert int(np.count_nonzero(ok)) == 3
        buf = np.zeros((2, 4), np.float32)
        sel = np.flatnonzero(ok)[:2]
        assert c.gather_into(buf, np.arange(2), pos[sel])
        assert np.array_equal(buf, r[sel])
        # refresh-in-place always lands, even at capacity
        r2 = _rows(5, 4, seed=9)
        got = c.fill(np.arange(5), r2)
        assert got == 3 and len(c) == 3

    def test_writethrough_applies_exact_f32_adds(self):
        c = TrainRowCache("t", 4, capacity=16, writethrough=True)
        r = _rows(6, 4)
        c.fill(np.arange(6), r)
        d = _rows(3, 4, seed=1)
        c.on_push(np.array([1, 3, 5]), d)
        want = r.copy()
        want[[1, 3, 5]] += d
        buf = np.empty((6, 4), np.float32)
        pos, ok = c.lookup(np.arange(6))
        assert bool(ok.all())
        c.gather_into(buf, np.arange(6), pos)
        assert np.array_equal(buf, want)

    def test_invalidate_drops_pushed_rows(self):
        c = TrainRowCache("t", 4, capacity=16, writethrough=False)
        c.fill(np.arange(6), _rows(6, 4))
        c.on_push(np.array([0, 2]), None)
        assert len(c) == 4
        assert not c.covers([0])
        assert c.covers([1, 3, 4, 5])

    def test_fill_since_replays_pushes_after_token(self):
        # a get's reply lands AFTER a push that was dispatched behind it:
        # the fill must reconcile or it would cache pre-push state
        c = TrainRowCache("t", 4, capacity=16, writethrough=True)
        token = c.fill_token()
        reply = _rows(4, 4)                      # pre-push server state
        d = _rows(2, 4, seed=2)
        c.on_push(np.array([1, 2]), d)           # lands before the reply
        assert c.fill_since(np.arange(4), reply, token) == 4
        want = reply.copy()
        want[[1, 2]] += d                        # replayed, same f32 adds
        buf = np.empty((4, 4), np.float32)
        pos, _ = c.lookup(np.arange(4))
        c.gather_into(buf, np.arange(4), pos)
        assert np.array_equal(buf, want)

    def test_on_push_atomic_vs_concurrent_fill_since(self):
        # regression: on_push used to apply the delta and append the
        # push-log entry in TWO lock holds — a wait()-thread fill_since
        # landing between them saw _push_seq still at its token, replayed
        # nothing, and refreshed the just-pushed rows with pre-push reply
        # values, permanently losing the delta from the cached copy
        c = TrainRowCache("t", 4, capacity=16, writethrough=True)
        ids = np.array([1, 2])
        rows = _rows(2, 4)
        c.fill(ids, rows)
        token = c.fill_token()
        reply = rows.copy()                      # reply fetched at token
        entered = threading.Event()
        release = threading.Event()
        real_note = c._note_mutation

        def paused_note(pids, pvals):            # holds the push open
            entered.set()                        # between apply and log
            release.wait(5)
            real_note(pids, pvals)

        c._note_mutation = paused_note
        d = _rows(2, 4, seed=3)
        pusher = threading.Thread(target=c.on_push, args=(ids, d))
        pusher.start()
        assert entered.wait(5)
        filler = threading.Thread(
            target=c.fill_since, args=(ids, reply, token))
        filler.start()                           # must block on the lock
        time.sleep(0.05)
        release.set()
        pusher.join(5)
        filler.join(5)
        del c.__dict__["_note_mutation"]
        _, out = c.serve_full(ids)
        assert np.array_equal(out, rows + d)     # delta survived the race

    def test_memory_stats_counts_push_log(self):
        # the write-through push log retains full delta copies — the
        # PR-10 ledger gauge must report them, not just the cached rows
        c = TrainRowCache("t", 4, capacity=16, writethrough=True)
        c.fill(np.array([1, 2]), _rows(2, 4))
        assert c.memory_stats()["push_log_bytes"] == 0
        c.on_push(np.array([1, 2]), _rows(2, 4, seed=4))
        ms = c.memory_stats()
        assert ms["push_log_entries"] == 1
        assert ms["push_log_bytes"] == 2 * 8 + 2 * 4 * 4   # ids + f32 delta
        c.clear()                                # wildcard entry: ids=None
        assert c.memory_stats()["push_log_entries"] == 2

    def test_fill_since_excludes_nonreplayable_rows(self):
        c = TrainRowCache("t", 4, capacity=16, writethrough=False)
        token = c.fill_token()
        c.on_push(np.array([1, 2]), None)        # invalidate: no replay
        assert c.fill_since(np.arange(4), _rows(4, 4), token) == 2
        assert c.covers([0, 3]) and not c.covers([1])
        # wildcard mutation (clear/overwrite) poisons the whole fill
        c2 = TrainRowCache("t2", 4, capacity=16, writethrough=True)
        t2 = c2.fill_token()
        c2.clear()
        assert c2.fill_since(np.arange(4), _rows(4, 4), t2) == 0

    def test_fill_since_log_overflow_is_conservative(self):
        c = TrainRowCache("t", 4, capacity=16, writethrough=True)
        token = c.fill_token()
        for i in range(TrainRowCache._PUSH_LOG_DEPTH + 2):
            c.on_push(np.array([i % 4]), _rows(1, 4, seed=i))
        assert c.fill_since(np.arange(4), _rows(4, 4), token) == 0

    def test_refresh_gets_bounds_staleness(self):
        c = TrainRowCache("t", 4, capacity=16, writethrough=True,
                          refresh_gets=3)
        c.fill(np.arange(4), _rows(4, 4))
        c.on_get(), c.on_get()
        assert len(c) == 4
        c.on_get()                               # 3rd get: whole-cache drop
        assert len(c) == 0 and c.refreshes == 1

    def test_device_mirror_is_a_private_copy(self):
        """Aliasing regression (caught by the parity suite in the wild):
        jax's CPU backend may zero-copy-alias an aligned host buffer on
        device_put, and the cache mutates its host rows IN PLACE — a
        device block handed out before a push must keep serving pre-push
        values no matter when its lazy gather executes."""
        c = TrainRowCache("t", 8, capacity=64, writethrough=True)
        r = _rows(32, 8)
        c.fill(np.arange(32), r)
        blk = c.device_block(np.arange(16), 16)   # builds the mirror
        assert blk is not None
        d = _rows(16, 8, seed=5)
        c.on_push(np.arange(16), d)               # in-place host +=
        assert np.array_equal(np.asarray(blk)[:16], r[:16])
        # and a FRESH serve sees the push
        blk2 = c.device_block(np.arange(16), 16)
        assert np.array_equal(np.asarray(blk2)[:16], r[:16] + d)

    def test_device_block_requires_full_coverage(self):
        c = TrainRowCache("t", 4, capacity=16)
        c.fill(np.arange(4), _rows(4, 4))
        assert c.device_block([0, 1, 9], 8) is None       # 9 uncached
        assert c.device_block(np.arange(4), 2) is None    # > bucket
        # a miss block must not pay the mirror build it can never use
        # (in invalidate mode EVERY post-push block is such a miss —
        # rebuilding 32 MB per block under the lock was pure waste)
        assert c._dev is None
        blk = c.device_block([2, 0], 4)
        assert blk is not None and np.asarray(blk).shape == (4, 4)
        assert c._dev is not None                         # hit built it

    def test_dashboard_counters_ride_count(self):
        Dashboard.reset()
        c = TrainRowCache("ctr", 4, capacity=4)
        c.count(5, 2)
        assert Dashboard.get("table[ctr].get.train_cache_hit").count == 5
        assert Dashboard.get("table[ctr].get.train_cache_miss").count == 2

    def test_factory_flag_gating_and_eligibility(self):
        assert make_train_cache("t", 4, np.float32, True) is None  # off
        config.set_flag("train_cache_rows", 8)
        config.set_flag("train_cache_mode", "writethrough")
        with pytest.raises(ValueError, match="not .*eligible|eligible"):
            make_train_cache("t", 4, np.float32, writethrough_ok=False)
        config.set_flag("train_cache_mode", "auto")
        c = make_train_cache("t", 4, np.float32, writethrough_ok=False)
        assert c is not None and not c.writethrough
        config.set_flag("train_cache_mode", "bogus")
        with pytest.raises(ValueError):
            make_train_cache("t", 4, np.float32, True)

    def test_match_positions_edge_cases(self):
        pos, ok = match_positions(None, np.array([1, 2]))
        assert not ok.any()
        cids = np.array([2, 5, 9])
        pos, ok = match_positions(cids, np.array([5, 1, 9, 10]))
        assert list(ok) == [True, False, True, False]
        assert pos[0] == 1 and pos[2] == 2


# ---------------------------------------------------------------------- #
# async-plane eligibility: transports that break dispatch==FIFO ordering
# must disqualify write-through (auto degrades, it never diverges)
# ---------------------------------------------------------------------- #
class TestWritethroughEligibility:
    def test_get_window_disqualifies_writethrough(self, tmp_path):
        """The get coalescer may QUEUE a cold fetch behind an in-flight
        one, so a push can enter the conn FIFO between a get's token and
        its actual dispatch — write-through would replay that push onto
        a reply that already contains it (double-apply). 'auto' must
        degrade to invalidate on such a table."""
        from multiverso_tpu.ps.service import (FileRendezvous, PSContext,
                                               PSService)
        from multiverso_tpu.ps.tables import AsyncMatrixTable
        config.set_flag("ps_native", False)
        config.set_flag("train_cache_rows", 32)
        config.set_flag("train_cache_mode", "auto")
        ctx = PSContext(0, 1, PSService(
            0, 1, FileRendezvous(str(tmp_path / "rdv"))))
        try:
            t = AsyncMatrixTable(16, 4, name="wt_gw", get_window_ms=5.0,
                                 ctx=ctx)
            assert t._train_cache is not None
            assert not t._train_cache.writethrough
            # the cache/dispatch ordering lock exists in BOTH modes:
            # invalidate needs it too — a push logged but not yet in
            # the conn FIFO lets a racing get cache pre-push rows under
            # a current fill token, permanently stale
            assert t._tc_order is not None
            # same table minus the coalescer: write-through eligible
            t2 = AsyncMatrixTable(16, 4, name="wt_ok", ctx=ctx)
            assert t2._train_cache is not None
            assert t2._train_cache.writethrough
            assert t2._tc_order is not None
        finally:
            ctx.close()


# ---------------------------------------------------------------------- #
# table-level: invalidation on push (no stale device serves)
# ---------------------------------------------------------------------- #
class TestTableTrainCache:
    def _sync_table(self, name, mode):
        import multiverso_tpu as mv
        mv.init()
        config.set_flag("train_cache_rows", 64)
        config.set_flag("train_cache_mode", mode)
        return mv.MatrixTable(32, 4, name=name, updater="default",
                              seed=3, init_scale=0.1)

    @pytest.mark.parametrize("mode", ["invalidate", "auto"])
    def test_push_never_serves_stale_device_copy(self, mode):
        t = self._sync_table(f"tc_stale_{mode}", mode)
        ids = np.arange(8)
        before = t.get_rows(ids)                 # warms the cache
        blk = t.train_cache_device_block(ids, 8)
        assert blk is not None                   # warm: device serve
        assert np.array_equal(np.asarray(blk), before)
        delta = _rows(8, 4, seed=7)
        t.add_rows(ids, delta)
        # the next serve must reflect the push — stale device copy is
        # the exact bug the invalidate/writethrough disciplines prevent
        after = t.get_rows(ids)
        assert np.array_equal(after, before + delta)
        blk2 = t.train_cache_device_block(ids, 8)
        if blk2 is not None:                     # writethrough keeps rows
            assert np.array_equal(np.asarray(blk2), before + delta)

    def test_cached_get_bit_equals_uncached(self):
        import multiverso_tpu as mv
        mv.init()
        t0 = mv.MatrixTable(32, 4, name="tc_par_off", updater="default",
                            seed=11, init_scale=0.1)
        config.set_flag("train_cache_rows", 64)
        t1 = mv.MatrixTable(32, 4, name="tc_par_on", updater="default",
                            seed=11, init_scale=0.1)
        rng = np.random.default_rng(0)
        # deterministic id sets: later gets are SUBSETS of earlier ones,
        # so the sync plane's all-or-nothing serve is guaranteed to hit
        # (a full-hit must be exercised for the parity to be non-vacuous)
        for step, ids in enumerate([np.arange(24), np.arange(16),
                                    np.arange(8, 24), np.arange(4, 12),
                                    np.arange(20), np.arange(24)]):
            a, b = t0.get_rows(ids), t1.get_rows(ids)
            assert np.array_equal(a, b), f"step {step}"
            d = rng.normal(size=(ids.size, 4)).astype(np.float32)
            t0.add_rows(ids, d), t1.add_rows(ids, d)
        assert np.array_equal(t0.get_rows(np.arange(24)),
                              t1.get_rows(np.arange(24)))
        stats = t1.train_cache_stats()
        assert stats is not None and stats["hits"] > 0


# ---------------------------------------------------------------------- #
# fused-path pair-batch LRU (the _pair_cache satellite)
# ---------------------------------------------------------------------- #
class TestPairCacheLRU:
    def test_bounded_lru_with_ledger_gauge(self):
        import multiverso_tpu as mv
        from multiverso_tpu.apps.word_embedding import (WEConfig,
                                                        WordEmbedding,
                                                        synthetic_corpus)
        from multiverso_tpu.data.dictionary import Dictionary
        from multiverso_tpu.telemetry import memstats

        mv.init()
        config.set_flag("we_pair_cache_corpora", 2)
        tokens = synthetic_corpus(4_000, vocab=50, seed=0)
        cfg = WEConfig(size=8, min_count=1, batch_size=64, negative=2,
                       window=2, epoch=1)
        we = WordEmbedding(cfg, Dictionary.build(tokens, 1))
        corpora = [we.prepare_ids(synthetic_corpus(4_000, vocab=50,
                                                   seed=s))
                   for s in range(3)]
        for ids in corpora:
            we._device_pairs(ids)
        # bounded at 2: the oldest corpus evicted, not the whole cache
        assert len(we._pair_cache) == 2
        # alternating epochs over the RETAINED corpora never regenerate:
        # same two keys survive, just LRU-reordered (the old keep-one
        # cache rebuilt every epoch here)
        keys_before = set(we._pair_cache)
        hit1 = we._device_pairs(corpora[1])
        hit2 = we._device_pairs(corpora[2])
        assert set(we._pair_cache) == keys_before
        assert we._device_pairs(corpora[1]) is hit1
        assert we._device_pairs(corpora[2]) is hit2
        # the PR-10 ledger sees it (registered at construct time)
        g = we.pair_cache_memory_stats()
        assert g["corpora"] == 2 and g["device_bytes"] > 0
        snap = memstats.LEDGER.snapshot()["components"]
        assert any(k.startswith("we.pair_cache[") for k in snap)


# ---------------------------------------------------------------------- #
# end-to-end parity: pipelined vs inline, both wire planes
# ---------------------------------------------------------------------- #
def _we_run(plane, pipeline, cache_rows, mode="auto"):
    """One tiny deterministic WE training run; returns (per-block losses,
    final embed_in rows, final embed_out rows)."""
    import multiverso_tpu as mv
    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary

    if plane == "async":
        config.set_flag("ps_world", 1)
        config.set_flag("ps_rank", 0)
        config.set_flag("ps_rendezvous", tempfile.mkdtemp())
    config.set_flag("train_cache_rows", cache_rows)
    config.set_flag("train_cache_mode", mode)
    mv.init()
    cfg = WEConfig(size=8, min_count=2, batch_size=256, negative=3,
                   window=3, epoch=2, data_block_size=6_000,
                   use_ps="1", async_ps="1" if plane == "async" else "0",
                   ps_device_plane="auto" if plane == "async" else "0",
                   seed=7, pipeline=str(pipeline))
    tokens = synthetic_corpus(24_000, vocab=400, seed=3)
    we = WordEmbedding(cfg, Dictionary.build(tokens, 2))
    losses = []
    orig = we._train_prepared

    def train_prepared(p, nw):      # (loss, the plans' counts)
        out = orig(p, nw)
        losses.append(out[0])
        return out

    we._train_prepared = train_prepared
    stats = we.train_ps_blocks(we.prepare_ids(tokens))
    rows = we._rows(np.arange(len(we.dict)))    # the words' rows
    rin, rout = we.table_in.get_rows(rows), we.table_out.get_rows(rows)
    cache = we.table_in.train_cache_stats()
    mv.shutdown()
    assert np.isfinite(stats["loss"])
    return losses, np.array(rin), np.array(rout), cache


@pytest.mark.parametrize("plane", ["async", "sync"])
class TestPipelineParity:
    """The ISSUE-11 acceptance gate, per wire plane: every pipelined
    variant is BIT-IDENTICAL to the inline oracle — losses block by
    block and both embedding tables row for row."""

    def test_pipeline_and_cache_bit_parity(self, plane):
        oracle = _we_run(plane, pipeline=0, cache_rows=0)
        variants = {
            "pipeline": _we_run(plane, 1, 0),
            "pipeline+writethrough": _we_run(plane, 1, 4096, "auto"),
            "pipeline+invalidate": _we_run(plane, 1, 4096, "invalidate"),
        }
        for tag, got in variants.items():
            bad = [i for i, (a, b) in enumerate(zip(oracle[0], got[0]))
                   if a != b][:3]
            assert got[0] == oracle[0], (
                f"{plane}/{tag}: block losses diverge at {bad}")
            assert np.array_equal(got[1], oracle[1]), f"{plane}/{tag} in"
            assert np.array_equal(got[2], oracle[2]), f"{plane}/{tag} out"
        # the cache actually served: parity must not be vacuous
        wt = variants["pipeline+writethrough"][3]
        assert wt is not None and wt["hits"] > 0, wt


# ---------------------------------------------------------------------- #
# ISSUE 40: a block's scan writes through row_combine.add_rows with plans
# made ahead of it, in both planes
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("negs", ["drawn_on_the_device", "packed"])
def test_a_device_plane_block_is_the_host_planes_local_train(negs):
    """One PS block through ``train_ps_blocks`` on the device plane (the
    plans a program of their own ahead of the block's) leaves the tables
    the host plane's ``_local_train_fn`` leaves on the same block, and
    both calls' spans say what the table writes were handed."""
    import multiverso_tpu as mv
    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary
    from multiverso_tpu.telemetry import trace as ttrace

    # small blocks of a large vocabulary bring their negatives packed
    # (the benchmark's cell), large ones draw them from a 4-byte seed
    block = 6_000 if negs == "drawn_on_the_device" else 300
    tokens = synthetic_corpus(24_000, vocab=400, seed=3)
    out = {}
    for plane in ("device", "host"):
        mv.init()
        cfg = WEConfig(size=8, min_count=2, batch_size=64, negative=3,
                       window=3, epoch=1, data_block_size=block, use_ps="1",
                       ps_device_plane="1" if plane == "device" else "0",
                       seed=7)
        we = WordEmbedding(cfg, Dictionary.build(tokens, 2))
        assert we._dev_negs == (negs == "drawn_on_the_device")
        local_calls = []
        if plane == "host":
            fn = we._local_train_fn()
            we._fused_cache["ps_local"] = lambda *a: (
                local_calls.append(fn(*a)) or local_calls[-1])
        start = len(ttrace.events())
        stats = we.train_ps_blocks(we.prepare_ids(tokens)[:block])
        [call] = [e for e in ttrace.events()[start:]
                  if e["name"] == "we.blocks"]
        assert call["args"]["plane"] == plane and call["args"]["blocks"] == 1
        assert len(local_calls) == (plane == "host")
        if local_calls:     # the span's counts are that program's
            assert np.asarray(local_calls[0][3]).tolist()[:4] == [
                call["args"][k] for k in ("update_rows", "unique_rows",
                                          "head_rows", "walk_slots")]
        rows = we._rows(np.arange(len(we.dict)))
        out[plane] = (stats["loss"], np.array(we.table_in.get_rows(rows)),
                      np.array(we.table_out.get_rows(rows)), call["args"])
        mv.shutdown()
    dev, host = out["device"], out["host"]
    assert dev[0] == pytest.approx(host[0], rel=1e-5)
    for got, want in zip(dev[1:3], host[1:3]):
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    # a pair's update rows are its centre, its context and its negatives;
    # both planes were handed the same ids, whatever their buckets
    for k in ("update_rows", "unique_rows"):
        assert dev[3][k] == host[3][k] > 0
    for args in (dev[3], host[3]):
        assert 0 < args["head_rows"] <= args["unique_rows"] <= (
            args["update_rows"])


# ---------------------------------------------------------------------- #
# ISSUE 45: the block's scan runs on buckets of whole lanes
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("same_floats", [False], indirect=True,
                         ids=["default"])
def test_a_block_on_the_lane_wide_bucket_trains_what_the_narrow_one_did(
        same_floats, monkeypatch):
    """One block through the host plane's local-train program (the scan
    both planes share) on its bucket padded to 128 columns, against the
    same program on the bucket as pulled, 12 wide: the zero columns stay
    zero and add nothing to a score, so the deltas and the loss are the
    narrow scan's, to the last bits ``same_floats`` allows two programs
    at XLA:CPU's default compile. Not bit for bit here, compiled as
    written either (8 values of 6,144 then differ by ulps): a score is a
    dot over the row, and XLA:CPU's dot sums a row of 128 in another
    order than a row of 12, zeros or not. On the chip a row of 300 is 384
    lanes before and after."""
    import multiverso_tpu as mv
    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary
    from multiverso_tpu.ops import row_combine

    tokens = synthetic_corpus(24_000, vocab=400, seed=3)
    mv.init()
    cfg = WEConfig(size=12, min_count=2, batch_size=64, negative=3, window=3,
                   epoch=1, data_block_size=2_000, use_ps="1",
                   ps_device_plane="0", seed=7)
    we = WordEmbedding(cfg, Dictionary.build(tokens, 2))
    fn, handed = we._local_train_fn(), []
    we._fused_cache["ps_local"] = lambda *a: handed.append(a) or fn(*a)
    we.train_ps_blocks(we.prepare_ids(tokens)[:2_000])
    [args] = handed

    def trained(lane_wide):
        monkeypatch.setattr(row_combine, "lane_wide", lane_wide)
        del we._fused_cache["ps_local"]
        lowered = we._local_train_fn().lower(*args)
        buckets = lowered.as_text().count(
            f"tensor<{args[0].shape[0] + 1}x{lane_wide(12)}xf32>")
        return buckets, lowered.compile()(*args)

    lanes = row_combine.lane_wide
    (wide_buckets, wide), (narrow_buckets, narrow) = (
        trained(lanes), trained(lambda width: width))
    assert lanes(12) == 128 and wide_buckets > 0 and narrow_buckets > 0
    for got, want in zip(wide[:3], narrow[:3]):     # d_in, d_sec, loss
        assert got.shape == want.shape and np.abs(np.asarray(want)).max() > 0
        same_floats(got, want)
    np.testing.assert_array_equal(wide[3], narrow[3])       # the counts
    mv.shutdown()
