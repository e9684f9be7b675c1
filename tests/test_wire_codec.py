"""The PS wire's payload codec (none / bf16) and the table get cache.

``ps/wire.encode_payload`` and ``decode_payload`` must invert each other
at either endpoint and refuse a mode they do not speak; the
version-stamped get cache's monitor counter must show that a repeated
Get with no intervening Add dispatches no device transfer.
"""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.utils import config
from multiverso_tpu.utils.dashboard import Dashboard


class TestPSWirePayload:
    """ps/wire.decode_payload inverts encode_payload (either endpoint)."""

    def test_none_and_bf16_roundtrip(self):
        from multiverso_tpu.ps import wire as ps_wire
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        for mode in ("none", "bf16"):
            blobs = ps_wire.encode_payload(arr, mode)
            out = ps_wire.decode_payload(blobs, mode, arr.shape, np.float32)
            np.testing.assert_allclose(out, arr, rtol=1e-2)

    @pytest.mark.parametrize("mode", ["1bit", "topk"])
    def test_removed_modes_are_refused(self, mode):
        from multiverso_tpu.ps import wire as ps_wire
        arr = np.ones(8, np.float32)
        with pytest.raises(ValueError):
            ps_wire.encode_payload(arr, mode)
        with pytest.raises(ValueError):
            ps_wire.decode_payload([arr], mode, arr.shape, np.float32)


class TestGetCache:
    def test_repeated_get_skips_transfer(self):
        """Acceptance: a repeated get with no intervening add is served
        from the version cache — the `.get.cached` monitor counts the hit
        and the snapshot/transfer is skipped."""
        mv.init()
        t = mv.ArrayTable(1000, updater="sgd", name="cache_t")
        mon = Dashboard.get("table[cache_t].get.cached")
        t.add(np.ones(1000, np.float32))
        a = t.get()
        base = mon.count
        b = t.get()           # no intervening add: cache hit
        c = t.get()
        assert mon.count == base + 2
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
        t.add(np.ones(1000, np.float32))
        d = t.get()           # version bumped: miss, fresh transfer
        assert mon.count == base + 2
        assert not np.array_equal(a, d)
        t.get()               # and the fresh value is cached again
        assert mon.count == base + 3

    def test_cache_returns_private_copy(self):
        mv.init()
        t = mv.ArrayTable(16, updater="sgd", name="cache_copy_t")
        t.add(np.ones(16, np.float32))
        t.get()              # prime the cache (a miss hands out the
        a = t.get()          # read-only device view; hits are writable)
        expect = a.copy()
        a[:] = -1            # caller mutates its hit...
        b = t.get()          # ...the next hit must not see it
        np.testing.assert_array_equal(b, expect)

    def test_get_async_populates_and_hits_cache(self):
        mv.init()
        t = mv.ArrayTable(64, updater="sgd", name="cache_async_t")
        t.add(np.ones(64, np.float32))
        mon = Dashboard.get("table[cache_async_t].get.cached")
        first = t.read(t.get_async())
        base = mon.count
        second = t.read(t.get_async())   # unchanged: served from cache
        assert mon.count == base + 1
        np.testing.assert_array_equal(first, second)

    def test_flag_disables_cache(self):
        mv.init()
        config.set_flag("table_get_cache", False)
        t = mv.ArrayTable(32, updater="sgd", name="cache_off_t")
        t.add(np.ones(32, np.float32))
        mon = Dashboard.get("table[cache_off_t].get.cached")
        t.get()
        t.get()
        assert mon.count == 0

    def test_version_property_monotonic(self):
        mv.init()
        t = mv.ArrayTable(8, updater="sgd", name="ver_t")
        v0 = t.version
        t.add(np.ones(8, np.float32))
        assert t.version > v0


class TestAsyncBufferVersionSkip:
    def test_unchanged_version_skips_fill(self):
        from multiverso_tpu.utils.async_buffer import AsyncBuffer
        calls = []
        state = {"v": 0}

        def fill():
            calls.append(1)
            return len(calls)

        buf = AsyncBuffer(fill, version_fn=lambda: state["v"])
        assert buf.get() == 1
        assert buf.get() == 1          # version unchanged: fill skipped
        assert buf.get() == 1
        assert buf.skipped_fills == 3
        assert len(calls) == 1
        state["v"] = 1
        buf.get()                      # stale serve + refill kicked off
        assert buf.get() == 2          # the refill's result
        buf.stop()

    def test_no_version_fn_always_fills(self):
        from multiverso_tpu.utils.async_buffer import AsyncBuffer
        calls = []

        def fill():
            calls.append(1)
            return len(calls)

        buf = AsyncBuffer(fill)
        assert buf.get() == 1
        assert buf.get() == 2
        buf.stop()
