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
    def test_cache_returns_private_copy(self):
        """Two ``get()``s hand out arrays that alias neither each other
        nor the table: what a caller writes into its own shows in no
        other, and a later (donating) add changes none handed out."""
        mv.init()
        t = mv.ArrayTable(16, updater="sgd", name="cache_copy_t")
        t.add(np.ones(16, np.float32))
        a = t.get(out=np.empty(16, np.float32))   # the caller's own buffer
        b = t.get()
        expect = a.copy()
        a[:] = -1            # caller mutates its array...
        np.testing.assert_array_equal(b, expect)
        np.testing.assert_array_equal(t.get(), expect)   # ...alone
        t.add(np.ones(16, np.float32))       # donates the live buffer
        np.testing.assert_array_equal(a, -1)
        np.testing.assert_array_equal(b, expect)
        np.testing.assert_array_equal(t.get(), expect - 1)


class TestAsyncBufferVersionSkip:
    def test_no_version_fn_always_fills(self):
        """Built as its two callers build it (a fill function, nothing
        else): every ``get()`` starts the next fill."""
        from multiverso_tpu.utils.async_buffer import AsyncBuffer
        with pytest.raises(TypeError):
            AsyncBuffer(lambda: 0, version_fn=lambda: 0)
        calls = []

        def fill():
            calls.append(1)
            return len(calls)

        buf = AsyncBuffer(fill)
        assert buf.get() == 1
        assert buf.get() == 2
        buf.stop()
