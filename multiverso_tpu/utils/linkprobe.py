"""One-shot host<->device link speed probe.

The host-plane wire filters (bf16/1bit) trade encode CPU for wire bytes —
a win on a slow link (a remote device at ~100 ms/MB), a loss on a fast
one (with no link at all, on the CPU backend, the 1bit filter measured
~10x SLOWER than plain: bench.py's ``array_table_cpu`` phase). The probe
lets table creation warn when a configured filter contradicts the
measured link (VERDICT r3 item 8's guard).

The timed region ends in a host readback of the uploaded buffer, so the
transfer has completed when the clock stops.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

_CACHED_MS: Optional[float] = None

# above this, a 1 MB upload is "slow wire" territory where payload
# compression pays for itself (a remote device measured 100+ ms; local
# CPU/PCIe measure ~1 ms)
FAST_LINK_MS = 20.0


def device_link_ms(refresh: bool = False) -> float:
    """Median warm latency (ms) of a 1 MB host->device upload + readback,
    cached for the process (the wire doesn't change under one run; its
    load does, so treat this as an order-of-magnitude signal)."""
    global _CACHED_MS
    if _CACHED_MS is not None and not refresh:
        return _CACHED_MS
    import jax
    buf = np.zeros(1 << 20, np.uint8)
    float(jax.device_put(buf)[0])          # warm the transfer path
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        x = jax.device_put(buf)
        float(x[0])                        # readback: transfer done
        times.append(time.perf_counter() - t0)
    _CACHED_MS = float(np.median(times) * 1e3)
    return _CACHED_MS


def link_is_fast() -> bool:
    return device_link_ms() < FAST_LINK_MS
