"""AsyncBuffer: double-buffered prefetch.

TPU-native equivalent of the reference ASyncBuffer
(ref: include/multiverso/util/async_buffer.h:11-116), which overlaps a
parameter pull with compute by keeping two buffers and a background fill
thread — the mechanism behind the LR app's pipeline mode
(ref Applications/LogisticRegression/src/model/ps_model.cpp:236-271).

On TPU the same overlap usually comes for free from JAX async dispatch, but
the host-side pattern is still needed when the fill function does blocking
host work (data loading, host-plane table Gets). The API mirrors the
reference: ``get()`` returns the ready buffer and kicks off the next fill.
"""

from __future__ import annotations

import threading
from typing import Callable, Generic, Optional, TypeVar

T = TypeVar("T")


class AsyncBuffer(Generic[T]):
    def __init__(self, fill_fn: Callable[[], T]):
        self._fill_fn = fill_fn
        self._result: Optional[T] = None
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._start_fill()

    def _start_fill(self) -> None:
        def run():
            try:
                self._result = self._fill_fn()
            except BaseException as e:  # surfaced on next get()
                self._error = e
        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def get(self, start_next: bool = True) -> T:
        """Block for the in-flight fill, return it, start the next one.

        On a fill error the exception is re-raised here; a new fill is still
        started (when ``start_next``) so the buffer recovers from transient
        failures instead of serving stale results forever."""
        assert self._thread is not None
        self._thread.join()
        err, self._error = self._error, None
        result = self._result
        if start_next:
            self._start_fill()
        if err is not None:
            raise err
        return result

    def stop(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
