"""Table micro-perf harness.

Parity with the reference's in-tree perf tests
(ref: Test/main.cpp:340-495 TestDensePerf/TestSparsePerf — timings of
whole-table Get, row-batch Add/Get on a 1M x 50 float matrix, plus a
Dashboard dump). Run on the real chip:

    python tools/perf_tables.py [rows] [cols]
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

import multiverso_tpu as mv
from multiverso_tpu.utils.dashboard import Dashboard


def timeit(fn, n=10, warmup=True):
    """Differential (two-point slope) ms/op via bench._differential, the
    methodology of the bench.py docstring. ``warmup=False`` + ``n=1``:
    stateful one-shot op whose first call IS the measurement (wall time
    incl. the fixed sync cost; a warmup would consume the state being
    measured)."""
    from bench import _differential
    if warmup:
        fn()  # compile

    def run(k):
        t0 = time.perf_counter()
        for _ in range(k):
            fn()
        return time.perf_counter() - t0

    lo, hi = max(n // 4, 1), n
    if hi == lo:
        return run(1) * 1e3
    return _differential(run, lo, hi)[0] * 1e3


def main():
    args = sys.argv[1:]
    if any(not a.isdigit() for a in args):  # incl. -h/--help/negatives
        print(__doc__)
        return
    rows = int(args[0]) if len(args) > 0 else 1_000_000
    cols = int(args[1]) if len(args) > 1 else 50
    mv.init()
    rng = np.random.default_rng(0)

    print(f"== dense perf: {rows} x {cols} float32 "
          f"({rows * cols * 4 / 1e6:.0f} MB) ==")
    m = mv.MatrixTable(rows, cols, name="perf_dense")
    full = rng.normal(size=(rows, cols)).astype(np.float32)
    print(f"add all      : {timeit(lambda: m.add(full), 5):9.2f} ms")
    print(f"get all      : {timeit(lambda: m.get(), 5):9.2f} ms")

    for k in (10, 1000, 100_000):
        ids = rng.choice(rows, size=k, replace=False)
        vals = rng.normal(size=(k, cols)).astype(np.float32)
        print(f"add {k:7d} rows: {timeit(lambda: m.add_rows(ids, vals)):9.2f} ms")
        print(f"get {k:7d} rows: {timeit(lambda: m.get_rows(ids)):9.2f} ms")

    print(f"== sparse (stale-row) perf ==")
    s = mv.SparseMatrixTable(rows, cols, name="perf_sparse", num_workers=1)
    ids = rng.choice(rows, size=100_000, replace=False)
    s.get_rows_sparse(ids)  # first pull: everything stale
    t = timeit(lambda: s.get_rows_sparse(ids))
    print(f"sparse re-get of fresh 100k rows: {t:9.2f} ms "
          f"(stale fraction {s.stale_fraction(ids):.3f})")
    s.add_rows(ids[:1000], np.ones((1000, cols), np.float32))
    # no warmup: the dirty bits ARE the state being measured (the jit is
    # already warm from the fresh re-get above)
    t = timeit(lambda: s.get_rows_sparse(ids), n=1, warmup=False)
    print(f"sparse get after 1k-row dirty   : {t:9.2f} ms")

    Dashboard.display()
    mv.shutdown()


if __name__ == "__main__":
    main()
