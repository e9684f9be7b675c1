"""Worker for bench.bench_aggregate_path: np=N jax.distributed CPU
processes timing mv.aggregate through (a) the device process_sum path and
(b) the legacy allgather+numpy-sum, on the same payload.

Invoked: python tools/bench_aggregate.py <coord_port> <world> <rank> <mb>
Rank 0 prints "RESULT {...}".
"""
import json
import sys
import time


def main():
    port, world, rank, mb = (int(sys.argv[1]), int(sys.argv[2]),
                             int(sys.argv[3]), float(sys.argv[4]))
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(f"127.0.0.1:{port}", world, rank)
    import numpy as np

    from multiverso_tpu.parallel.collectives import process_sum

    n = int(mb * 1e6 / 4)
    arr = np.full(n, float(rank + 1), np.float32)

    def legacy(a):
        from jax.experimental import multihost_utils
        g = multihost_utils.process_allgather(a, tiled=False)
        return np.asarray(g).sum(axis=0).astype(a.dtype)

    # Compressed-wire variant of the host aggregation: the cross-process
    # delta aggregation is a seam where half the bytes could beat the
    # cast's cost — measured against plain here.
    def bf16_agg(a):
        import ml_dtypes
        from jax.experimental import multihost_utils
        g = multihost_utils.process_allgather(
            a.astype(ml_dtypes.bfloat16), tiled=False)
        return np.asarray(g).astype(np.float32).sum(axis=0)

    out = {}
    want = world * (world + 1) / 2
    for name, fn, exact in (("process_sum", process_sum, True),
                            ("allgather", legacy, True),
                            ("allgather_bf16", bf16_agg, False)):
        fn(arr)                     # warm/compile
        reps, t0 = 5, time.monotonic()
        for _ in range(reps):
            got = fn(arr)
        dt = (time.monotonic() - t0) / reps
        if exact:
            assert got[0] == want, got[0]
        else:
            # lossy wire: small integers survive bfloat16 near-exactly
            assert abs(got[0] - want) < 0.1 * want, (name, got[0])
        out[name + "_ms"] = round(dt * 1e3, 2)
    out["speedup"] = round(out["allgather_ms"] / out["process_sum_ms"], 2)
    out["bf16_vs_plain"] = round(out["allgather_ms"]
                                 / out["allgather_bf16_ms"], 2)
    if rank == 0:
        print("RESULT " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
