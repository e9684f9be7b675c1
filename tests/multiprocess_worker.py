"""Subprocess body for the multi-process integration test (tier-2 fixture:
the reference runs the same binary under ``mpirun -np N`` — here the same
script runs under N coordinated JAX processes; ref Test/main.cpp:497-518).

Invoked as: python multiprocess_worker.py <coordinator> <nprocs> <pid>
Prints one line of JSON results that the parent asserts on.
"""

import json
import sys


def main():
    coordinator, nprocs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=nprocs, process_id=pid)
    import numpy as np

    import multiverso_tpu as mv
    from multiverso_tpu.sharedvar import mv_shared

    mv.init()
    out = {"rank": mv.rank(), "size": mv.size(),
           "num_workers": mv.num_workers(),
           "num_servers": mv.num_servers(),
           "devices": len(jax.devices())}

    # barrier (ref TestArray barrier fencing)
    mv.barrier()

    # aggregate: each process contributes rank+1 -> sum = N(N+1)/2
    data = np.full(4, float(pid + 1), np.float32)
    agg = mv.aggregate(data)
    out["aggregate"] = agg.tolist()

    # KV aggregated Get (ref kv_table.h:44-99 server-summed read): repeatable
    # and non-destructive, so two calls must agree and must not perturb the
    # local store that allreduce() then commits
    kv = mv.KVTable(name="mp_kv")
    kv.add(list(range(pid + 1)), [10] * (pid + 1))  # rank r adds r+1 keys
    gview = kv.get(global_=True)
    assert kv.get(global_=True) == gview
    out["kv_global"] = {str(k): float(v) for k, v in sorted(gview.items())}
    merged = kv.allreduce()
    out["kv"] = {str(k): float(v) for k, v in sorted(merged.items())}

    # collective matrix row add: same ids everywhere, vals summed
    mt = mv.MatrixTable(16, 4, name="mp_matrix")
    mt.add_rows([1, 3], np.full((2, 4), float(pid + 1), np.float32))
    out["matrix_rows"] = mt.get_rows([1, 3]).tolist()

    # collective row add with DIFFERENT id sets per process (the
    # WordEmbedding pattern): union semantics
    mt2 = mv.MatrixTable(16, 4, name="mp_matrix_union")
    mt2.add_rows([pid, pid + 1], np.full((2, 4), float(pid + 1), np.float32))
    out["matrix_union"] = mt2.get_rows(list(range(nprocs + 1)))[:, 0].tolist()

    # sparse stale-row protocol under DIFFERING per-rank id sets: rank p
    # adds only row p, but the dirty bits must cover the cross-process
    # union, or every other rank serves row p stale from its cache
    smt = mv.SparseMatrixTable(nprocs + 1, 4, name="mp_sparse_union",
                               num_workers=nprocs)
    all_rows = list(range(nprocs + 1))
    smt.get_rows_sparse(all_rows, worker_id=pid)      # warm the cache
    smt.add_rows([pid], np.ones((1, 4), np.float32))  # collective, union ids
    out["sparse_union"] = smt.get_rows_sparse(
        all_rows, worker_id=pid)[:, 0].tolist()

    # uncoordinated async plane over the jax.distributed coordinator's KV
    # store: each rank pushes its OWN disjoint rows at its own pace
    from multiverso_tpu.ps import AsyncMatrixTable
    at = AsyncMatrixTable(8 * nprocs, 4, name="mp_async_jx")
    # the default context under jax.distributed must have taken the
    # coordinator-KV rendezvous (ref Controller registration,
    # src/controller.cpp:38-80) — the multi-host path, explicitly
    from multiverso_tpu.ps.service import JaxRendezvous
    rdv = at.ctx.service._rendezvous
    out["rendezvous"] = type(rdv).__name__ if rdv is not None else None
    if nprocs > 1:
        assert isinstance(rdv, JaxRendezvous), rdv
        # publish/lookup round-trip through the coordinator KV store
        rdv.publish(1000 + pid, f"probe:{pid}")
        assert rdv.lookup(1000 + ((pid + 1) % nprocs), 20.0) == (
            f"probe:{(pid + 1) % nprocs}")
    my_rows = np.arange(8) * nprocs + pid
    for _ in range(pid + 1):   # per-rank rate
        at.add_rows(my_rows, np.ones((8, 4), np.float32))
    at.flush()
    mv.barrier()               # test determinism only: all pushes landed
    got = at.get_rows(np.arange(8 * nprocs))
    out["async_row_sum"] = float(got.sum())

    # sharedvar delta-sync across processes: every worker adds +1 to its
    # local copy; after sync the shared value reflects all workers' deltas
    shared = mv_shared({"w": np.zeros(4, np.float32)}, name="mp_shared")
    local = shared.get()
    local["w"] = local["w"] + 1.0
    merged_params = shared.sync(local)
    mv.barrier()
    final = shared.get()
    out["sharedvar"] = final["w"].tolist()

    mv.shutdown()
    print("RESULT " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
