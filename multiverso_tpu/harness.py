"""Tier-2 integration battery: the reference ``Test/main.cpp`` dispatcher as a
runnable module.

The reference builds one binary whose argv[1] selects a test and runs it under
``mpirun -np N`` — the MPI world *is* the fixture (ref Test/main.cpp:497-518;
the Docker CI battery runs kv/array/net/ip/checkpoint/restore/allreduce at
np=4, ref deploy/docker/Dockerfile). Here the same battery runs as::

    python -m multiverso_tpu.harness <cmd> [-key=value ...]

with cmd in {kv, array, net, ip, matrix, checkpoint, restore, allreduce,
async, ftrl_sparse, dense_perf, sparse_perf, all}. ``-nprocs=N``
relaunches the chosen test as N
coordinated JAX processes on this host (the ``mpirun -np N`` analogue used by
tests/test_multiprocess.py); inside each process the battery is identical, so
single- and multi-process behavior are asserted by the same code.

Every test *asserts* its expected values (the reference printed-and-eyeballed
or had its exits commented out, Test/main.cpp:110-119) and prints one
``HARNESS PASS <cmd>`` line on success.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import List, Optional

import numpy as np

from multiverso_tpu.utils import config, log

config.define_int("nprocs", 1, "relaunch the battery as N coordinated "
                  "processes (mpirun -np N analogue)")
config.define_string("coordinator", "", "internal: coordinator address of a "
                     "relaunched battery process")
config.define_int("procid", -1, "internal: process id of a relaunched "
                  "battery process")
config.define_int("local_devices", 2, "virtual CPU devices per battery "
                  "process in -nprocs mode")
config.define_bool("cpu", False, "force the single-process battery onto a "
                   "virtual 8-device CPU mesh instead of the default "
                   "platform")
config.define_int("rows", 100_000, "num_row for the perf tests (ref default "
                  "1000000, Test/main.cpp:357)")
config.define_int("iters", 3, "outer iterations for array/matrix tests")
config.define_string("checkpoint_dir", "/tmp/mv_harness_ckpt",
                     "where the checkpoint/restore battery writes")


def _init(**kw):
    import multiverso_tpu as mv
    mv.init(**kw)
    return mv


# --------------------------------------------------------------------------- #
# battery (each mirrors one Test/main.cpp entry)
# --------------------------------------------------------------------------- #
def test_kv() -> None:
    """ref TestKV (Test/main.cpp:31-83): get-miss is 0, add accumulates;
    multi-process: allreduce merges every worker's adds."""
    mv = _init()
    kv = mv.KVTable(name="harness_kv")
    assert kv.get([0])[0] == 0, "unwritten key must read 0"
    kv.add([0], [1])
    assert kv.get([0])[0] == 1
    merged = kv.allreduce()
    assert merged[0] == mv.size(), f"key 0 = {merged[0]} != size {mv.size()}"
    log.info("kv: key0=%s over %d processes", merged[0], mv.size())
    mv.shutdown()


def test_array() -> None:
    """ref TestArray (Test/main.cpp:85-124): sync mode, delta[i]=i, three adds
    per iter; after iter i the table holds 3*(i+1)*num_workers*delta."""
    mv = _init(sync=True)
    n = 500
    t = mv.create_table(mv.ArrayTableOption(n), name="harness_array")
    mv.barrier()
    delta = np.arange(n, dtype=np.float32)
    iters = config.get_flag("iters")
    for i in range(iters):
        for _ in range(3):
            t.add(delta)
        data = t.get()
        expect = delta * 3 * (i + 1) * mv.num_workers()
        np.testing.assert_allclose(data, expect, rtol=1e-6)
    log.info("array: %d iters verified (workers=%d)", iters, mv.num_workers())
    mv.shutdown()


def test_net() -> None:
    """ref TestNet (Test/main.cpp:126-200): raw transport echo. The TPU
    transport is XLA collectives over the mesh, so the echo is a broadcast
    from rank 0 + an all_gather identity check on every device."""
    mv = _init()
    from multiverso_tpu.parallel import collectives as coll

    zoo = mv.Zoo.get()
    n_shards = int(mv.mesh().shape[zoo.shard_axis()])
    chunk = 4
    msg = np.arange(chunk * n_shards, dtype=np.float32)
    # echo: scatter the message over the mesh, gather it back unchanged
    np.testing.assert_allclose(np.asarray(coll.all_gather(msg)), msg)
    # broadcast: every shard adopts shard 0's chunk
    np.testing.assert_allclose(np.asarray(coll.broadcast(msg)), msg[:chunk])
    # allreduce: chunks sum across shards
    np.testing.assert_allclose(np.asarray(coll.all_reduce(msg)),
                               msg.reshape(n_shards, chunk).sum(axis=0))
    log.info("net: gather/broadcast/allreduce echo over %d shards OK",
             n_shards)
    mv.shutdown()


def test_ip() -> None:
    """ref TestIP → net::GetLocalIPAddress, which the reference implements
    for Windows only (src/util/net_util.cpp:70-74 is CHECK(false) on Linux).
    Topology discovery here is the JAX runtime — and works everywhere."""
    import jax
    mv = _init()
    log.info("ip/topology: process %d/%d, %d local devices, mesh %s",
             jax.process_index(), jax.process_count(),
             jax.local_device_count(), dict(mv.mesh().shape))
    assert jax.process_count() >= 1
    mv.shutdown()


def test_matrix() -> None:
    """ref TestMatrix (Test/main.cpp:203-291): dense whole-table Add/Get plus
    row-batch Add/Get on rows {0,1,3,7}; after `count` rounds the expected
    value doubles on the row-batch rows. Also asserts the sparse stale-row
    protocol (ref matrix.cpp up_to_date bits) that TestMatrix exercises via
    is_sparse tables."""
    mv = _init(sync=True)
    num_row, num_col = 11, 64
    size = num_row * num_col
    t = mv.create_table(mv.MatrixTableOption(num_row, num_col),
                        name="harness_matrix")
    mv.barrier()
    v = [0, 1, 3, 7]
    delta = (np.arange(size, dtype=np.float32) + 1).reshape(num_row, num_col)
    w = mv.num_workers()
    iters = config.get_flag("iters")
    for count in range(1, iters + 1):
        t.add(delta)
        t.add_rows(v, delta[v])
        data = t.get()
        expect = delta * count * w
        expect[v] *= 2
        np.testing.assert_allclose(data, expect, rtol=1e-6)
        rows = t.get_rows(v)
        np.testing.assert_allclose(rows, expect[v], rtol=1e-6)

    # sparse stale-row protocol on the same shape
    st = mv.SparseMatrixTable(num_row, num_col, name="harness_sparse")
    all_rows = list(range(num_row))
    first = st.get_rows_sparse(all_rows, worker_id=0)
    np.testing.assert_allclose(first, 0.0)
    assert st.stale_fraction(all_rows, worker_id=0) == 0.0, \
        "everything fresh after a full pull"
    st.add_rows([2, 5], np.ones((2, num_col), np.float32))
    frac = st.stale_fraction(all_rows, worker_id=0)
    assert 0 < frac <= 2 / num_row + 1e-6, f"stale fraction {frac}"
    got = st.get_rows_sparse(all_rows, worker_id=0)
    np.testing.assert_allclose(got[2], w)
    np.testing.assert_allclose(got[5], w)
    log.info("matrix: %d rounds + sparse staleness verified (workers=%d)",
             iters, w)
    mv.shutdown()


def test_checkpoint(restore: bool = False) -> None:
    """ref TestCheckPoint (Test/main.cpp:292-330) — and the MV_LoadTable
    resume API the reference planned but never landed (:302-316 comments) is
    real here: `restore` reloads table + updater state and training continues.
    """
    import multiverso_tpu as mv_mod
    from multiverso_tpu import checkpoint

    mv = _init()
    num_row, num_col = 11, 10
    size = num_row * num_col
    t = mv.MatrixTable(num_row, num_col, name="harness_ckpt")
    mv.barrier()
    delta = np.arange(size, dtype=np.float32).reshape(num_row, num_col)
    ckpt_dir = config.get_flag("checkpoint_dir")
    w = mv.num_workers()
    if not restore:
        for _ in range(50):
            t.add(delta)
        checkpoint.save(ckpt_dir, tag="harness")
        np.testing.assert_allclose(t.get(), delta * 50 * w, rtol=1e-6)
        log.info("checkpoint: 50 adds stored to %s", ckpt_dir)
    else:
        n = checkpoint.restore(ckpt_dir, tag="harness")
        assert n >= 1, "no tables restored"
        np.testing.assert_allclose(t.get(), delta * 50 * w, rtol=1e-6)
        t.add(delta)  # resume: training continues on restored state
        np.testing.assert_allclose(t.get(), delta * (50 * w + w), rtol=1e-6)
        log.info("restore: state verified, training resumed")
    mv.shutdown()


def test_allreduce() -> None:
    """ref TestAllreduce (Test/main.cpp:331-339): -ma mode MV_Aggregate."""
    prev_ma = config.get_flag("ma")
    config.set_flag("ma", True)
    try:
        mv = _init()
        a = np.ones(1, dtype=np.float32)
        mv.aggregate(a)
        assert a[0] == mv.size(), f"aggregate: {a[0]} != {mv.size()}"
        log.info("allreduce: a = %s (size %d)", a[0], mv.size())
        mv.shutdown()
    finally:
        config.set_flag("ma", prev_ma)  # don't poison later battery entries


def _perf(sparse: bool) -> None:
    """ref TestmatrixPerformance (Test/main.cpp:340-452): get-all, add a
    growing fraction of rows, get-all again, verify, Dashboard dump."""
    from multiverso_tpu.utils.dashboard import Dashboard

    mv = _init()
    num_row, num_col = config.get_flag("rows"), 50
    wid, wnum = mv.worker_id(), mv.num_workers()
    delta = np.arange(num_row * num_col,
                      dtype=np.float32).reshape(num_row, num_col)
    for percent in range(0, 10, 3):
        cls = mv.SparseMatrixTable if sparse else mv.MatrixTable
        t = cls(num_row, num_col, name=f"perf_{percent}")
        mv.barrier()

        t0 = time.perf_counter()
        data = (t.get_rows_sparse(range(num_row), worker_id=wid)
                if sparse else t.get())
        log.info("%.3fs: get all rows first time (worker %d)",
                 time.perf_counter() - t0, wid)

        # ref splits rows across workers (i % worker_num == worker_id);
        # collective add_rows needs identical id sets per process, so every
        # worker pushes the full fraction and the sum scales by num_workers
        rows = [i for i in range(num_row) if i % 10 <= percent]
        if rows:
            t.add_rows(rows, delta[rows])
        mv.barrier()

        t0 = time.perf_counter()
        data = (t.get_rows_sparse(range(num_row), worker_id=wid)
                if sparse else t.get())
        log.info("%.3fs: get all rows after adding %d0%% (worker %d)",
                 time.perf_counter() - t0, percent + 1, wid)

        touched = np.zeros(num_row, bool)
        touched[rows] = True
        np.testing.assert_allclose(data[touched], delta[touched] * wnum,
                                   rtol=1e-6)
        np.testing.assert_allclose(data[~touched], 0.0)
    Dashboard.display()
    mv.shutdown()


def test_async() -> None:
    """Uncoordinated async-PS plane (no reference analogue in Test/main.cpp
    — the reference could only exercise async through full apps; here the
    plane is its own battery entry): per-worker disjoint row sets at
    per-worker rates over PSService shards, plus hash-sharded KV."""
    mv = _init()
    rank, world = mv.rank(), mv.size()
    t = mv.AsyncMatrixTable(8 * max(world, 1), 4, name="harness_async")
    kv = mv.AsyncKVTable(name="harness_async_kv")
    my_rows = np.arange(8) * max(world, 1) + rank
    for i in range(rank + 1):
        t.add_rows(my_rows, np.ones((8, 4), np.float32))
        kv.add([rank], [1.0])
    t.flush()
    mv.barrier()   # determinism fence for the asserts, not the plane
    got = t.get_rows(np.arange(8 * max(world, 1)))
    total = float(got.sum())
    expect = sum((r + 1) for r in range(world)) * 8 * 4
    assert total == expect, (total, expect)
    counts = kv.get()
    assert counts == {r: float(r + 1) for r in range(world)}, counts
    log.info("async: %d workers, row mass %.0f, kv %s", world, total, counts)
    mv.shutdown()


def test_ftrl_sparse() -> None:
    """Hash-sharded sparse keys + FTRL z/n on the uncoordinated plane (ref
    Applications/LogisticRegression/src/util/{sparse_table,
    ftrl_sparse_table}.h; no Test/main.cpp analogue — the reference never
    exercised its sparse tables outside the LR app)."""
    mv = _init()
    rank, world = mv.rank(), mv.size()
    from multiverso_tpu.ps.tables import AsyncSparseKVTable
    t = AsyncSparseKVTable(4, updater="ftrl", name="harness_ftrl")
    keys = np.array([7, 1_000_003, 1_000 + rank])  # shared + per-rank keys
    for _ in range(10):
        t.add_rows(keys, np.full((3, 4), 0.5, np.float32))
    t.flush()
    mv.barrier()   # determinism fence for the asserts, not the plane
    w = t.get_rows([7, 1_000_003])
    # steady +g gradients push the FTRL weight negative once |z| > lambda1
    assert np.all(w < 0) and np.all(np.isfinite(w)), w
    per_rank = t.get_rows([1_000 + r for r in range(world)])
    assert np.all(per_rank < 0), per_rank
    fresh = t.get_rows([555])
    np.testing.assert_allclose(fresh, 0.0)   # untouched key = empty state
    log.info("ftrl_sparse: %d workers, shared w[0]=%.4f", world,
             float(w[0, 0]))
    mv.shutdown()


def test_readers() -> None:
    """Weighted + binary-sparse reader variants end-to-end (ref
    reader.h:96-114 WeightedSampleReader, :118-146 BSparseSampleReader):
    every rank writes its own weighted-text and binary shard of the same
    synthetic samples, reads both back through SampleReader, asserts the
    parsed batches agree bit-for-bit, and pushes its sample mass to a
    shared async KV table so the asserts span ranks."""
    import tempfile

    from multiverso_tpu.io.sample_reader import (SampleReader,
                                                 write_bsparse_sample)
    mv = _init()
    rank, world = mv.rank(), mv.size()
    rng = np.random.default_rng(100 + rank)
    dim, n = 32, 12
    samples = [(int(rng.integers(0, 2)),
                np.unique(rng.integers(0, dim, 5)),
                float(rng.uniform(0.5, 2.0)))
               for _ in range(n)]
    with tempfile.TemporaryDirectory(prefix="mv_readers_") as d:
        wpath, bpath = f"{d}/w_{rank}.txt", f"{d}/b_{rank}.bin"
        with open(wpath, "w") as f:
            for label, keys, w in samples:
                f.write(f"{label}:{w} "
                        + " ".join(f"{k}:1.0" for k in keys) + "\n")
        with open(bpath, "wb") as f:
            for label, keys, w in samples:
                write_bsparse_sample(f, label, keys, w)
        wbatches = list(SampleReader(wpath, dim, 4, fmt="weight"))
        bbatches = list(SampleReader(bpath, dim, 4, fmt="bsparse"))
    assert len(wbatches) == len(bbatches) == 3, len(wbatches)
    mass = 0.0
    for (wx, wy, wk), (bx, by, bk) in zip(wbatches, bbatches):
        np.testing.assert_allclose(wx, bx)     # weight folded into values
        np.testing.assert_array_equal(wy, by)
        np.testing.assert_array_equal(wk, bk)  # same active-key sets
        mass += float(wx.sum())
    kv = mv.AsyncKVTable(name="harness_readers")
    kv.add([rank], [round(mass, 3)])
    mv.barrier()
    counts = kv.get()
    assert set(counts) == set(range(world)) and all(
        v > 0 for v in counts.values()), counts
    log.info("readers: %d ranks, weighted==bsparse, mass %s", world, counts)
    mv.shutdown()


def test_dense_perf() -> None:
    _perf(sparse=False)


def test_sparse_perf() -> None:
    _perf(sparse=True)


_TESTS = {
    "kv": test_kv,
    "array": test_array,
    "net": test_net,
    "ip": test_ip,
    "matrix": test_matrix,
    "checkpoint": lambda: test_checkpoint(False),
    "restore": lambda: test_checkpoint(True),
    "allreduce": test_allreduce,
    "async": test_async,
    "ftrl_sparse": test_ftrl_sparse,
    "readers": test_readers,
    "dense_perf": test_dense_perf,
    "sparse_perf": test_sparse_perf,
}
# the Docker CI battery order (deploy/docker/Dockerfile) + the async plane
_ALL = ["kv", "array", "net", "ip", "matrix", "checkpoint", "restore",
        "allreduce", "async", "ftrl_sparse", "readers"]


def _spawn_cluster(cmd: str, nprocs: int, extra: List[str]) -> int:
    """Relaunch this harness as N coordinated processes (mpirun analogue).

    One process holds a chip, so an N-process world is a CPU world: this
    parent never touches JAX, and every child is pinned to the CPU by the
    environment here and by ``force_cpu_mesh`` before its first device
    use."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "multiverso_tpu.harness", cmd,
             f"-coordinator={coordinator}", f"-nprocs={nprocs}",
             f"-procid={pid}", *extra],
            env=env)
        for pid in range(nprocs)
    ]
    rc = 0
    for pid, p in enumerate(procs):
        code = p.wait()
        if code == 77 and rc == 0:
            rc = 77  # child couldn't bring up jax.distributed: skip, not fail
        elif code not in (0, 77):
            log.error("battery process %d failed (rc=%d)", pid, code)
            rc = 1
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # accept the natural bare form of the boolean flag
    argv = ["-cpu=true" if a == "-cpu" else a for a in argv]
    cmds = [a for a in argv if not a.startswith("-")]
    flags = [a for a in argv if a.startswith("-")]

    def maybe_force_cpu() -> None:
        if config.get_flag("cpu"):
            from multiverso_tpu.utils.platform import force_cpu_mesh
            if not force_cpu_mesh(8):
                log.error("-cpu requested but a JAX backend is already "
                          "initialized; battery would run on the default "
                          "platform")
                raise SystemExit(3)

    if not cmds:
        # ref: argc==1 -> bare MV_Init/MV_ShutDown smoke (Test/main.cpp:500)
        config.parse_cmd_flags(["prog", *flags])
        maybe_force_cpu()
        mv = _init()
        mv.shutdown()
        print("HARNESS PASS init")
        return 0
    cmd = cmds[0]
    config.parse_cmd_flags(["prog", *flags])

    nprocs = config.get_flag("nprocs")
    procid = config.get_flag("procid")
    if nprocs > 1 and procid < 0:
        names = _ALL if cmd == "all" else cmds
        for name in names:
            rc = _spawn_cluster(name, nprocs, [f for f in flags
                                               if not f.startswith("-nprocs")])
            if rc == 77:
                print(f"HARNESS SKIP {name} (jax.distributed unavailable)")
                return 77
            if rc:
                return rc
            print(f"HARNESS PASS {name} (nprocs={nprocs})")
        return 0

    if procid >= 0:  # child of _spawn_cluster
        import jax

        from multiverso_tpu.utils.platform import force_cpu_mesh
        force_cpu_mesh(config.get_flag("local_devices"))
        try:
            jax.distributed.initialize(
                coordinator_address=config.get_flag("coordinator"),
                num_processes=nprocs, process_id=procid)
        except Exception as e:  # environment without jax.distributed
            log.error("jax.distributed unavailable: %s", e)
            return 77  # conventional skip code, consumed by _spawn_cluster

    if procid < 0:
        maybe_force_cpu()

    names = _ALL if cmd == "all" else cmds
    for name in names:
        if name not in _TESTS:
            log.error("unknown battery test %r (have: %s)", name,
                      " ".join(sorted(_TESTS)))
            return 2
        _TESTS[name]()
        if procid <= 0:
            print(f"HARNESS PASS {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
