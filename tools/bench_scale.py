"""Mesh scale-curve harness (ISSUE 12; measurement methodology and the
plane under test reworked by ISSUE 15): the async-PS workload at
1->2->4->8 server shards on a host-platform device mesh, judged by the
device-plane observability layer it ships with.

Each shard count ``n`` runs in its OWN subprocess ("--point" mode): an
n-rank in-process PS world with the ISSUE-15 mesh data plane ARMED
(``ps_fanout`` process-coalesced routing + multi-owner super-frames;
``ps_spmd_stack`` stacked SPMD apply/gather, exercised and
parity-gated by :func:`_parity_stage`) plus an n-device mesh slice of
the 8-virtual-device host platform. Process-per-point is load-bearing,
not convenience: two shard counts' collective executables coexisting
in one XLA CPU client raced the process-global rendezvous (observed
live: interleaved all_reduce participants wedged both worlds) — and it
also gives each point a process-fresh devstats/span-ring reading.

**Constant offered load (ISSUE 15).** Every point drives the SAME
``M = min(cpu_count, 4)`` worker threads — the textbook scaling-curve
design: hold the load generators fixed, scale the resource under test.
The PR-12 harness scaled workers WITH shards (n workers at point n),
which conflated client-side thread-convoy costs (8 GIL-rotating
threads on a 2-core box) with the server plane's sharding behavior —
most of its E_8 = 0.02 was the client, not the shards. With M fixed,
E_n answers the production question directly: does adding server
shards relieve the serialization a loaded single shard exhibits? (It
does — a 1-shard server under M concurrent workers convoys on its one
lock domain, which is precisely the bottleneck Li et al.'s sharded-KV
design removes.) Ops are production-shaped (2048x128 row batches — a
~1 MB delta/pull per op) so the instrument measures the data plane,
not per-call python fixed costs.

Per point the child drives the M workers through a step-profiled
train-shaped loop (prepare / push / ps_wait over the sharded table),
then measures the model-average ``parallel/collectives.all_reduce``
QUIESCED (PS plane idle — host-platform virtual devices share one
in-process client whose collective executions must not interleave with
concurrent jit work). Recorded per point:

* **T_n** — aggregate row throughput; the parent computes
  **E_n = T_n / (n * T_1)** in-run via :func:`efficiency_curve`
  (pure; oracle-tested in tests/test_devstats.py).
* per-shard **skew** from the PR-6 aggregator's merged record;
* **stall fraction** from the step spans (``trace.step_summary``);
* per-direction **transfer bytes**, per-op **collective** tallies, and
  per-mesh-shape **compile** cost from ``telemetry/devstats.py`` —
  each compile keyed to the ``{'mv': n}`` configuration that fired it.

**Compile-hygiene gate:** every point's collective dryrun compiles
inside ``devstats.capture_hygiene``; the run FAILS (nonzero exit — a
failed sub-bench, not a degraded record) if any SPMD remat /
sharding-fallback warning classifies, or if any shard count escaped
the check. The merged report rides the RESULT for ``extra.scale`` and
dumps to ``compile-hygiene-rank<r>.json`` for ``mvprof`` when a
metrics dir is configured.

Invoked as: python tools/bench_scale.py [seconds] [shards_csv] [rows] [dim]
Prints "RESULT <json>".
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

DEFAULT_SHARDS = (1, 2, 4, 8)
# constant offered load at every point (see module docstring): the
# box's cores are its useful load generators, capped so a many-core
# host doesn't turn the curve into a client-thread study
DEFAULT_ROWS = 40_000
DEFAULT_DIM = 128
BATCH_ROWS = 2048


def worker_count() -> int:
    return max(2, min(os.cpu_count() or 2, 4))


def efficiency_curve(throughput_by_n):
    """T_n -> E_n = T_n / (n * T_1): 1.0 = perfect linear scaling.
    Pure (the E_n oracle test drives it directly). Returns
    ``{"efficiency": {n: E_n}, "efficiency_min": min E_n over n>1}`` —
    the min is the run_bench-tracked regression scalar (higher is
    better; the weakest point of the curve is the one that regressed).
    efficiency_min is None when no baseline point (n=1) exists."""
    ns = sorted(int(n) for n in throughput_by_n)
    t1 = float(throughput_by_n.get(1, throughput_by_n.get("1", 0)) or 0)
    if t1 <= 0 or not ns:
        return {"efficiency": {}, "efficiency_min": None}
    eff = {}
    for n in ns:
        t_n = float(throughput_by_n.get(n, throughput_by_n.get(str(n), 0))
                    or 0)
        eff[n] = round(t_n / (n * t1), 4)
    tail = [e for n, e in eff.items() if n > 1]
    return {"efficiency": eff,
            "efficiency_min": round(min(tail), 4) if tail else None}


def _parity_stage(n: int, dim: int, devstats) -> bool:
    """Drive a deterministic add/get sequence over an n-shard
    device-backed (adagrad) table — fan-out super-frames + the
    mesh-stacked SPMD apply/gather — and bit-compare the final table
    against a 1-shard oracle world running the CLASSIC path. Returns
    True only on an exact match; raises on plumbing failures."""
    import numpy as np

    from multiverso_tpu.ps.service import (FileRendezvous, PSContext,
                                           PSService)
    from multiverso_tpu.ps.tables import AsyncMatrixTable
    from multiverso_tpu.utils import config

    prows = 2048

    def _drive(tabs, nshards):
        rng = np.random.default_rng(99)
        for step in range(16):
            ids = np.sort(rng.choice(prows, size=96, replace=False))
            deltas = rng.normal(size=(96, dim)).astype(np.float32)
            t = tabs[step % nshards]
            if step == 0 and nshards > 1:
                sh = tabs[0]._shard
                plane = getattr(sh, "_plane", None)
                mesh = plane.mesh if plane is not None else None
                # the stacked program's first compile happens HERE:
                # capture it under the hygiene gate, keyed to the
                # plane's mesh shape
                with devstats.capture_hygiene("scale.spmd_apply",
                                              mesh=mesh):
                    t.add_rows(ids, deltas)
            else:
                t.add_rows(ids, deltas)
            t.get_rows(ids)   # grouped SPMD gather on the stacked path
        return tabs[0].get_rows(np.arange(prows))

    # the parity world rendezvouses in its OWN directory — the measured
    # world's rank addr files (and its colocation registry key) must
    # not collide with this stage's
    with tempfile.TemporaryDirectory(prefix="mv_scale_par_") as prdv:
        ctxs = [PSContext(r, n, PSService(r, n, FileRendezvous(prdv)))
                for r in range(n)]
        tabs = [AsyncMatrixTable(prows, dim, name="scale_par",
                                 updater="adagrad", ctx=ctxs[r])
                for r in range(n)]
        if n > 1 and getattr(tabs[0]._shard, "_plane", None) is None:
            raise AssertionError(
                "parity stage: the adagrad table did not group into a "
                "mesh-stacked plane (ps_spmd_stack armed?)")
        got = _drive(tabs, n)
        for c in ctxs:
            c.close()
    # 1-shard oracle world: classic storage, classic dispatch
    config.set_flag("ps_fanout", False)
    config.set_flag("ps_spmd_stack", False)
    try:
        with tempfile.TemporaryDirectory(prefix="mv_scale_orc_") as ordv:
            ctx = PSContext(0, 1, PSService(0, 1, FileRendezvous(ordv)))
            t1 = AsyncMatrixTable(prows, dim, name="scale_par_oracle",
                                  updater="adagrad", ctx=ctx)
            want = _drive([t1], 1)
            ctx.close()
    finally:
        config.set_flag("ps_fanout", True)
        config.set_flag("ps_spmd_stack", True)
    return bool(np.array_equal(got, want))


def run_point(n: int, seconds: float, rows: int, dim: int):
    """One shard count, measured in THIS (fresh) process. Returns the
    point record incl. this process's devstats snapshot and hygiene
    report — the parent merges across points."""
    from multiverso_tpu.utils.platform import force_cpu_mesh
    force_cpu_mesh(8)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from multiverso_tpu.parallel import collectives
    from multiverso_tpu.ps.service import (FileRendezvous, PSContext,
                                           PSService)
    from multiverso_tpu.ps.tables import AsyncMatrixTable
    from multiverso_tpu.telemetry import aggregator
    from multiverso_tpu.telemetry import devstats
    from multiverso_tpu.telemetry import trace
    from multiverso_tpu.utils import config

    devices = jax.devices()
    if len(devices) < n:
        raise AssertionError(
            f"host platform exposes {len(devices)} devices, need {n}: "
            "xla_force_host_platform_device_count did not take "
            "(backend initialized early?)")
    config.set_flag("ps_timeout", 120.0)
    # Local-device sharding OFF for the harness table: above
    # ps_local_shard_min_mb a shard device-shards its row range over
    # ALL local devices, making every apply an 8-participant collective
    # program — and two shards applying CONCURRENTLY race XLA-CPU's
    # process-global rendezvous and wedge the world (found by this
    # harness's own flightrec/devstats instrumentation; reproduced at
    # rows*dim*4 > 1MB, never below). The curve measures the PLANE's
    # shard scaling; single-shard intra-op sharding is a separate axis.
    config.set_flag("ps_local_shard_min_mb", 1e9)
    # the mesh data plane under measurement (ISSUE 15, ps/spmd.py):
    # process-coalesced fan-out routing + multi-owner super-frames for
    # the measured table, and the mesh-stacked SPMD apply/gather for
    # the parity stage's device-backed (adagrad) table — its grouped
    # dispatches serialize on the plane lock, so the XLA-CPU
    # rendezvous hazard above cannot recur (one multi-device program
    # in flight at a time)
    config.set_flag("ps_fanout", True)
    config.set_flag("ps_spmd_stack", True)
    # sketch sized to the workload's key set (the PR-8 bench rule): the
    # workers' strided batches touch BATCH_ROWS * M distinct hot rows,
    # and an UNDERSIZED Space-Saving sketch turns every observe into a
    # heap eviction — a worst-case pure-python tax the curve is not
    # here to measure (real deployments size the sketch to their hot
    # set)
    config.set_flag("hotkeys_capacity", 16384)
    # acceptance config: skew from the aggregator, stall fraction from
    # the step spans, device costs from devstats — the whole
    # instrument live while the point is measured
    config.set_flag("stats_poll_interval_s", 1.0)
    # a step here is one loop iteration of a worker thread: per request,
    # so its spans are fine ones and follow trace_ids, as the client's
    # send-to-reply spans beside them do
    config.set_flag("trace_ids", True)
    trace.configure(0)
    devstats.configure(0)

    batch = BATCH_ROWS
    workers = worker_count()
    rng = np.random.default_rng(12)
    vals = rng.normal(size=(batch, dim)).astype(np.float32)
    mesh = Mesh(np.asarray(devices[:n]), ("mv",))
    # model-average payload: [n * chunk] sharded over the axis ->
    # replicated [chunk] sum (the reference Allreduce shape); the
    # upload is a real h2d transfer, counted at the chokepoint
    host_delta = rng.normal(size=(n * 2048,)).astype(np.float32)
    devstats.note_transfer(host_delta.nbytes, "h2d")
    delta = jnp.asarray(host_delta)
    # compile-hygiene gate: the dryrun compile for THIS mesh shape runs
    # inside a capture scope; SPMD remat / sharding-fallback warnings
    # become machine-readable findings the parent fails on
    with devstats.capture_hygiene("scale.all_reduce", mesh=mesh):
        collectives.all_reduce(delta, mesh=mesh).block_until_ready()

    with tempfile.TemporaryDirectory(prefix=f"mv_scale_{n}_") as rdv:
        ctxs = [PSContext(r, n, PSService(r, n, FileRendezvous(rdv)))
                for r in range(n)]
        tables = [AsyncMatrixTable(rows, dim, name="scale",
                                   ctx=ctxs[r]) for r in range(n)]
        # WARMUP (ISSUE 15 satellite): a short loop-shaped pass per
        # worker slot — strided route, both shard programs, the fan-out
        # super-frame path, the async-add/wait pipeline AND one
        # step span each — so point 1's first-compile +
        # first-dispatch cost stops polluting T_1 (a depressed T_1
        # inflated every E_n of the curve)
        for w in range(workers):
            t = tables[w % n]
            ids = (np.arange(batch) * (rows // batch) + w) % rows
            mids = []
            for k in range(4):
                mids.append(t.add_rows_async(ids, vals))
                t.get_rows(ids)
            with trace.span(f"scale.np{n}", step=1):
                with trace.span("scale.push", phase="push"):
                    mids.append(t.add_rows_async(ids, vals))
                with trace.span("scale.pull", phase="ps_wait"):
                    t.get_rows(ids)
            for m in mids:
                t.wait(m)

        # SPMD-apply parity stage (ISSUE 15 acceptance): a
        # device-backed (adagrad) parity table across ALL n shards —
        # grouped into ONE mesh-stacked plane by ps_spmd_stack — driven
        # with a deterministic op sequence through the fan-out
        # super-frame path, asserted BIT-IDENTICAL to a 1-shard oracle
        # in a separate world. The first add (the stacked program's
        # compile) runs inside a hygiene capture scope keyed to the
        # plane's mesh shape.
        parity_ok = _parity_stage(n, dim, devstats)

        stop = time.monotonic() + seconds
        counts = [0] * workers

        def worker(w):
            # constant offered load: M workers at EVERY point (module
            # docstring) — each drives a table view round-robin and a
            # strided id batch spanning every shard
            t = tables[w % n]
            ids = (np.arange(batch) * (rows // batch) + w) % rows
            mids = []
            while time.monotonic() < stop:
                with trace.span(f"scale.np{n}", step=1):
                    with trace.span("scale.prepare", phase="prepare"):
                        v = vals * (1.0 + 1e-4 * counts[w])
                    with trace.span("scale.push", phase="push"):
                        mids.append(t.add_rows_async(ids, v))
                        if len(mids) >= 4:
                            with trace.span("scale.wait", phase="ps_wait"):
                                t.wait(mids.pop(0))
                    with trace.span("scale.pull", phase="ps_wait"):
                        t.get_rows(ids)
                counts[w] += 2
            for m in mids:
                t.wait(m)

        t0 = time.monotonic()
        threads = [threading.Thread(target=worker, args=(w,),
                                    name=f"scale-w{w}")
                   for w in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        dt = time.monotonic() - t0

        # model-average collective cost at this shard count, measured
        # QUIESCED (workers joined, PS plane idle — see module
        # docstring; on real chips the phases overlap, here the
        # instrument separates them and attributes each honestly)
        coll_iters = 16
        c0 = time.monotonic()
        for _ in range(coll_iters):
            collectives.all_reduce(delta, mesh=mesh).block_until_ready()
        coll_ms = (time.monotonic() - c0) * 1e3 / coll_iters

        agg = aggregator.global_aggregator()
        skew = None
        straggler = None
        if agg is not None:
            rec = agg.poll_once()
            tbl = rec.get("tables", {}).get("scale") or {}
            skew = tbl.get("skew")
            # per-point straggler attribution (telemetry/slo.py): the
            # slowest rank at this shard count, named with its dominant
            # component (compute/wire/stall) — the scale curve's E_n
            # drop gets a who, not just a how-much
            from multiverso_tpu.telemetry import slo as _slo
            straggler = _slo.straggler(rec)
        summary = trace.step_summary() or {}
        snap = devstats.stats_snapshot() or {}
        compiles = (snap.get("compiles_by_mesh") or {}).get(
            devstats.mesh_label(mesh)) or {}
        point = {
            "n": n,
            "rows_per_s": round(sum(counts) * batch / dt),
            "ops": sum(counts),
            "workers": workers,
            "batch_rows": batch,
            "skew": skew,
            "straggler": straggler,
            "stall_fraction": summary.get("stall_fraction"),
            "steps": summary.get("steps"),
            # zero steady-state recompiles is an ACCEPTANCE gate: the
            # warmed-up measured loop (and the stacked SPMD programs)
            # must never retrace past the warmup pass
            "steady_recompiles": summary.get("steady_recompiles", 0),
            # bit-parity of the mesh data plane (fan-out super-frames +
            # stacked SPMD apply/gather) vs the 1-shard classic oracle,
            # asserted in-run by the parent
            "parity_bit_for_bit": parity_ok,
            "all_reduce_ms": round(coll_ms, 3),
            "all_reduce_bytes": int(delta.nbytes),
            "compiles": compiles.get("compiles"),
            "compile_s": compiles.get("compile_s"),
            "devices": snap,
            "hygiene": devstats.hygiene_report(),
        }
        for c in ctxs:
            c.close()
    return point


def _merge_devices(points):
    """Sum the per-point devstats snapshots into one RESULT-level view
    (each point ran in its own process, so plain summation is exact)."""
    transfers = {}
    colls = {}
    compiles = {}
    for p in points:
        snap = p.get("devices") or {}
        for d, g in (snap.get("transfers") or {}).items():
            t = transfers.setdefault(d, {"ops": 0, "bytes": 0})
            t["ops"] += g.get("ops", 0)
            t["bytes"] += g.get("bytes", 0)
        for op, c in (snap.get("collectives") or {}).items():
            t = colls.setdefault(op, {"calls": 0, "bytes": 0})
            t["calls"] += c.get("calls", 0)
            t["bytes"] += c.get("bytes", 0)
        for label, c in (snap.get("compiles_by_mesh") or {}).items():
            t = compiles.setdefault(label,
                                    {"compiles": 0, "compile_s": 0.0})
            t["compiles"] += c.get("compiles", 0)
            t["compile_s"] = round(t["compile_s"]
                                   + c.get("compile_s", 0.0), 3)
    return transfers, colls, compiles


def main():
    if sys.argv[1:2] == ["--point"]:
        n, seconds, rows, dim = (int(sys.argv[2]), float(sys.argv[3]),
                                 int(sys.argv[4]), int(sys.argv[5]))
        print("POINT " + json.dumps(run_point(n, seconds, rows, dim)),
              flush=True)
        return

    seconds = float(sys.argv[1]) if len(sys.argv) > 1 else 3.0
    shards = (tuple(int(s) for s in sys.argv[2].split(","))
              if len(sys.argv) > 2 else DEFAULT_SHARDS)
    rows = int(sys.argv[3]) if len(sys.argv) > 3 else DEFAULT_ROWS
    dim = int(sys.argv[4]) if len(sys.argv) > 4 else DEFAULT_DIM

    points = []
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    for n in shards:
        print(f"bench_scale: shard point n={n}", file=sys.stderr,
              flush=True)
        # per-point budget well above the measured ~60-90 s/point; the
        # parent's caller (bench.bench_scale_curve) budgets MORE than
        # the sum of these, so a wedged point dies HERE with its
        # structured "scale point n=N" error, never as a generic
        # whole-worker timeout that hides which shard count hung
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--point",
             str(n), str(seconds), str(rows), str(dim)],
            capture_output=True, text=True, timeout=120 + 30 * n,
            env=env, cwd=_REPO)
        if out.returncode != 0:
            raise RuntimeError(
                f"scale point n={n} rc={out.returncode}: "
                f"{out.stderr[-400:]}")
        point = None
        for line in out.stdout.splitlines():
            if line.startswith("POINT "):
                point = json.loads(line[len("POINT "):])
        if point is None:
            raise RuntimeError(f"scale point n={n} produced no POINT "
                               f"line: {out.stderr[-400:]}")
        points.append(point)

    # the gate: a dirty compile is a FAILED run, and so is a point that
    # never entered a capture scope (an unchecked shape is not clean,
    # it is unmeasured — the MSG_SNAPSHOT lesson)
    findings = []
    checked = []
    for p in points:
        rep = p.get("hygiene") or {}
        if not rep.get("checked"):
            raise AssertionError(
                f"compile-hygiene gate: shard point n={p['n']} never "
                "entered a capture_hygiene scope — the report cannot "
                "vouch for it")
        checked.extend(rep["checked"])
        findings.extend(rep.get("findings") or [])
        # ISSUE 15 acceptance gates, per point: the mesh data plane's
        # bit-parity vs the 1-shard oracle, and zero steady-state
        # recompiles on the warmed measured loop
        if not p.get("parity_bit_for_bit"):
            raise AssertionError(
                f"parity gate: shard point n={p['n']} diverged from "
                "the 1-shard oracle (fan-out / SPMD apply broke "
                "bit-parity)")
        if p.get("steady_recompiles"):
            raise AssertionError(
                f"recompile gate: shard point n={p['n']} recompiled "
                f"{p['steady_recompiles']}x in steady state")
    if findings:
        raise AssertionError(
            "compile-hygiene gate: SPMD findings on the shipped "
            f"workload: {findings[:4]}")

    curve = {p["n"]: {k: v for k, v in p.items()
                      if k not in ("devices", "hygiene", "n")}
             for p in points}
    eff = efficiency_curve({n: c["rows_per_s"]
                            for n, c in curve.items()})
    transfers, colls, compiles = _merge_devices(points)

    # machine-readable report for tools/mvprof.py (beside the
    # trace files when a metrics dir is configured)
    from multiverso_tpu.utils import config
    mdir = config.get_flag("metrics_dir")
    if mdir:
        report = {"clean": not findings, "checked": checked,
                  "findings": findings, "rank": 0}
        os.makedirs(mdir, exist_ok=True)
        path = os.path.join(mdir, "compile-hygiene-rank0.json")
        with open(path + ".tmp", "w") as f:
            json.dump(report, f, indent=1)
        os.replace(path + ".tmp", path)

    print("RESULT " + json.dumps({
        "shards": list(shards),
        "seconds_per_point": seconds,
        "batch_rows": BATCH_ROWS, "dim": dim,
        "workers": worker_count(),
        "curve": {str(n): c for n, c in curve.items()},
        "efficiency": {str(n): e for n, e in
                       eff["efficiency"].items()},
        "efficiency_min": eff["efficiency_min"],
        # per-shard-count efficiency as first-class scalars, so the
        # BENCH_HISTORY headline (and run_bench's higher-is-better
        # flags) track each point of the curve, not just its min
        "e2": eff["efficiency"].get(2),
        "e4": eff["efficiency"].get(4),
        "e8": eff["efficiency"].get(8),
        "t1_rows_per_s": (curve.get(1) or {}).get("rows_per_s"),
        "parity_bit_for_bit": all(p.get("parity_bit_for_bit")
                                  for p in points),
        "steady_recompiles": sum(int(p.get("steady_recompiles") or 0)
                                 for p in points),
        "fanout": True, "spmd_stack": True,
        "hygiene_clean": not findings,
        "hygiene_checked": len(checked),
        "transfers": transfers,
        "collectives": colls,
        "compiles_by_mesh": compiles,
    }), flush=True)


if __name__ == "__main__":
    main()
