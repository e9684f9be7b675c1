"""The cell on the sharded table path, and the readers of its layer:
``we-fused-x4`` through ``run.py --cpu-tiny`` on four CPU devices (the
child gets ``--xla_force_host_platform_device_count=4``), ``layers/mesh``
on a synthetic reduction and a synthetic ring, and ``owner_rows``."""

import json
import os

import numpy as np
import pytest

from conftest import ROOT, run_cell

from benchmark.layers import mesh
from benchmark.reference.sharding import owner_rows

CELL = "we-fused-x4"
NEW = {"mesh.collective_share.we", "mesh.owner_skew.we"}


@pytest.fixture
def four_cpu_devices(monkeypatch):
    flags = os.environ.get("XLA_FLAGS", "")
    monkeypatch.setenv(
        "XLA_FLAGS",
        (flags + " --xla_force_host_platform_device_count=4").strip())


def test_the_cell_is_correct_on_four_shards(four_cpu_devices):
    result, lines = run_cell(ROOT, CELL, trace=0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) == {"words_per_s", "setup_s"}
    detail = json.loads(lines[-2])["detail"]
    assert detail["compiles_in_window"] == 0
    check, facts = detail["check"], detail["facts"]
    assert check["shards"] == 4 and len(check["in_digests"]) == 4
    assert check["in_others_unchanged"] and check["out_others_unchanged"]
    assert check["pool_as_foretold"] and check["one_batch"]
    # ids are frequency ranks: the first shard owns most update rows
    by_shard = facts["update_rows_by_shard"]
    assert len(by_shard) == 4 and by_shard[0] == max(by_shard)
    per_batch = 2 * facts["batch"] + facts["pool"]
    batches = facts["calls"] * (facts["pairs_per_call"] // facts["batch"])
    assert sum(by_shard) == batches * per_batch
    assert facts["allreduce_bytes"] == batches * per_batch * 300 * 4


def test_the_traced_line_reports_the_mesh_layer(four_cpu_devices):
    result, _ = run_cell(ROOT, CELL, trace=1)
    assert result["correct"] is True
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    ours = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", ())}
    assert NEW <= ours and set(result["metrics"]) <= ours
    # the CPU has no device trace: the share of the collectives is read
    # from device operations and stays out; the skew is the program's count
    assert "mesh.collective_share.we" not in result["metrics"]
    skew = result["metrics"]["mesh.owner_skew.we"]
    assert skew["unit"] == "%" and 25.0 < skew["value"] <= 100.0
    assert "prog.fused_host_ms.we" in result["metrics"]


def test_collective_share_is_the_collectives_among_the_top_ops():
    trace = {"busy_s": 20.0, "device_ops": [
        ["fusion.74 f32[3000001,300]", 8.0],
        ["all-reduce.7 bf16[8192,300]", 1.5],
        ["all-gather-start.2 f32[64]", 0.25],
        ["collective-permute.1", 0.25],
        ["fusion.all-reduce-looking f32[8]", 3.0],     # not a collective
        ["reduce-scatter.3 f32[8,8]", 0.5],
        ["all-to-all.9", 0.5]]}
    assert mesh.collective_share(trace) == pytest.approx(100 * 3.0 / 20.0)
    assert mesh.read("mesh.collective_share.we", {"trace": trace}) == \
        pytest.approx(15.0)
    # none among the ten: a floor of 0, not a missing reading
    assert mesh.collective_share(
        {"busy_s": 2.0, "device_ops": [["fusion.1 f32[4]", 2.0]]}) == 0.0
    # no device trace (a CPU run): nothing to read
    assert mesh.collective_share({"busy_s": 0.0, "device_ops": []}) is None
    assert mesh.read("mesh.unknown.we", {"trace": trace}) is None


def _fused(rows, prof=True, name="we.fused"):
    return {"name": name, "prof": prof, "ts": 0.0, "dur": 1.0,
            "args": {"update_rows_by_shard": rows} if rows else {}}


def test_owner_skew_is_the_busiest_shards_share_of_the_window():
    ring = [_fused([900, 50, 30, 20], prof=False),       # set-up: left out
            _fused([940, 30, 20, 10]), _fused([940, 30, 10, 20]),
            _fused([1, 1, 1, 1], name="we.blocks"),      # another span
            _fused(None)]                                # no count on it
    assert mesh.owner_skew(ring) == pytest.approx(94.0)
    assert mesh.owner_skew([_fused([5, 5, 5, 5])]) == pytest.approx(25.0)
    assert mesh.owner_skew([_fused([7])]) == pytest.approx(100.0)
    # a program from before the count, or a window without the span
    assert mesh.owner_skew([_fused(None)]) is None
    assert mesh.owner_skew([]) is None


def test_owner_skew_reads_the_programs_ring():
    from multiverso_tpu.telemetry import trace

    trace.TRACER.reset()
    assert mesh.read("mesh.owner_skew.we", {}) is None
    try:
        trace.record("we.fused", 0, 1_000, update_rows_by_shard=[3, 1])
        assert mesh.read("mesh.owner_skew.we", {}) is None   # not in a window
        trace.TRACER._events[-1]["prof"] = True
        assert mesh.read("mesh.owner_skew.we", {}) == pytest.approx(75.0)
    finally:
        trace.TRACER.reset()


@pytest.mark.parametrize("rows,shards,per", [(12_000_000, 4, 3_000_001),
                                             (203, 8, 26), (7, 1, 8)])
def test_owner_rows_is_the_contiguous_partition(rows, shards, per):
    ids = np.arange(rows)
    owner = owner_rows(ids, rows, shards)
    assert owner[0] == 0 and owner[-1] <= shards - 1
    assert (np.diff(owner) >= 0).all()
    counts = np.bincount(owner, minlength=shards)
    assert (counts[:-1] == per).all() and 0 < counts[-1] <= per
