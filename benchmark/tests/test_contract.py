"""The printed result keeps to the contract, for every cell in
BENCHMARK.json, and BENCHMARK.json keeps to its own."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import ROOT, run_cell

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _expected(group, cell):
    return {m["name"] for m in SPEC[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_last_line_has_exactly_the_contract_keys(cell):
    result, _ = run_cell(ROOT, cell, trace=0)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["metrics"]) == _expected("end_to_end", cell)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_reports_per_layer_metrics(cell):
    result, _ = run_cell(ROOT, cell, trace=1)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device trace, so only host-side readers answer here;
    # whatever is reported is a per-layer metric of this cell
    assert set(result["metrics"]) <= _expected("per_layer", cell)
    assert not set(result["metrics"]) & _expected("end_to_end", cell)


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the paths, the
    command exits non-zero and prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=""))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_no_result_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_benchmark_json_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        data = json.load(open(os.path.join(ROOT, c["file"])))
        assert data["source"] == c["source"]
        assert set(c["reduced"]) == set(data["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and NAME.match(m["name"])
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        moved = next(x for x in SPEC["end_to_end"] if x["name"] == m["moves"])
        cells = m.get("workloads", CELLS)
        assert set(cells) <= set(moved.get("workloads", CELLS))
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_run_py_names_no_cell_config_driver_or_metric():
    text = open(os.path.join(ROOT, "benchmark", "run.py")).read()
    names = ([w["name"] for w in SPEC["workloads"]]
             + [c["name"] for c in SPEC["configs"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
             + [f[:-3] for d in ("drivers", "layers")
                for f in os.listdir(os.path.join(ROOT, "benchmark", d))
                if f.endswith(".py") and f != "__init__.py"])
    # "device" is also a key of the result object the contract fixes
    names = [n for n in names if n != "device"]
    assert not [n for n in names if re.search(
        r"(?<![A-Za-z0-9_])" + re.escape(n) + r"(?![A-Za-z0-9_])", text)]
