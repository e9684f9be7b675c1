"""The third language-model cell (``trinity-train-16k``): what its cores
must compute against hand counts, the cell found by name with every metric
it reports, the traffic's one change from ``lm-train-8k``, and
the comparison's control at ``--cpu-tiny`` sizes (``lm_afmoe_control.py``).
``test_contract.py`` runs the cell itself through ``run.py --cpu-tiny``."""

import json
import os
import subprocess
import sys

from benchmark import attn_shapes
from conftest import ROOT

CELL = "trinity-train-16k"


def test_the_cells_cores_must_compute_what_the_issue_counted():
    full = attn_shapes.core_flops(1, 32, 16384, 128)
    band = attn_shapes.core_flops(1, 32, 16384, 128, 2048)
    assert attn_shapes.live_positions(16384) == 134_225_920
    assert attn_shapes.live_positions(16384, 2048) == 31_458_304
    assert 6.59e12 < full < 6.60e12 and 1.54e12 < band < 1.55e12


def test_the_cell_is_found_by_name_and_lists_every_metric_it_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert spec["workloads"][-1]["name"] == CELL
    entry = spec["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "trinity-mini-ep8", "lm-train-16k", 1)
    config = spec["configs"][-1]
    assert config["name"] == entry["config"]
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {m for m in mine if m.startswith("attnmix.")} == {
        "attnmix.window_device_share.lm", "attnmix.full_device_share.lm",
        "attnmix.window_mxu_share.lm", "attnmix.full_mxu_share.lm",
        "attnmix.band_pairs_share.lm"}
    # every language-model metric the other two cells share, this one has
    shared = {m["name"] for m in spec["per_layer"]
              if {"glm47f-train-8k", "mellum2-train-8k"}
              <= set(m.get("workloads", []))}
    assert shared == mine - {m for m in mine if m.startswith("attnmix.")}
    assert len(shared) == 13
    assert CELL in next(m for m in spec["end_to_end"]
                        if m["name"] == "words_per_s")["workloads"]


def test_the_traffic_is_lm_train_8k_with_one_change_the_sequence():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            return json.load(f)

    base, mine = load("lm-train-8k.json"), load("lm-train-16k.json")
    differ = {k for k in base if base[k] != mine[k]}
    assert differ == {"driver", "why", "sequences", "positions", "tiny",
                      "calibration"}
    # the load is the sequence's change alone; set-up's calibration is the
    # same schedule given more passes to end in
    assert {k for k in base["calibration"]
            if base["calibration"][k] != mine["calibration"][k]} == {
                "max_passes"}
    assert (mine["sequences"], mine["positions"]) == (1, 16384)
    assert (base["sequences"] * base["positions"]
            == mine["sequences"] * mine["positions"])
    assert mine["driver"] == "lm_train_afmoe"


def test_the_control_is_told_apart_at_tiny_sizes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_afmoe_control.py"),
         "--seed", "2147483019", "--cpu-tiny"], capture_output=True,
        text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert said["program"]["step_agrees"] and not said["control"]["agrees"]
    assert said["control"]["operands"] == "float8_e4m3fn"
