"""The fourth language-model cell (``nemotron3n-train-16k``): the cell
found by name with every metric it reports, the traffic as
``lm-train-16k``'s load letter for letter, what its experts and its scan
must compute against hand counts (``ssm_shapes``), and the comparison's
controls at ``--cpu-tiny`` sizes (``lm_hybrid_control.py``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import lm_shapes, ssm_shapes
from benchmark.drivers import lm_train, lm_train_hybrid
from benchmark.layers import attn
from conftest import ROOT, run_cell

CELL = "nemotron3n-train-16k"
CONFIG = "nemotron-3-nano-30b-a3b-ep16"


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_found_by_name_and_lists_every_metric_it_reports():
    spec = _spec()
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "lm-train-16k-hybrid", 1)
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    # every language-model metric the three other cells share, this one has
    shared = {m["name"] for m in spec["per_layer"]
              if {"glm47f-train-8k", "mellum2-train-8k", "trinity-train-16k"}
              <= set(m.get("workloads", []))}
    assert shared == mine and len(mine) == 13
    assert not {m for m in mine if m.startswith("attnmix.")}
    for name in ("words_per_s",):
        assert CELL in next(m for m in spec["end_to_end"]
                            if m["name"] == name)["workloads"]
    # every reader the cell's metrics name is there to be found
    for family in {m.split(".")[0] for m in mine}:
        assert (os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", family + ".py"))
            or os.path.isdir(os.path.join(ROOT, "benchmark", "layers",
                                          family)))


def test_the_configuration_is_the_published_one_but_for_three_keys():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        c = json.load(f)
    assert set(c["reduced"]) == set(c["published"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert c["published"] == {"num_hidden_layers": 52,
                              "n_routed_experts": 128, "vocab_size": 131072}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (9, 8, 16384)
    # no width differs from the source
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["n_groups"], c["ssm_state_size"], c["conv_kernel"],
            c["chunk_size"], c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["num_experts_per_tok"]) == (
                2688, 64, 64, 8, 128, 4, 128, 1856, 3712, 32, 2, 128, 6)
    pattern = c["hybrid_override_pattern"]
    assert len(pattern) == 52 and pattern[:9] == "MEMEM*EME"
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        23, 23, 6)
    for key in ("source", "assumed", "deployment", "tiny"):
        assert c[key]
    assert not set(c["tiny"]) - {"num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "published",
                                 "hybrid_override_pattern"}


def test_the_traffic_is_lm_train_16ks_load_under_another_driver():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            return json.load(f)

    base, mine = load("lm-train-16k.json"), load("lm-train-16k-hybrid.json")
    assert {k for k in base if base[k] != mine[k]} == {"driver", "why",
                                                       "tiny"}
    assert set(base) == set(mine)
    assert mine["driver"] == "lm_train_hybrid"
    assert (mine["sequences"], mine["positions"]) == (1, 16384)


def test_the_experts_products_count_two_matrices():
    rows, dim, ffn = 6144, 2688, 1856
    two = ssm_shapes.expert_products_flops(rows, dim, ffn)
    assert two == 2 * 3 * 2 * dim * ffn * rows == 367_823_683_584
    assert 3 * two == 2 * lm_shapes.expert_products_flops(rows, dim, ffn)


def test_the_scan_must_compute_what_the_hand_count_says():
    # one chunk of 2 positions, 1 head of 1, 1 group, state 1: 3 live
    # pairs; C B^T 2 * 3, the masked product 2 * 3, the chunk's state and
    # C H 2 * 2 each; times 3 for the backward pass
    assert ssm_shapes.scan_flops(1, 2, 1, 1, 1, 1, 2) == 3 * (6 + 6 + 8)
    # the cell: 128 chunks of 128; 8,256 live pairs a chunk
    flops = ssm_shapes.scan_flops(1, 16384, 64, 64, 8, 128, 128)
    a_chunk = (2 * 128 * 8256 * 8 + 2 * 64 * 8256 * 64
               + 4 * 128 * 128 * 64 * 64)
    assert flops == 3 * 128 * a_chunk == 135_543_128_064
    # x and y 16,384 x 4,096, B and C 16,384 x 1,024 each in bfloat16, the
    # step sizes 16,384 x 64 in float32; once forward, twice backward
    one_pass = 16384 * (4096 + 2048) * 2 + 16384 * 64 * 4 + 16384 * 4096 * 2
    assert ssm_shapes.scan_bytes(1, 16384, 64, 64, 8, 128) == 3 * one_pass


@pytest.mark.parametrize("seen, share", [
    (104, 10.0),      # every kernel of 26 steps
    (100, 10.0),      # a trace that lost one step's core: still the share
    (96, 10.0),       # and two
    (92, None),       # more than a stopped host explains
    (78, None),       # a kernel the program names otherwise, once a step
    (0, None)])       # no kernel under the scope (or no trace)
def test_a_trace_that_lost_a_stretch_still_gives_the_attention_share(
        monkeypatch, seen, share):
    def lm_check(state, run):
        run["attention_s"] = ({"seconds": 2.0, "kernels": seen} if seen
                              else {})
        return {"correct": True, "detail": {}}

    monkeypatch.setattr(lm_train, "check", lm_check)
    run = {"attention_kernels": 104}
    verdict = lm_train_hybrid.check({}, run)
    assert verdict["correct"] and verdict["detail"]["attention_kernels"] == {
        "seen": seen, "expected": 104}
    got = attn.read("attn.device_share.lm",
                    {"trace": {"busy_s": 20.0}, "run": run})
    assert got == share


def test_the_cell_runs_at_tiny_sizes_and_reports_its_metrics():
    result, lines = run_cell(ROOT, CELL, seed=2147483019)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"words_per_s", "setup_s"}
    detail = json.loads(lines[-2])["detail"]
    assert detail["compiles_in_window"] == 0
    assert detail["facts"]["overflow_rows"] == 0
    assert detail["check"]["count_identities"]
    assert detail["check"]["router_flips"] <= detail["check"][
        "router_flips_allowed"]


def test_the_controls_are_told_apart_at_tiny_sizes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_hybrid_control.py"),
         "--seed", "2147483019", "--cpu-tiny"], capture_output=True,
        text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(said["controls"]) == {"sums_bfloat16", "no_carry"}
    assert not any(v["agrees"] for v in said["controls"].values())
    assert out.returncode == 0, out.stderr[-3000:]
    assert said["program"]["step_agrees"]
