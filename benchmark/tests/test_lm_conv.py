"""The fifth language-model cell (``lfm2-train-8k``): the cell found by name
with every metric it reports, the configuration as the published one but
for its four reduced keys, the traffic as ``lm-train-8k``'s load letter for
letter, what its mixers and its step must compute against hand counts
(``conv_shapes``), its readers on made-up records, and the comparison's
controls at ``--cpu-tiny`` sizes (``lm_conv_control.py``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import attn_shapes, conv_shapes
from benchmark.drivers import lm_train, lm_train_conv
from benchmark.layers import attn, attnmix, conv
from conftest import ROOT, run_cell

CELL = "lfm2-train-8k"
CONFIG = "lfm2-8b-a1b-ep4"
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size"]


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cell_is_found_by_name_and_lists_every_metric_it_reports():
    spec = _spec()
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "lm-train-8k-conv", 1)
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == REDUCED
    assert config["source"] == _config()["source"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    # every language-model metric the four other cells share, this one has
    shared = {m["name"] for m in spec["per_layer"]
              if {"glm47f-train-8k", "mellum2-train-8k", "trinity-train-16k",
                  "nemotron3n-train-16k"} <= set(m.get("workloads", []))}
    own = {"conv.mixer_flops_share.lm"}
    by_kind = {"attnmix.full_device_share.lm", "attnmix.full_mxu_share.lm"}
    assert mine == shared | own | by_kind and len(shared) == 13
    new = next(m for m in spec["per_layer"]
               if m["name"] == "conv.mixer_flops_share.lm")
    assert new == {"name": "conv.mixer_flops_share.lm", "unit": "%",
                   "better": "lower", "source": "program_counter",
                   "layer": "short-convolution mixer (models/lfm2_moe.py)",
                   "moves": "words_per_s", "workloads": [CELL]}
    assert CELL in next(m for m in spec["end_to_end"]
                        if m["name"] == "words_per_s")["workloads"]
    # every reader the cell's metrics name is there to be found
    for family in {m.split(".")[0] for m in mine}:
        assert (os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", family + ".py"))
            or os.path.isdir(os.path.join(ROOT, "benchmark", "layers",
                                          family)))


def test_the_configuration_is_the_published_one_but_for_four_keys():
    c = _config()
    assert list(c["reduced"]) == list(c["published"]) == REDUCED
    assert c["published"] == {"num_hidden_layers": 24, "num_dense_layers": 2,
                              "num_experts": 32, "vocab_size": 65536}
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 8, 16384)
    # no width differs from the source
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["conv_L_cache"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["routed_scaling_factor"]) == (
                2048, 32, 8, 3, 7168, 1792, 4, 1)
    assert (c["norm_eps"], c["rope_theta"], c["conv_bias"],
            c["use_expert_bias"], c["norm_topk_prob"]) == (
                1e-5, 1000000, False, True, True)
    kinds = c["layer_types"]
    assert len(kinds) == 24 and kinds.count("conv") == 18
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    # layer 0, then one whole period from the first expert layer on
    assert c["layers_run"] == [0, 2, 3, 4, 5]
    assert len(c["layers_run"]) == c["num_hidden_layers"]
    assert [kinds[i] for i in c["layers_run"]] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    for key in ("source", "assumed", "deployment", "tiny"):
        assert c[key]
    for key in ("tied_head", "route", "dense_width", "conv_mixer",
                "first_values", "init_scales"):
        assert c["assumed"][key]
    assert not set(c["tiny"]) - {"num_hidden_layers", "num_experts",
                                 "vocab_size", "published", "layers_run"}


def test_the_traffic_is_lm_train_8ks_load_under_another_driver():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            return json.load(f)

    base, mine = load("lm-train-8k.json"), load("lm-train-8k-conv.json")
    assert {k for k in base if base[k] != mine[k]} == {"driver", "why"}
    assert set(base) == set(mine)
    assert mine["driver"] == "lm_train_conv"
    assert (mine["sequences"], mine["positions"], mine["batch_pool"],
            mine["zipf_a"], mine["document_tokens"]) == (
                2, 8192, 16, 1.1, [64, 2048])


def test_the_mixers_and_the_step_must_compute_what_the_hand_count_says():
    # one mixer: 2,048 -> 6,144 and 2,048 -> 2,048, 2 operations a
    # multiply-add
    assert conv_shapes.mixer_flops(2048) == 2 * 2048 * 8192 == 33_554_432
    assert conv_shapes.mixer_flops(1) == 8
    # in [16,384, 6,144], out [16,384, 2,048], bfloat16
    assert conv_shapes.mixer_bytes(2, 8192, 2048) == 16384 * 8192 * 2
    c = _config()
    parts = {"conv mixers": 4 * 33_554_432,
             "dense FFN": 3 * 2 * 2048 * 7168,
             "held experts, one a token": 4 * 3 * 2 * 2048 * 1792,
             "routers": 4 * 2 * 2048 * 32,
             "head": 2 * 2048 * 16384,
             "attention projections": 2 * 2048 * (2 * 2048 + 2 * 512),
             "causal core": 2 * 2 * 64 * 32 * 8193 // 2}
    assert conv_shapes.step_flops_token(c, 8192) == sum(parts.values())
    share = 100.0 * parts["conv mixers"] / sum(parts.values())
    assert round(share, 2) == 31.03
    assert max(parts, key=parts.get) == "conv mixers"
    # a step's core, forward and backward, at the head's 64
    assert attn_shapes.core_flops(2, 32, 8192, 64) == (
        2 * 32 * 12 * 64 * 8192 * 8193 // 2)


def test_the_mixers_share_is_read_from_the_steps_counts():
    step = lambda **args: {"name": "lm.step", "prof": True, "args": args}
    events = [step(mixer_flops_token=30, step_flops_token=100),
              step(mixer_flops_token=30, step_flops_token=100),
              {"name": "lm.step", "args": {"mixer_flops_token": 1,
                                           "step_flops_token": 1}}]
    assert conv.read_events("conv.mixer_flops_share.lm", events) == 30.0
    # a program from before the counts (the parent), another quantity
    assert conv.read_events("conv.mixer_flops_share.lm",
                            [step(tokens=16384)]) is None
    assert conv.read_events("conv.mixer_flops_share.lm", []) is None
    assert conv.read_events("conv.other.lm", events) is None


@pytest.mark.parametrize("seen, share", [
    (104, 10.0),      # every kernel of 26 steps
    (100, 10.0),      # a trace that lost one step's core: still the share
    (96, 10.0),       # and two
    (92, None),       # more than a stopped host explains
    (78, None),       # a kernel the program names otherwise, once a step
    (0, None)])       # no kernel under the scope (or no trace)
def test_a_trace_that_lost_a_stretch_still_gives_the_kernels_shares(
        monkeypatch, seen, share):
    def lm_check(state, run):
        run["attention_s"] = ({"seconds": 2.0, "kernels": seen} if seen
                              else {})
        return {"correct": True, "detail": {}}

    by_kind = ({"full": {"seconds": 2.0, "kernels": seen},
                "window": {"seconds": 0.0, "kernels": 0}} if seen else {})
    monkeypatch.setattr(lm_train, "check", lm_check)
    monkeypatch.setattr(attnmix, "kernel_seconds", lambda name: by_kind)

    class _Cell:
        name = CELL

    flops = 26 * attn_shapes.core_flops(2, 32, 8192, 64)
    run = {"attention_kernels": 104, "attnmix_kernels": {"full": 104},
           "attnmix_flops": {"full": flops}}
    verdict = lm_train_conv.check({"cell": _Cell}, run)
    assert verdict["correct"] and verdict["detail"]["attention_kernels"] == {
        "seen": seen, "expected": 104}
    ctx = {"trace": {"busy_s": 20.0}, "run": run,
           "device_kind": "TPU v5 lite"}
    assert attn.read("attn.device_share.lm", ctx) == share
    assert attnmix.read("attnmix.full_device_share.lm", ctx) == share
    assert attnmix.read("attnmix.window_device_share.lm", ctx) is None
    mxu = attnmix.read("attnmix.full_mxu_share.lm", ctx)
    if share is None:
        assert mxu is None
    else:       # the operations of the cores SEEN over the seconds seen
        assert mxu == pytest.approx(
            100.0 * (flops * seen // 104) / 2.0 / 197e12)
        assert 0 < mxu < 100


def test_an_expert_blocks_norm_is_held_to_the_experts_limits():
    routed = ("L1", "L2")
    assert lm_train_conv.table_class("L1.ffn_norm", routed) == "experts"
    assert lm_train_conv.table_class("L0.ffn_norm", routed) == "plain"
    assert lm_train_conv.table_class("L1.attn_norm", routed) == "plain"
    assert lm_train_conv.table_class("embed", routed) == "tied"
    assert lm_train_conv.table_class("L2.conv_w", routed) == "taps"
    assert lm_train_conv.table_class("L2.eg", routed) == "experts"
    assert lm_train_conv.table_class("L2.router", routed) == "router"
    for limits in (lm_train_conv.TOL_NORM, lm_train_conv.TOL_ELEM):
        assert set(limits) == {"plain", "experts", "router", "tied", "taps"}


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
    assert detail["check"]["tables"] == 2 + 8 + 12 + 9


def test_the_controls_are_told_apart_at_tiny_sizes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_conv_control.py"),
         "--seed", "2147483019", "--cpu-tiny"], capture_output=True,
        text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(said["controls"]) == {"operands_float8", "taps_reversed"}
    assert not any(v["agrees"] for v in said["controls"].values())
    assert out.returncode == 0, out.stderr[-3000:]
    assert said["program"]["step_agrees"]
