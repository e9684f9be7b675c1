"""The sixth language-model cell (``keye-train-16k``): the cell found by
name with every metric it reports (every count derived from the cells'
own lists), the configuration as the published one but for its three
reduced keys, the traffic as ``lm-train-16k``'s load letter for letter,
what its indexer, its selected core and its step must compute against hand
counts (``sparse_shapes``), its readers on made-up records, and the
comparison's control at ``--cpu-tiny`` sizes (``lm_sparse_control.py``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import attn_shapes, sparse_shapes
from benchmark.drivers import lm_train, lm_train_sparse
from benchmark.layers import attn, sparse
from conftest import ROOT, run_cell

CELL = "keye-train-16k"
CONFIG = "keye-vl-2.0-30b-a3b-ep8"
TRAFFIC = "lm-train-16k-sparse"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
OWN = {"sparse.core_device_share.lm", "sparse.core_mxu_share.lm",
       "sparse.selected_share.lm"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def test_the_cell_is_found_by_name_and_lists_every_metric_it_reports():
    spec = _spec()
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == REDUCED
    assert config["source"] == _config()["source"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    # the other language-model cells: those whose traffic a driver of the
    # lm_train family runs, whatever their number
    siblings = {w["name"] for w in spec["workloads"] if w["name"] != CELL
                and _traffic(w["traffic"])["driver"].startswith("lm_train")}
    assert len(siblings) >= 5
    # every per-layer metric that all of them share, this cell has too
    shared = {m["name"] for m in spec["per_layer"]
              if siblings <= set(m.get("workloads", []))}
    assert shared and mine == shared | OWN
    for name in OWN:
        new = next(m for m in spec["per_layer"] if m["name"] == name)
        assert new["workloads"] == [CELL] and new["moves"] == "words_per_s"
        assert new["unit"] == "%"
    layers = {m["name"]: m["layer"] for m in spec["per_layer"]}
    assert layers["sparse.core_device_share.lm"] == layers[
        "sparse.core_mxu_share.lm"] == layers["attn.device_share.lm"]
    assert layers["sparse.selected_share.lm"] == (
        "indexer and selection (models/keye_moe.py)")
    assert CELL in next(m for m in spec["end_to_end"]
                        if m["name"] == "words_per_s")["workloads"]
    # every reader the cell's metrics name is there to be found
    for family in {m.split(".")[0] for m in mine}:
        assert (os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", family + ".py"))
            or os.path.isdir(os.path.join(ROOT, "benchmark", "layers",
                                          family)))
    # the new entries stand last in their lists
    assert spec["workloads"][-1]["name"] == CELL
    assert spec["configs"][-1]["name"] == CONFIG
    assert {m["name"] for m in spec["per_layer"][-len(OWN):]} == OWN


def test_the_configuration_is_the_published_one_but_for_three_keys():
    c = _config()
    assert list(c["reduced"]) == list(c["published"]) == REDUCED
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                              "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 16, 18992)
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["num_experts"] * 8 == c["published"]["num_experts"]
    # no width differs from the source
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["intermediate_size"], c["num_local_experts"]) == (
                2048, 32, 4, 128, 768, 8, 6144, 128)
    assert c["sa_config"] == {"indexer_head_dim": 64,
                              "indexer_num_heads": 16,
                              "indexer_num_kv_heads": 1,
                              "kv_chunk_size": 512, "q_chunk_size": 512,
                              "topk": 2048}
    assert c["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert (c["rope_theta"], c["rms_norm_eps"], c["norm_topk_prob"],
            c["tie_word_embeddings"]) == (10000000, 1e-6, True, False)
    if os.path.exists(CATALOG):     # every number of the catalog's entry
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert c["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if c.get(k) != v}
        assert differs == set(REDUCED)
    for key in ("source", "assumed", "deployment", "tiny", "parameters"):
        assert c[key]
    for key in ("qk_norm", "router_aux_loss_coef", "positions", "left_out",
                "indexer (+)", "index_loss (+)", "indexer_precision",
                "optimizer", "learning_rate", "init_scale"):
        assert c["assumed"][key], key
    assert "vision tower" in c["assumed"]["left_out"]
    assert "8" in c["deployment"] and "eight" in c["deployment"]
    # tiny shrinks counts and the selection's reach, never a width
    assert not set(c["tiny"]) - {"num_hidden_layers", "num_experts",
                                 "vocab_size", "published", "sa_config"}
    tiny = c["tiny"]["sa_config"]
    assert (tiny["indexer_head_dim"], tiny["indexer_num_heads"]) == (64, 16)


def test_the_configuration_counts_its_parameters_as_the_program_does():
    import numpy as np
    from multiverso_tpu.models import mla_moe

    class _Cell:
        config = _config()

    cfg = lm_train_sparse._model_config(_Cell)
    shapes = mla_moe.param_shapes(cfg)
    total = sum(int(np.prod(s)) for s in shapes.values())
    said = _config()["parameters"]
    assert total == 465_391_104 == next(
        v for k, v in said.items() if k.startswith("total"))
    assert said["one layer"] * 4 == said["four layers"]
    assert said["four layers"] + next(
        v for k, v in said.items() if k.startswith("embedding")) == total
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk,
            cfg.index_chunk, cfg.mrope_section) == (16, 64, 2048, 512,
                                                    (16, 24, 24))
    assert len(cfg.layers()) == 4 and cfg.route == "softmax"


def test_the_traffic_is_lm_train_16ks_load_under_another_driver():
    base, mine = _traffic("lm-train-16k"), _traffic(TRAFFIC)
    assert {k for k in base if base[k] != mine[k]} == {
        "driver", "why", "calibration", "tiny"}
    assert {k for k in base["tiny"] if base["tiny"][k] != mine["tiny"][k]
            } == {"calibration"}
    assert set(base) == set(mine)
    assert mine["driver"] == "lm_train_sparse"
    assert (mine["sequences"], mine["positions"], mine["batch_pool"],
            mine["zipf_a"], mine["document_tokens"],
            mine["end_of_document_id"]) == (1, 16384, 16, 1.1, [64, 2048], 0)
    # the routers are calibrated by their balance term, at the window
    # cell's schedule for 128 outputs
    window = _traffic("lm-train-8k-window")["calibration"]
    assert {k for k in window if window[k] != mine["calibration"][k]} == {
        "max_passes"}
    assert mine["calibration"]["max_passes"] == base["calibration"][
        "max_passes"]


def test_the_selection_and_the_step_must_compute_what_the_hand_count_says():
    c = _config()
    assert sparse_shapes.selected_positions(16384, 2048) == (
        2048 * 2049 // 2 + (16384 - 2048) * 2048) == 31_458_304
    assert sparse_shapes.selected_positions(1024, 2048) == 1024 * 1025 // 2
    assert attn_shapes.live_positions(16384) == 134_225_920
    assert round(100 * 31_458_304 / 134_225_920, 2) == 23.44
    assert sparse_shapes.core_flops(1, 32, 16384, 128, 2048) == (
        12 * 128 * 32 * 31_458_304)
    assert sparse_shapes.select_bytes(1, 16384) == 268_435_456
    parts = {"indexer's products": 2 * 2048 * (16 * 64 + 64 + 16),
             "indexer's scores": 16 * 64 * 16385,
             "selected core": 4 * 128 * 32 * 31_458_304 // 16384,
             "target": 2 * 128 * 32 * 31_458_304 // 16384,
             "projections": 2 * 2048 * 128 * 2 * (32 + 4),
             "router": 2 * 2048 * 128,
             "held experts, one a token": 3 * 2 * 2048 * 768}
    assert sparse_shapes.index_flops(c, 16384) == (
        parts["indexer's products"] + parts["indexer's scores"])
    assert sparse_shapes.target_flops(c, 16384) == parts["target"]
    head = 2 * 2048 * 18992
    assert sparse_shapes.step_flops_token(c, 16384) == (
        4 * sum(parts.values()) + head)
    mechanism = 4 * sum(v for k, v in parts.items()
                        if k.startswith(("indexer", "selected", "target")))
    assert round(100 * mechanism / (4 * sum(parts.values()) + head)) == 50


def test_the_selected_share_is_read_from_the_steps_counts():
    step = lambda **args: {"name": "lm.step", "prof": True, "args": args}
    events = [step(attn_positions_selected=30, attn_positions_causal=120),
              step(attn_positions_selected=30, attn_positions_causal=120),
              {"name": "lm.step", "args": {"attn_positions_selected": 1,
                                           "attn_positions_causal": 1}}]
    assert sparse.read_events("sparse.selected_share.lm", events) == 25.0
    # a program from before the counts (the parent), another quantity
    assert sparse.read_events("sparse.selected_share.lm",
                              [step(tokens=16384)]) is None
    assert sparse.read_events("sparse.selected_share.lm", []) is None
    assert sparse.read_events("sparse.other.lm", events) is None


@pytest.mark.parametrize("seen, share", [
    (208, 10.0),      # every kernel of 13 steps
    (200, 10.0),      # a trace that lost a stretch: still the share
    (192, None),      # more than a stopped host explains
    (0, None)])       # no kernel under the scope (or no trace)
def test_the_kernels_shares_under_the_allowance_for_a_lost_stretch(
        monkeypatch, seen, share):
    def lm_check(state, run):
        run["attention_s"] = ({"seconds": 2.0, "kernels": seen} if seen
                              else {})
        return {"correct": True, "detail": {}}

    monkeypatch.setattr(lm_train, "check", lm_check)
    monkeypatch.setattr(sparse, "kernel_seconds", lambda name: (
        {"seconds": 2.0, "kernels": seen} if seen else {}))

    class _Cell:
        name = CELL
        traffic = {"calibration": {"held_share_within": 0.5}}

    class _Cfg:
        experts_held, n_experts = 16, 128

    flops = 13 * 4 * sparse_shapes.core_flops(1, 32, 16384, 128, 2048)
    run = {"attention_kernels": 208, "sparse_kernels": 208,
           "sparse_flops": flops, "facts": {"held_share": [12.4, 12.9]}}
    verdict = lm_train_sparse.check({"cell": _Cell, "cfg": _Cfg}, run)
    assert verdict["correct"]
    assert verdict["detail"]["held_share_off_even"] == pytest.approx(0.4)
    ctx = {"trace": {"busy_s": 20.0}, "run": run,
           "device_kind": "TPU v5 lite"}
    assert attn.read("attn.device_share.lm", ctx) == share
    assert sparse.read("sparse.core_device_share.lm", ctx) == share
    mxu = sparse.read("sparse.core_mxu_share.lm", ctx)
    if share is None:
        assert mxu is None
    else:       # the operations of the cores SEEN over the seconds seen
        assert mxu == pytest.approx(
            100.0 * (flops * seen // 208) / 2.0 / 197e12)
        assert 0 < mxu < 100
    # a layer whose held share strays is not correct
    run["facts"]["held_share"] = [12.4, 14.6]
    run["attention_kernels"] = 208
    assert not lm_train_sparse.check({"cell": _Cell, "cfg": _Cfg},
                                     run)["correct"]


def test_the_indexers_tensors_are_a_class_of_their_own():
    for kind in lm_train_sparse.INDEXER:
        assert lm_train_sparse.table_class("L2." + kind) == "index"
    assert lm_train_sparse.table_class("L1.wq") == "plain"
    assert lm_train_sparse.table_class("L1.q_norm") == "plain"
    assert lm_train_sparse.table_class("L2.eg") == "experts"
    assert lm_train_sparse.table_class("L2.router") == "router"
    for limits in (lm_train_sparse.TOL_NORM, lm_train_sparse.TOL_ELEM):
        assert set(limits) == {"plain", "experts", "router", "index"}


def test_the_cell_runs_at_tiny_sizes_and_reports_its_metrics():
    result, lines = run_cell(ROOT, CELL, seed=2147483019)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"words_per_s", "setup_s"}
    detail = json.loads(lines[-2])["detail"]
    assert detail["compiles_in_window"] == 0
    assert detail["facts"]["overflow_rows"] == 0
    assert detail["facts"]["index_loss_last"] > 0
    check = detail["check"]
    assert check["count_identities"]
    assert check["router_flips"] <= check["router_flips_allowed"]
    assert check["tables"] == 3 + 2 * 17
    assert len(check["differ_share"]) == 2 and "far" in check
    assert check["selection_rows_remade_differ"] >= 0


def test_the_control_is_told_apart_at_tiny_sizes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_sparse_control.py"),
         "--seed", "2147483019", "--cpu-tiny"], capture_output=True,
        text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert not said["control"]["agrees"]
    assert said["control"]["differ_err_over_tol"] > 1.0
    assert out.returncode == 0, out.stderr[-3000:]
    assert said["program"]["step_agrees"]
