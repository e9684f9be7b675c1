"""The ninth language-model cell (``ouro-train-4k``): the cell found by name
with every metric it reports, the configuration as the published one but
for its depth, the traffic as ``lm-train-4k-hc``'s load without a
calibration, what the looped stack and its exits compute against hand
counts (``loop_shapes``), the readers of ``layers/loop`` on made-up sums,
and the comparison's controls at ``--cpu-tiny`` sizes
(``lm_loop_control.py``)."""

import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import loop_shapes
from benchmark.drivers import lm_train_loop
from benchmark.layers import loop
from conftest import ROOT, run_cell

CELL = "ouro-train-4k"
CONFIG = "ouro-2.6b-pp6"
OWN = {"loop.stack_mxu_share.lm", "loop.head_device_share.lm",
       "loop.head_mxu_share.lm", "loop.exit_entropy_share.lm"}
# what every language-model cell reports but this one: it has no router
ROUTERS = {"moe.expert_device_share.lm", "moe.expert_mxu_share.lm",
           "moe.held_share.lm", "moe.load_max_over_mean.lm",
           "counts.overflow_rows.lm"}


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
        CONFIG, "lm-train-4k-loop", 1)
    assert "no router" in entry["why"] and len(entry["why"]) <= 200
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == _config()["source"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    shared = {m["name"] for m in spec["per_layer"]
              if {"glm47f-train-8k", "mellum2-train-8k", "trinity-train-16k"}
              <= set(m.get("workloads", []))}
    assert shared - mine == ROUTERS
    assert mine - shared == OWN | {"attnmix.full_device_share.lm",
                                   "attnmix.full_mxu_share.lm"}
    for m in spec["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "words_per_s"
            assert m["layer"].startswith("looped stack") and m["unit"] == "%"
            assert m["source"] == ("program_counter" if "entropy" in m["name"]
                                   else "device_trace")
    assert spec["per_layer"][-4:] == [m for m in spec["per_layer"]
                                      if m["name"] in OWN]
    assert spec["workloads"][-1] == entry and spec["configs"][-1] == config
    assert next(m for m in spec["end_to_end"] if m["name"] == "words_per_s")[
        "workloads"][-1] == CELL
    # every reader the cell's metrics name is there to be found
    for family in {m.split(".")[0] for m in mine}:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", family + ".py"))
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)


def test_the_configuration_is_the_published_one_but_for_its_depth():
    c = _config()
    assert set(c["reduced"]) == set(c["published"]) == {"num_hidden_layers"}
    assert c["published"] == {"num_hidden_layers": 48}
    assert c["num_hidden_layers"] == 8 and 48 % c["num_hidden_layers"] == 0
    # no width differs from the source, nor the vocabulary, nor the passes
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"], c["total_ut_steps"], c["early_exit_threshold"],
            c["rms_norm_eps"], c["rope_theta"], c["tie_word_embeddings"]) == (
                2048, 16, 16, 128, 5632, 49152, 4, 1, 1e-6, 1000000, False)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert row["source_url"] == c["source"]
        differ = {k for k, v in row["config"].items() if c.get(k) != v}
        assert differ == set(c["reduced"])
    for key in ("source", "assumed", "deployment", "tiny", "parameters"):
        assert c[key]
    for key in ("loop", "exit_gate", "exit_entropy_coef",
                "early_exit_threshold", "attention_bias", "rotary_pairing",
                "document_mask", "compute_precision", "optimizer",
                "learning_rate", "init_scale", "init_scales"):
        assert c["assumed"][key]
    assert c["exit_entropy_coef"] == 0.05
    assert c["init_scales"]["wo"] == c["init_scales"]["wd"] == pytest.approx(
        0.02 / math.sqrt(2 * 4 * 8))
    assert "stage one of six" in c["deployment"]
    assert "number of chips that share a layer is 1" in c["deployment"]
    # the tiny sizes shrink row counts alone: the passes stay
    assert set(c["tiny"]) == {"num_hidden_layers", "vocab_size"}


def test_the_parameters_are_the_programs_count():
    """The file's arithmetic, from ``param_shapes``."""
    import numpy as np
    from multiverso_tpu.models import mla_moe

    c = _config()
    c.pop("tiny")

    class _Cell:
        config = c

    cfg = lm_train_loop._model_config(_Cell)
    shapes = mla_moe.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == c[
        "parameters"]["total"] == 612_438_017
    assert len(shapes) == c["parameters"]["tables"] == 93
    assert {lm_train_loop.table_class(n) for n in shapes} == {
        "plain", "vocab", "norms", "gate"}
    assert [n for n in shapes if lm_train_loop.table_class(n) == "gate"] == [
        "exit.w", "exit.b"]
    assert sum(lm_train_loop.table_class(n) == "norms"
               for n in shapes) == 4 * 8 + 1
    assert set(lm_train_loop.CONTROLS) == {
        "operands_float8", "one_pass_less", "no_renorm", "untrained_weights"}
    bad = dict(c, layer_types=["sliding_attention"] * 48)
    with pytest.raises(ValueError):
        lm_train_loop._model_config(type("C", (), {"config": bad}))


def test_the_traffic_is_lm_train_4k_hcs_load_without_a_calibration():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            return json.load(f)

    base, mine = load("lm-train-4k-hc.json"), load("lm-train-4k-loop.json")
    assert set(base) - set(mine) == {"calibration"}
    assert {k for k in mine if base[k] != mine[k]} == {"driver", "why",
                                                       "tiny"}
    assert mine["driver"] == "lm_train_loop"
    assert (mine["sequences"], mine["positions"], mine["batch_pool"],
            mine["zipf_a"], mine["document_tokens"],
            mine["end_of_document_id"]) == (1, 4096, 16, 1.1, [64, 2048], 0)
    assert mine["reports"] == {"words_per_s": "rate", "setup_s": "setup"}


def test_the_stack_and_the_exits_compute_what_the_hand_count_says():
    # one block of hidden 2, one head of 2, an MLP of 3: q, o 2 x 2 x 2, k,
    # v 2 x 2 x 2, the MLP 3 x 2 x 3 = 34 numbers; 2 layers x 3 passes
    c = dict(hidden_size=2, head_dim=2, num_attention_heads=1,
             num_key_value_heads=1, intermediate_size=3, num_hidden_layers=2,
             total_ut_steps=3, vocab_size=5)
    assert loop_shapes.block_weights(c) == 8 + 8 + 18
    assert loop_shapes.block_runs(c) == 6
    # 6 block runs x 7 positions x 2 x 34, four runs of every product
    assert loop_shapes.stack_flops(c, 1, 7) == 6 * 7 * 2 * 34 * 4
    # 3 products x 3 exits x 7 positions x 2 x 5 x 2
    assert loop_shapes.head_flops(c, 1, 7) == 3 * 3 * 7 * 2 * 5 * 2
    # the cell, by hand from the configuration's file
    c = _config()
    assert loop_shapes.block_weights(c) == 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert loop_shapes.block_weights(c) == 51_388_416 - 4 * 2048
    assert loop_shapes.block_runs(c) == 32
    assert loop_shapes.stack_flops(c, 1, 4096) == (
        32 * 4096 * 2 * 51_380_224 * 4) == 53_876_069_761_024
    assert loop_shapes.head_flops(c, 1, 4096) == (
        12 * 4096 * 2 * 49152 * 2048) == 9_895_604_649_984
    assert loop_shapes.block_runs(dict(c, **c["tiny"])) == 8


def _seen(filed=19.9, stack_s=10.0, head_s=2.0):
    return {"every_scope": {}, "filed_s": filed, "busy_s": 20.0,
            "stack_s": stack_s, "head_s": head_s}


@pytest.mark.parametrize("seen, want", [
    (_seen(), (50.0, 10.0, 25.0)),      # the join filed 99.5% of busy
    (_seen(filed=19.0), (None, None, None)),    # under the floor
    (_seen(stack_s=0.0), (None, 10.0, 25.0)),   # nothing under the loop
    (_seen(head_s=0.0), (50.0, None, None)),
    ({}, (None, None, None))])          # no trace, or the parent's program
def test_the_device_readers_answer_only_over_a_whole_join(seen, want):
    peak = 197e12
    ctx = {"run": {"loop_s": seen, "loop_flops": {
        "stack": 0.5 * 10.0 * peak, "head": 0.25 * 2.0 * peak}},
           "device_kind": "TPU v5 lite", "trace": {"busy_s": 20.0}}
    got = tuple(loop.read(name, ctx) for name in (
        "loop.stack_mxu_share.lm", "loop.head_device_share.lm",
        "loop.head_mxu_share.lm"))
    assert got == tuple(None if w is None else pytest.approx(w)
                        for w in want)
    assert loop.scope_seconds("no-such-cell") == {}
    # a run that hands over no operations reports no share of a roofline
    bare = dict(ctx, run={"loop_s": seen})
    assert loop.read("loop.stack_mxu_share.lm", bare) is None
    assert loop.read("loop.head_mxu_share.lm", bare) is None


def test_the_join_files_the_loops_scopes():
    """``scopes_in`` on a made-up trace and record: the stack's seconds are
    the loop's own and its blocks', every pass, less the attention cores;
    the exits' are the head's and the gate's."""
    from benchmark import trace_reduce

    class Op:
        def __init__(self, name, start, dur):
            self.name, self.text, self.start, self.dur = (
                name, f"%{name} = f32[4]{{0}} fusion()", start, dur)

    class Span:
        name, start, dur = trace_reduce.WINDOW_SPAN, 1.0, 30.0

    names = ["mv.lm.loop", "mv.lm.dense", "mv.lm.attn", "mv.lm.attn.turn"
             ":kernel", "mv.lm.attn.full:kernel", "mv.lm.norm.pre",
             "mv.lm.norm.final", "mv.lm.loop.exit", "mv.lm.head",
             "mv.lm.update"]
    ops = {"chip0": [Op(f"fusion.{i}", 1.0 + 2 * i, 1.0 + 0.125 * i)
                     for i in range(len(names))]}
    record = {"name": "xla.program", "args": {"scopes": {
        scope: {("bwd" if i % 2 else "fwd"): [[f"fusion.{i}", "f32[4]"]]}
        for i, scope in enumerate(names)}}}
    got = loop.scopes_in(ops, [Span()], [record])
    durs = [1.0 + 0.125 * i for i in range(len(names))]
    assert got["stack_s"] == pytest.approx(sum(
        durs[i] for i in (0, 1, 2, 3, 5, 6)))
    assert got["head_s"] == pytest.approx(durs[7] + durs[8])
    assert got["filed_s"] == got["busy_s"] == pytest.approx(sum(durs))
    assert got["every_scope"]["mv.lm.attn.full:kernel"] == {"fwd": durs[4]}
    # a program without the loop (the parent's, another cell's) answers
    # nothing, and so do its readers
    bare = {"name": "xla.program", "args": {"scopes": {
        "mv.lm.attn": {"fwd": [["fusion.2", "f32[4]"]]}}}}
    assert loop.scopes_in(ops, [Span()], [bare]) == {}
    assert loop.scopes_in(ops, [Span()], []) == {}
    assert loop.read("loop.head_device_share.lm", {
        "run": {"loop_s": {}}, "device_kind": "TPU v5 lite"}) is None


def test_the_entropy_share_is_read_from_the_windows_steps():
    step = lambda entropy, prof=True, **more: {
        "name": "lm.step", "prof": prof, "ts": 1.0,
        "args": dict(loop_passes=4, exit_entropy=entropy, **more)}
    events = [step(math.log(4)), step(0.5 * math.log(4)),
              step(0.0, prof=False)]
    assert loop.read_events("loop.exit_entropy_share.lm",
                            events) == pytest.approx(75.0)
    # a program that runs no loop (the parent's) says nothing
    assert loop.read_events("loop.exit_entropy_share.lm", [
        {"name": "lm.step", "prof": True, "ts": 1.0, "args": {}}]) is None
    assert loop.read_events("loop.stack_mxu_share.lm", events) is None


def test_the_cell_runs_at_tiny_sizes_and_reports_its_metrics():
    result, lines = run_cell(ROOT, CELL, seed=2147483019)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"words_per_s", "setup_s"}
    detail = json.loads(lines[-2])["detail"]
    assert detail["compiles_in_window"] == 0
    check, facts = detail["check"], detail["facts"]
    assert set(check["by_class"]) == {"plain", "vocab", "norms", "gate"}
    assert check["by_kind"]["exit"] == check["by_class"]["gate"]
    assert len(check["exit_loss"]) == len(check["exit_p_ref"]) == 4
    assert sum(check["exit_p"]) == pytest.approx(1.0, abs=1e-5)
    assert (facts["loop_passes"], facts["loop_layers"]) == (4, 2)
    assert facts["loop_block_runs"] == 8 * facts["steps"]
    assert 0 < facts["exit_entropy"] <= facts["exit_entropy_most"]
    assert 1 <= facts["exit_expected_pass"] <= 4
    assert "calibration" not in detail["setup_breakdown_s"]


def test_a_traced_tiny_run_reports_the_exits_entropy_and_no_device_share():
    """The per-layer line of a traced run: the shared metrics are there and
    the routers' are not; of its own the exits' entropy is the program's
    to say; the three device shares are the chip's to give (on the CPU the
    trace has no device line and they are left out, as the parent's would
    be)."""
    result, _ = run_cell(ROOT, CELL, trace=1, seed=2147483021)
    assert result["correct"] and result["failed"] == 0
    assert OWN & set(result["metrics"]) == {"loop.exit_entropy_share.lm"}
    assert 0 < result["metrics"]["loop.exit_entropy_share.lm"]["value"] <= 100
    assert not ROUTERS & set(result["metrics"])
    for name in ("xla.program_memory_gb.lm", "xla.lower_s.setup",
                 "lm.step_host_ms.lm", "prog.compile_s.setup"):
        assert name in result["metrics"], name


def test_the_controls_are_told_apart_at_tiny_sizes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_loop_control.py"),
         "--seed", "2147483019", "--cpu-tiny"], capture_output=True,
        text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(said["controls"]) == set(lm_train_loop.CONTROLS)
    assert not any(v["agrees"] for v in said["controls"].values())
    assert out.returncode == 0, out.stderr[-3000:]
    assert said["program"]["step_agrees"]
    # three passes in four's place are seen by the exits themselves
    assert said["controls"]["one_pass_less"]["p_mean_err_over_tol"] > 1
    # constant weights leave the forward pass as it is
    assert said["controls"]["untrained_weights"]["loss_err_over_tol"] < 1e-3
