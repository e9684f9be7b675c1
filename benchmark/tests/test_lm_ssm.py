"""The tenth language-model cell (``granite4h-train-8k``): the cell and its
configuration found by NAME with every metric the cell reports, the
configuration as the published one but for its depth and its vocabulary's
slice, the traffic as ``lm-train-8k``'s load without a calibration, what
the mixers' scans and projections and the MLPs compute against hand counts
(``ssm_shapes``, ``ssmblock_shapes``), the readers of ``layers/ssm`` and
``layers/ffn`` on made-up sums, and the comparison's controls at
``--cpu-tiny`` sizes (``lm_granite_control.py``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import ssm_shapes, ssmblock_shapes
from benchmark.drivers import lm_train_ssm
from benchmark.layers import ffn, ssm
from conftest import ROOT, run_cell

CELL = "granite4h-train-8k"
CONFIG = "granite-4.0-h-micro-pp4"
OWN = {"ssm.mixer_device_share.lm", "ssm.scan_device_share.lm",
       "ssm.scan_roofline_share.lm", "ssm.proj_mxu_share.lm",
       "ssm.kernel_layers_share.lm", "ffn.dense_device_share.lm",
       "ffn.dense_mxu_share.lm"}
# the shared metrics a language-model cell without a router reports
SHARED = {"prog.table_init_s.setup", "prog.compile_s.setup",
          "device.idle_share.lm", "lm.step_host_ms.lm",
          "attn.device_share.lm", "attnmix.full_device_share.lm",
          "attnmix.full_mxu_share.lm", "devline.starved_share.lm",
          "devline.unfiled_idle_share.lm", "devline.run_max_over_p50.lm",
          "xla.scoped_ops_share.lm", "xla.program_memory_gb.lm",
          "xla.temp_memory_gb.lm", "xla.lower_s.setup"}


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cell_is_found_by_name_and_lists_every_metric_it_reports():
    spec = _spec()
    entry, = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "lm-train-8k-ssm", 1)
    assert "no router" in entry["why"] and len(entry["why"]) <= 200
    config, = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["source"] == _config()["source"]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    # no other cell runs the configuration, and no other configuration
    # the file
    assert [w["name"] for w in spec["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert [c["name"] for c in spec["configs"]
            if c["file"] == config["file"]] == [CONFIG]
    mine = {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == OWN | SHARED
    # no metric of a router's, and none without a list
    assert not [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    assert not [n for n in mine if n.startswith(("moe.", "counts."))]
    for m in spec["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "words_per_s"
            assert m["unit"] == "%"
            assert m["layer"].startswith(
                "state-space mixer" if m["name"].startswith("ssm.")
                else "dense feed-forward")
            assert m["source"] == ("program_counter"
                                   if "kernel_layers" in m["name"]
                                   else "device_trace")
            assert m["better"] == ("lower" if "device_share" in m["name"]
                                   else "higher")
    assert CELL in next(m for m in spec["end_to_end"]
                        if m["name"] == "words_per_s")["workloads"]
    # every reader the cell's metrics name is there to be found
    for family in {m.split(".")[0] for m in mine}:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layers", family + ".py"))
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)


def test_the_configuration_is_the_published_one_but_for_depth_and_slice():
    c = _config()
    assert set(c["reduced"]) == set(c["published"]) == {
        "num_hidden_layers", "vocab_size"}
    assert c["published"] == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert (c["num_hidden_layers"], c["vocab_size"]) == (10, 12544)
    assert c["vocab_size"] * 8 == 100352 and c["vocab_size"] % 128 == 0
    # ONE whole period of the published pattern, nine to one
    run = c["layer_types"][:c["num_hidden_layers"]]
    assert len(c["layer_types"]) == 40
    assert run == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert c["layer_types"] == run * 4
    # no width differs from the source, nor a multiplier
    assert (c["hidden_size"], c["mamba_n_heads"], c["mamba_d_head"],
            c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"],
            c["mamba_chunk_size"], c["mamba_expand"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["shared_intermediate_size"], c["intermediate_size"],
            c["num_local_experts"], c["rms_norm_eps"]) == (
                2048, 64, 64, 128, 1, 4, 256, 2, 32, 8, 8192, 8192, 0, 1e-5)
    assert (c["embedding_multiplier"], c["residual_multiplier"],
            c["attention_multiplier"], c["logits_scaling"],
            c["tie_word_embeddings"], c["position_embedding_type"]) == (
                12, 0.22, 0.015625, 8, True, "nope")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")
        assert row["source_url"] == c["source"]
        differ = {k for k, v in row["config"].items() if c.get(k) != v}
        assert differ == set(c["reduced"])
    for key in ("source", "assumed", "deployment", "tiny", "parameters",
                "kept_unused"):
        assert c[key]
    for key in ("initializer_range", "conv_w", "first_values_of_the_scan",
                "optimizer", "learning_rate", "mlp", "gated_norm",
                "document_mask", "compute_precision"):
        assert c["assumed"][key]
    for key in ("rope_theta", "rope_scaling", "num_experts_per_tok",
                "max_position_embeddings", "mamba_proj_bias",
                "attention_bias"):
        assert c["kept_unused"][key]
    assert "stage 0 of 4" in c["deployment"]
    assert "shared by eight chips" in c["deployment"]
    # the tiny sizes shrink row counts alone
    assert set(c["tiny"]) == {"num_hidden_layers", "layer_types",
                              "vocab_size"}
    assert "attention" in c["tiny"]["layer_types"][:c["tiny"][
        "num_hidden_layers"]]


def test_the_parameters_are_the_programs_count():
    """The file's arithmetic, from ``param_shapes``."""
    import numpy as np
    from multiverso_tpu.models import mla_moe

    c = _config()
    c.pop("tiny")
    cfg = lm_train_ssm._model_config(type("C", (), {"config": c}))
    shapes = mla_moe.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == c[
        "parameters"]["total"] == 772_160_448
    assert 9 * 76_182_976 + 60_821_504 + 12_544 * 2048 + 2048 == 772_160_448
    assert len(shapes) == c["parameters"]["tables"] == 128
    assert {lm_train_ssm.table_class(n) for n in shapes} == {
        "plain", "vocab", "norms", "scan"}
    assert [n for n in shapes
            if lm_train_ssm.table_class(n) == "vocab"] == ["embed"]
    assert sum(lm_train_ssm.table_class(n) == "scan"
               for n in shapes) == 9 * 5
    assert sum(lm_train_ssm.table_class(n) == "norms"
               for n in shapes) == 9 * 3 + 2 + 1
    assert set(lm_train_ssm.CONTROLS) == {
        "sums_bfloat16", "no_carry", "residual_1", "softmax_sqrt",
        "logits_unscaled"}
    for bad in (dict(c, position_embedding_type="rope"),
                dict(c, tie_word_embeddings=False),
                dict(c, num_local_experts=8), dict(c, mamba_expand=3)):
        with pytest.raises(ValueError):
            lm_train_ssm._model_config(type("C", (), {"config": bad}))


def test_the_traffic_is_lm_train_8ks_load_without_a_calibration():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            return json.load(f)

    base, mine = load("lm-train-8k.json"), load("lm-train-8k-ssm.json")
    assert set(base) - set(mine) == {"calibration"}
    assert {k for k in mine if base[k] != mine[k]} <= {
        "driver", "why", "tiny", "sequences", "positions"}
    assert mine["driver"] == "lm_train_ssm"
    assert (mine["sequences"], mine["positions"], mine["batch_pool"],
            mine["zipf_a"], mine["document_tokens"],
            mine["end_of_document_id"]) == (1, 8192, 16, 1.1, [64, 2048], 0)
    assert mine["reports"] == {"words_per_s": "rate", "setup_s": "setup"}
    assert mine["positions"] % _config()["mamba_chunk_size"] == 0


def test_the_mixers_and_the_mlps_compute_what_the_hand_count_says():
    # hidden 2, 2 heads of 3 (inner 6), 1 group, a state of 5, an MLP of
    # 7; 3 layers run of which 2 mixers
    c = dict(hidden_size=2, mamba_n_heads=2, mamba_d_head=3,
             mamba_n_groups=1, mamba_d_state=5, shared_intermediate_size=7,
             num_hidden_layers=3,
             layer_types=["mamba", "attention", "mamba", "mamba"])
    assert ssmblock_shapes.mixers(c) == 2
    # [z | xBC | dt] 2 x (6 + 6 + 10 + 2), the out-projection 6 x 2
    assert ssmblock_shapes.proj_weights(c) == 2 * 24 + 12
    assert ssmblock_shapes.proj_flops(c, 1, 11) == 2 * 11 * 2 * 60 * 3
    assert ssmblock_shapes.dense_flops(c, 1, 11) == 3 * 11 * 2 * 3 * 14 * 3
    # the cell, by hand from the configuration's file
    c = _config()
    assert ssmblock_shapes.mixers(c) == 9
    assert ssmblock_shapes.proj_weights(c) == 17_432_576 + 8_388_608
    assert ssmblock_shapes.proj_flops(c, 1, 8192) == (
        9 * 8192 * 2 * 25_821_184 * 3) == 11_422_465_523_712
    assert ssmblock_shapes.dense_flops(c, 1, 8192) == (
        10 * 8192 * 2 * 50_331_648 * 3) == 24_739_011_624_960
    # a chunk of 256: 32,896 live pairs; C B^T once for the ONE group
    a_chunk = (2 * 128 * 32_896 + 2 * 64 * 32_896 * 64
               + 2 * 2 * 256 * 128 * 64 * 64)
    assert ssm_shapes.scan_flops(1, 8192, 64, 64, 1, 128, 256) == (
        3 * 32 * a_chunk) == 78_218_526_720
    assert ssm_shapes.scan_bytes(1, 8192, 64, 64, 1, 128) == 3 * (
        8192 * (4096 + 256) * 2 + 8192 * 64 * 4 + 8192 * 4096 * 2)
    # bytes bind the scan's roofline on a v5e: 0.51 ms against 0.40
    assert (ssm_shapes.scan_bytes(1, 8192, 64, 64, 1, 128) / 819e9
            > ssm_shapes.scan_flops(1, 8192, 64, 64, 1, 128, 256) / 197e12)
    # the needed forward products a token (LM_SSM.md): the MLPs and the
    # mixers are the step
    parts = ssmblock_shapes.forward_flops_token(c, 8192)
    assert parts == {"mlp": 1_006_632_960, "mixer_proj": 464_781_312,
                     "scan": 28_644_480, "attention_proj": 20_971_520,
                     "attention_core": 33_554_432, "head": 51_380_224}
    assert parts["mlp"] + parts["mixer_proj"] > 0.9 * sum(parts.values())


def _seen(filed=19.9, mixer_s=8.0, scan_s=2.0, kernel_s=1.0, proj_s=4.0,
          dense_s=10.0):
    return {"every_scope": {}, "filed_s": filed, "busy_s": 20.0,
            "mixer_s": mixer_s, "scan_s": scan_s, "kernel_s": kernel_s,
            "proj_s": proj_s, "dense_s": dense_s}


NAMES = ("ssm.mixer_device_share.lm", "ssm.scan_device_share.lm",
         "ssm.scan_roofline_share.lm", "ssm.proj_mxu_share.lm",
         "ffn.dense_device_share.lm", "ffn.dense_mxu_share.lm")


@pytest.mark.parametrize("seen, want", [
    # the join filed 99.5% of busy; the scan's bytes bind its roofline
    (_seen(), (40.0, 10.0, 30.0, 50.0, 50.0, 60.0)),
    (_seen(filed=19.0), (None,) * 6),           # under the floor
    (_seen(kernel_s=0.0), (40.0, 10.0, None, 50.0, 50.0, 60.0)),  # plain
    (_seen(dense_s=0.0), (40.0, 10.0, 30.0, 50.0, None, None)),
    (_seen(mixer_s=0.0), (None, None, None, None, 50.0, 60.0)),
    ({}, (None,) * 6)])             # no trace, or the parent's program
def test_the_device_readers_answer_only_over_a_whole_join(seen, want):
    flops, hbm = 197e12, 819e9
    ctx = {"run": {"ssm_s": seen, "ssm_work": {
        "steps": 40, "scan_flops": 0.1 * 1.0 * flops,
        "scan_bytes": 0.3 * 1.0 * hbm, "proj_flops": 0.5 * 4.0 * flops,
        "dense_flops": 0.6 * 10.0 * flops}},
           "device_kind": "TPU v5 lite", "trace": {"busy_s": 20.0}}
    read = lambda name, ctx: (ssm if name.startswith("ssm.")
                              else ffn).read(name, ctx)
    got = tuple(read(name, ctx) for name in NAMES)
    assert got == tuple(None if w is None else pytest.approx(w)
                        for w in want)
    assert ssm.scope_seconds("no-such-cell") == {}
    # a run that hands over no work reports no share of a roofline or peak
    bare = dict(ctx, run={"ssm_s": seen})
    for name in ("ssm.scan_roofline_share.lm", "ssm.proj_mxu_share.lm",
                 "ffn.dense_mxu_share.lm"):
        assert read(name, bare) is None
    # where the operations bind, they are the roofline
    ctx["run"]["ssm_work"]["scan_flops"] = 0.45 * flops
    if seen and seen["filed_s"] > 19.5 and seen["kernel_s"] and seen[
            "mixer_s"]:
        assert ssm.read("ssm.scan_roofline_share.lm",
                        ctx) == pytest.approx(45.0)


def test_the_join_files_the_mixers_scopes():
    """``scopes_in`` on a made-up trace and record: the mixer's seconds are
    ``mv.lm.ssm`` and its children's, every pass; the projections' are the
    scope itself; the kernels' are ``mv.lm.ssm.scan:kernel``."""
    from benchmark import trace_reduce

    class Op:
        def __init__(self, name, start, dur):
            self.name, self.text, self.start, self.dur = (
                name, f"%{name} = f32[4]{{0}} fusion()", start, dur)

    class Span:
        name, start, dur = trace_reduce.WINDOW_SPAN, 1.0, 30.0

    names = ["mv.lm.ssm", "mv.lm.ssm.conv:kernel", "mv.lm.ssm.scan",
             "mv.lm.ssm.scan:kernel", "mv.lm.ssm.norm", "mv.lm.dense",
             "mv.lm.attn", "mv.lm.attn.full:kernel", "mv.lm.norm.pre",
             "mv.lm.head"]
    ops = {"chip0": [Op(f"fusion.{i}", 1.0 + 2 * i, 1.0 + 0.125 * i)
                     for i in range(len(names))]}
    record = {"name": "xla.program", "args": {"scopes": {
        scope: {("bwd" if i % 2 else "fwd"): [[f"fusion.{i}", "f32[4]"]]}
        for i, scope in enumerate(names)}}}
    got = ssm.scopes_in(ops, [Span()], [record])
    durs = [1.0 + 0.125 * i for i in range(len(names))]
    assert got["mixer_s"] == pytest.approx(sum(durs[:5]))
    assert got["scan_s"] == pytest.approx(durs[2] + durs[3])
    assert got["kernel_s"] == pytest.approx(durs[3])
    assert got["proj_s"] == pytest.approx(durs[0])
    assert got["dense_s"] == pytest.approx(durs[5])
    assert got["filed_s"] == got["busy_s"] == pytest.approx(sum(durs))
    # a program without a state-space mixer (another cell's) answers
    # nothing, and so do its readers
    bare = {"name": "xla.program", "args": {"scopes": {
        "mv.lm.dense": {"fwd": [["fusion.5", "f32[4]"]]}}}}
    assert ssm.scopes_in(ops, [Span()], [bare]) == {}
    assert ssm.scopes_in(ops, [Span()], []) == {}
    for name in NAMES:
        reader = ssm if name.startswith("ssm.") else ffn
        assert reader.read(name, {"run": {"ssm_s": {}},
                                  "device_kind": "TPU v5 lite"}) is None
        # a cell whose driver hands nothing (every other cell's)
        assert reader.read(name, {"run": {},
                                  "device_kind": "TPU v5 lite"}) is None


def test_the_kernel_layers_share_is_read_from_the_windows_steps():
    step = lambda kernels, prof=True, **more: {
        "name": "lm.step", "prof": prof, "ts": 1.0,
        "args": dict(ssm_layers=9, ssd_kernel_layers=kernels, **more)}
    assert ssm.read_events("ssm.kernel_layers_share.lm", [
        step(9), step(9), step(0, prof=False)]) == pytest.approx(100.0)
    assert ssm.read_events("ssm.kernel_layers_share.lm", [
        step(0, ssd_kernel_why="no TPU")]) == 0.0
    # a program without the counts (the parent of the PR that brought the
    # scan's kernels, another cell's) says nothing
    assert ssm.read_events("ssm.kernel_layers_share.lm", [
        {"name": "lm.step", "prof": True, "ts": 1.0, "args": {}}]) is None
    assert ssm.read_events("ssm.mixer_device_share.lm", [step(9)]) is None


def test_the_cell_runs_at_tiny_sizes_and_reports_its_metrics():
    result, lines = run_cell(ROOT, CELL, seed=2147483019)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"words_per_s", "setup_s"}
    detail = json.loads(lines[-2])["detail"]
    assert detail["compiles_in_window"] == 0
    check, facts = detail["check"], detail["facts"]
    assert set(check["by_class"]) == {"plain", "vocab", "norms", "scan"}
    assert check["tables"] == 2 + 2 * 13 + 9
    assert (facts["ssm_layers"], facts["attention_layers"],
            facts["dense_layers"]) == (2, 1, 3)
    assert "calibration" not in detail["setup_breakdown_s"]


def test_a_traced_tiny_run_reports_the_plain_form_and_no_device_share():
    """The per-layer line of a traced run: the shared metrics are there and
    the routers' are not; of its own the kernels' share of the mixers is
    the program's to say (0 on the CPU: the plain form runs); the device
    shares are the chip's to give (on the CPU the trace has no device line
    and they are left out, as the parent's would be)."""
    result, _ = run_cell(ROOT, CELL, trace=1, seed=2147483021)
    assert result["correct"] and result["failed"] == 0
    assert OWN & set(result["metrics"]) == {"ssm.kernel_layers_share.lm"}
    assert result["metrics"]["ssm.kernel_layers_share.lm"]["value"] == 0.0
    assert set(result["metrics"]) <= OWN | SHARED
    for name in ("xla.program_memory_gb.lm", "xla.lower_s.setup",
                 "lm.step_host_ms.lm", "prog.compile_s.setup"):
        assert name in result["metrics"], name


def test_the_controls_are_told_apart_at_tiny_sizes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark",
                                      "lm_granite_control.py"),
         "--seed", "2147483019", "--cpu-tiny"], capture_output=True,
        text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(said["controls"]) == set(lm_train_ssm.CONTROLS)
    # at tiny sizes (three layers, chunks of 256 over 512 positions, float32
    # products on the CPU) the multipliers and the dropped state are told
    # apart; the sums' precision is the chip's to tell
    for how in ("no_carry", "residual_1", "softmax_sqrt", "logits_unscaled"):
        assert not said["controls"][how]["agrees"], how
    assert said["program"]["step_agrees"]
