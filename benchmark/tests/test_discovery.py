"""A later PR adds a cell, a configuration, a traffic driver and a
per-layer reader as new files plus entries in BENCHMARK.json, and edits
no file that is there: run.py finds all four by name."""

import json
import os
import shutil

from conftest import ROOT, run_cell

DRIVER = '''
import time
import jax, jax.numpy as jnp

def setup(cell):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((cell.config["width"], cell.config["width"]))
    f(x).block_until_ready()
    return {"f": f, "x": x, "per": cell.traffic["per_step"]}

def window(state, seconds):
    t0 = now = time.perf_counter(); n = 0
    while now - t0 < seconds:
        with jax.profiler.TraceAnnotation("bench.probe"):
            state["f"](state["x"]).block_until_ready()
        n += 1; now = time.perf_counter()
    return {"work": n * state["per"], "elapsed_s": now - t0, "attempted": n,
            "failed": 0, "counts": {"probes": n}}

def check(state, run):
    return {"correct": float(state["f"](state["x"])) == 8.0 ** 3,
            "detail": {}}
'''

READER = '''
def read(name, ctx):
    return float(ctx["run"]["counts"]["probes"])
'''


def test_new_files_are_found_without_an_edit(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "multiverso_tpu"),
               tmp_path / "multiverso_tpu")
    before = {p: open(os.path.join(d, p), "rb").read()
              for d, _, fs in os.walk(tmp_path / "benchmark") for p in fs}
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = tmp_path / "benchmark"
    (bench / "configs" / "probe-w8.json").write_text(json.dumps(
        {"name": "probe-w8", "source": "none: a test", "width": 8,
         "reduced": {}, "assumed": {}}))
    (bench / "traffic" / "probe-loop.json").write_text(json.dumps(
        {"driver": "probe_driver", "per_step": 3,
         "reports": {"probes_per_s": "rate", "setup_s": "setup"}}))
    (bench / "drivers" / "probe_driver.py").write_text(DRIVER)
    (bench / "layers" / "probecount.py").write_text(READER)
    spec["configs"].append(
        {"name": "probe-w8", "source": "none: a test", "reduced": [],
         "file": "benchmark/configs/probe-w8.json", "why": "test"})
    spec["workloads"].append(
        {"name": "probe-cell", "config": "probe-w8", "traffic": "probe-loop",
         "chips": 1, "why": "test"})
    spec["end_to_end"].append(
        {"name": "probes_per_s", "unit": "probes/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": ["probe-cell"]})
    spec["per_layer"].append(
        {"name": "probecount.steps", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "probe",
         "moves": "probes_per_s", "workloads": ["probe-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    result, _ = run_cell(str(tmp_path), "probe-cell", trace=0, seconds=0.3)
    assert result["correct"] and set(result["metrics"]) == {"probes_per_s",
                                                            "setup_s"}
    assert result["metrics"]["probes_per_s"]["unit"] == "probes/s"
    traced, _ = run_cell(str(tmp_path), "probe-cell", trace=1, seconds=0.3)
    assert set(traced["metrics"]) == {"probecount.steps"}
    assert traced["metrics"]["probecount.steps"]["value"] == traced["attempted"]
    after = {p: open(os.path.join(d, p), "rb").read()
             for d, _, fs in os.walk(bench) for p in fs
             if "__pycache__" not in d}
    assert all(after[p] == before[p] for p in before)     # no file edited
