"""CPU tests of the harness itself (not tier-1): ``pytest benchmark/tests``.

Cells run through ``run.py --cpu-tiny`` in a child process, so nothing
here touches an accelerator and the parent never initialises JAX for a
cell."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def run_cell(root, workload, trace=0, seconds=1.0, seed=3000000019):
    """``run.py --cpu-tiny`` for one cell; returns (result, stdout lines)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace), "--cpu-tiny"],
        capture_output=True, text=True, timeout=900, env=env, cwd=root)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines
