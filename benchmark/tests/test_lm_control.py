"""The language-model cell's comparison tells a step in the precision
below the configuration's from the program's, at ``--cpu-tiny`` sizes
(``benchmark/lm_control.py``; the chip's readings are in ``LM.md``)."""

import json
import os
import subprocess
import sys

from conftest import ROOT


def test_the_float8_step_in_the_programs_place_does_not_agree():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "lm_control.py"),
         "--seed", "3000000019", "--cpu-tiny"],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    said = json.loads(out.stdout.strip().splitlines()[-1])
    program, control = said["program"], said["control"]
    assert program["step_agrees"] and not control["agrees"]
    assert control["count_identities"]
    # the control fails by the gradients' limits, which is what they are for
    assert max(control["grad_norm_err_over_tol"],
               control["grad_elem_err_over_tol"]) > 1.0
    for key in ("loss_err_over_tol", "grad_norm_err_over_tol",
                "grad_elem_err_over_tol", "count_err_over_tol",
                "move_err_over_tol"):
        assert program[key] <= 1.0, (key, program[key])
