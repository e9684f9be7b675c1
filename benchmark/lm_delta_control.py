"""The controls of ``qwen3next-train-16k``'s comparison: is a step
computed in the precision below the configuration's, with a faulty delta
rule or with rotary over a whole head, told apart from the program's?
``lm_control.py``'s procedure for the seventh language-model cell (that
file names its cell and driver, and a PR that adds a cell edits no file the
benchmark has).

    python3 benchmark/lm_delta_control.py --seed <n> [--cpu-tiny]

Sets the cell up as ``run.py`` does and makes ``drivers/lm_train_delta``'s
comparison once, with the float32 reference computed as each faulty program
would put in the measured step's place beside the measured step itself
(``lm_train_delta.CONTROLS``): the rule without the state one chunk hands the
next (``no_carry``), without its correction inside a chunk (``no_correction``:
``T`` left out), the attention with rotary over all 256 of a head
(``rope_whole``), and every product's operands in float8_e4m3
(``operands_float8``: the precision below the configuration's). Prints what
the comparison says of each as one JSON line, and exits 0 only if the program
agrees and no control does. No window is run and nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "qwen3next-train-16k"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-tiny", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    import multiverso_tpu as mv
    from multiverso_tpu.utils.platform import enable_compile_cache

    from benchmark.drivers import lm_train_delta
    from benchmark.lm_control import _load
    from benchmark.run import Cell

    if args.cpu_tiny:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        enable_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    config = next(c for c in spec["configs"] if c["name"] == entry["config"])
    cell = Cell(CELL, _load(config["file"], args.cpu_tiny),
                _load(os.path.join(spec["paths"][0], "traffic",
                                   entry["traffic"] + ".json"),
                      args.cpu_tiny), args.seed, 0.0)
    mv.init()
    try:
        state = lm_train_delta.setup(cell, controls=tuple(lm_train_delta.CONTROLS))
        state["trainer"].adopt()
    finally:
        mv.shutdown()
    program = state["verdict"]
    faulty = program.pop("controls")
    print(json.dumps({"seed": args.seed, "program": program,
                      "controls": faulty,
                      "calibration": state["calibration"],
                      "setup_breakdown_s": cell.setup_spans}))
    return 0 if program["step_agrees"] and not any(
        v["agrees"] for v in faulty.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
