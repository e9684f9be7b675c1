"""The controls of ``granite4h-train-8k``'s comparison: is a step whose scan
keeps its sums in the precision below the configuration's, or drops the
state between chunks, or leaves one published multiplier out, told apart
from the program's? ``lm_control.py``'s procedure for the tenth
language-model cell (that file names its cell and driver, and a PR that
adds a cell edits no file the benchmark has).

    python3 benchmark/lm_granite_control.py --seed <n> [--cpu-tiny]
        [--controls a,b]

Sets the cell up as ``run.py`` does and makes ``drivers/lm_train_ssm``'s
comparison once, with the float32 reference computed as each faulty program
would put in the measured step's place beside the measured step itself
(``lm_train_ssm.CONTROLS``, ``reference/granite_h.control``): the scan's
state and running sums kept in bfloat16 (``sums_bfloat16``: the precision
below the configuration's, where it tells), the scan without the state one
chunk of 256 hands the next (``no_carry``), ``residual_multiplier`` taken as
1 (``residual_1``), the scores over ``sqrt(64)`` and not times
``attention_multiplier`` (``softmax_sqrt``), ``logits_scaling`` left out
(``logits_unscaled``). Prints what the comparison says of each as one JSON
line, and exits 0 only if the program agrees and no control does. No window
is run and nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "granite4h-train-8k"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-tiny", action="store_true")
    ap.add_argument("--controls", default=None,
                    help="names of lm_train_ssm.CONTROLS, comma-separated "
                         "(default: all)")
    args = ap.parse_args(argv)
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    import multiverso_tpu as mv
    from multiverso_tpu.utils.platform import enable_compile_cache

    from benchmark.drivers import lm_train_ssm
    from benchmark.lm_control import _load
    from benchmark.run import Cell

    if args.cpu_tiny:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        enable_compile_cache()
    names = (tuple(args.controls.split(",")) if args.controls
             else tuple(lm_train_ssm.CONTROLS))
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
        state = lm_train_ssm.setup(cell, controls=names)
        state["trainer"].adopt()
    finally:
        mv.shutdown()
    program = state["verdict"]
    faulty = program.pop("controls")
    print(json.dumps({"seed": args.seed, "program": program,
                      "controls": faulty,
                      "setup_breakdown_s": cell.setup_spans}))
    return 0 if program["step_agrees"] and not any(
        v["agrees"] for v in faulty.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
