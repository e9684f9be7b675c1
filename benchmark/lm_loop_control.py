"""The controls of ``ouro-train-4k``'s comparison: is a step computed in the
precision below the configuration's, or with the loop or its exits computed
wrongly, told apart from the program's? ``lm_control.py``'s procedure for
the ninth language-model cell (that file names its cell and driver, and a
PR that adds a cell edits no file the benchmark has).

    python3 benchmark/lm_loop_control.py --seed <n> [--cpu-tiny]
        [--controls a,b]

Sets the cell up as ``run.py`` does and makes ``drivers/lm_train_loop``'s
comparison once, with the float32 reference computed as each faulty program
would put in the measured step's place beside the measured step itself
(``lm_train_loop.CONTROLS``): every product's operands in float8_e4m3
(``operands_float8``: the precision below the configuration's), three
passes in the place of four (``one_pass_less``), the next pass fed the
stream as the blocks left it and not ``N_f`` of it (``no_renorm``), the
exit distribution held constant where it weighs the cross-entropies
(``untrained_weights``). Prints what the comparison says of each as one
JSON line, and exits 0 only if the program agrees and no control does. No
window is run and nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ouro-train-4k"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-tiny", action="store_true")
    ap.add_argument("--controls", default=None,
                    help="names of lm_train_loop.CONTROLS, comma-separated "
                         "(default: all)")
    args = ap.parse_args(argv)
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    import multiverso_tpu as mv
    from multiverso_tpu.utils.platform import enable_compile_cache

    from benchmark.drivers import lm_train_loop
    from benchmark.lm_control import _load
    from benchmark.run import Cell

    if args.cpu_tiny:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        enable_compile_cache()
    names = (tuple(args.controls.split(",")) if args.controls
             else tuple(lm_train_loop.CONTROLS))
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
        state = lm_train_loop.setup(cell, controls=names)
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
