"""The controls of ``xing4-train-4k``'s comparison: is a step computed in
the precision below the configuration's, or with one of the stream maps
computed wrongly, told apart from the program's? ``lm_control.py``'s
procedure for the eighth language-model cell (that file names its cell and
driver, and a PR that adds a cell edits no file the benchmark has).

    python3 benchmark/lm_hc_control.py --seed <n> [--cpu-tiny]

Sets the cell up as ``run.py`` does and makes ``drivers/lm_train_hc``'s
comparison once, with the float32 reference computed as each faulty program
would put in the measured step's place beside the measured step itself
(``lm_train_hc.CONTROLS``): every product's operands of attention and the
feed-forward in float8_e4m3 (``operands_float8``: the precision below the
configuration's), the maps without their input-dependent part
(``static_maps``: alpha = 0), ``H_res`` a row softmax, one normalisation
(``no_sinkhorn``), ``H_post`` without its factor 2 (``post_unscaled``).
Prints what the comparison says of each as one JSON line (with
``map_spread``: what the draw of the hyper-connections' tables gives on the
batch), and exits 0 only if the program agrees and no control does. No window
is run and nothing is timed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "xing4-train-4k"


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

    from benchmark.drivers import lm_train_hc
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
        state = lm_train_hc.setup(cell, controls=tuple(lm_train_hc.CONTROLS))
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
