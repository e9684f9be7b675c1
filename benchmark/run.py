"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell and
its configuration in ``BENCHMARK.json``, the traffic parameters in
``benchmark/traffic/<traffic>.json``, the driver they name in
``benchmark/drivers/``, and one reader per per-layer metric family in
``benchmark/layers/`` (the part of a metric's name before its first dot).
This file holds no list of any of them.

The last line of standard output is the result object; everything else
goes on earlier lines or on standard error. Without a TPU (or with fewer
chips than the cell asks for, or without the program beside it) it
prints no result and exits non-zero. ``--cpu-tiny`` is for
``benchmark/tests`` only: it pins the CPU and lets the configuration's
and the traffic's ``tiny`` keys shrink row counts and stream lengths,
never a width, so the same code paths run in seconds.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()     # process start, as near as Python can say

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_PROGRAM, EXIT_NO_CHIP = 3, 4


class Cell:
    """What a driver is given: the cell's data, the seed, the window's
    length, and where to put what set-up costs."""

    def __init__(self, name: str, config: Dict[str, Any],
                 traffic: Dict[str, Any], seed: int, seconds: float):
        self.name, self.config, self.traffic = name, config, traffic
        self.seed, self.seconds = seed, seconds
        self.setup_spans: Dict[str, float] = {}     # name -> seconds

    @contextlib.contextmanager
    def timed(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.setup_spans[name] = (self.setup_spans.get(name, 0.0)
                                      + time.perf_counter() - t)


def _by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def _load(path: str, tiny: bool) -> Dict[str, Any]:
    with open(os.path.join(ROOT, path)) as f:
        data = json.load(f)
    shrink = data.pop("tiny", {})
    if tiny:
        data.update(shrink)
    return data


def _metrics_of(spec: Dict, group: str, cell: str) -> List[Dict]:
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


def _summary(ms: List[float]) -> Dict[str, float]:
    ms = sorted(ms)
    return {"n": len(ms), "p50": ms[len(ms) // 2],
            "p95": ms[min(len(ms) - 1, int(0.95 * len(ms)))], "max": ms[-1]}


def _fail(code: int, why: str) -> int:
    print(f"run.py: {why}", file=sys.stderr)
    return code


def _count_compiles(sink: List[float]):
    """Collect the seconds of every XLA compilation or cache load from
    now on (there should be none inside the measured window)."""
    from jax import monitoring

    def on_event(event: str, duration: float, **_):
        if event.endswith("backend_compile_duration"):
            sink.append(duration)

    monitoring.register_event_duration_secs_listener(on_event)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="benchmark/tests only: CPU, shrunken rows")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = _by_name(spec["workloads"], args.workload, "workload")
    config_entry = _by_name(spec["configs"], entry["config"], "config")
    config = _load(config_entry["file"], args.cpu_tiny)
    traffic = _load(os.path.join(spec["paths"][0], "traffic",
                                 entry["traffic"] + ".json"), args.cpu_tiny)
    seconds = float(args.seconds if args.seconds is not None
                    else spec["run_seconds"])
    chips = int(entry["chips"])

    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    try:
        import jax
        import multiverso_tpu as mv
        from multiverso_tpu.utils.platform import enable_compile_cache
    except ImportError as e:
        return _fail(EXIT_NO_PROGRAM, f"the program is not beside the "
                                      f"benchmark ({e}); no result")
    if args.cpu_tiny:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        # <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR places it;
        # cache every program, however quick, so that a second run of a
        # cell compiles nothing
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return _fail(EXIT_NO_CHIP, f"JAX found no backend ({e}); no result")
    platform = devices[0].platform
    if not args.cpu_tiny and (platform != "tpu" or len(devices) < chips):
        return _fail(EXIT_NO_CHIP,
                     f"cell {args.workload!r} needs {chips} TPU chip(s); JAX "
                     f"found {len(devices)} {platform} device(s); no result")

    cell = Cell(args.workload, config, traffic, args.seed, seconds)
    driver = importlib.import_module(
        f"{spec['paths'][0]}.drivers.{traffic['driver']}")
    compiles: List[float] = []
    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    cell.setup_spans["start_to_devices"] = time.perf_counter() - _T0
    _count_compiles(compiles)
    try:
        mv.init()
        state = driver.setup(cell)          # builds, warms every shape
        in_setup = (len(compiles), sum(compiles))
        del compiles[:]
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        # everything set-up built is long-lived: move it out of the
        # collector's sight, so that no full collection (about 0.1 s over a
        # million module objects) lands in one window in three
        gc.collect()
        gc.freeze()
        setup_seconds = time.perf_counter() - _T0
        cpu0 = time.process_time()
        with jax.profiler.TraceAnnotation("bench.window"):
            run = driver.window(state, seconds)
        cpu_seconds = time.process_time() - cpu0
        if args.trace:
            jax.profiler.stop_trace()
        in_window = len(compiles)
        # the peak of set-up and window; a check after the window may hold
        # copies of the tables, which are no part of the cell
        peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devices[:chips])
        t_check = time.perf_counter()
        verdict = driver.check(state, run)
        check_seconds = time.perf_counter() - t_check
    finally:
        mv.shutdown()

    quantities = {"rate": run["work"] / run["elapsed_s"],
                  "setup": setup_seconds}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    result: Dict[str, Any] = {
        "correct": bool(verdict["correct"]),
        "attempted": int(run["attempted"]), "failed": int(run["failed"]),
        "metrics": {}, "device": device}
    # for a reader of the log; the last line holds the contract's keys only
    print(json.dumps({"detail": {
        "workload": args.workload, "seed": args.seed,
        "window_s": run["elapsed_s"], "work": run["work"],
        "window_cpu_s": cpu_seconds, "compiles_in_window": in_window,
        "programs_in_setup": {"count": in_setup[0], "seconds": in_setup[1]},
        "check": verdict.get("detail", {}), "check_s": check_seconds,
        "setup_breakdown_s": cell.setup_spans,
        "spans_ms": {k: _summary(v)
                     for k, v in run.get("spans_ms", {}).items()},
        "facts": run.get("facts", {})}}))
    if in_window:
        print(f"run.py: {in_window} compilation(s) inside the measured "
              f"window", file=sys.stderr)

    if not args.trace:
        for m in _metrics_of(spec, "end_to_end", args.workload):
            result["metrics"][m["name"]] = {
                "value": quantities[traffic["reports"][m["name"]]],
                "unit": m["unit"]}
    else:
        from benchmark import trace_reduce

        device_ops, host_spans = trace_reduce.read_xplane(
            trace_reduce.find_xplane(trace_dir))
        reduction = trace_reduce.reduce(
            device_ops, host_spans, run.get("table_shapes", ()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
        ctx = {"cell": cell, "run": run, "trace": reduction,
               "device_kind": devices[0].device_kind}
        for m in _metrics_of(spec, "per_layer", args.workload):
            reader = importlib.import_module(
                f"{spec['paths'][0]}.layers.{m['name'].split('.')[0]}")
            value = reader.read(m["name"], ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
