#!/usr/bin/env python
"""mvprof — per-step critical-path report over a run's span files.

A training loop's iteration is a **step** span (the count ``step=1``)
and what it does inside are **phase** spans; with ``metrics_dir`` set
they land in ``trace-rank<r>.jsonl`` like every other span
(``multiverso_tpu/telemetry/trace.py``). This tool is the read side:
``trace.step_report`` over each file, rendered. Point it at the metrics
directory (or explicit files):

    python tools/mvprof.py DIR_OR_FILES... [--json]

It prints, per rank:

* the per-step table — wall, top (critical-path) phase, stall %,
  overlap credit, compile count — and which phase won the critical
  path across steps (the "prepare dominates block" headline, measured
  instead of inferred);
* a stall-fraction histogram (how much wall time NO span claimed,
  bucketed across steps);
* the recompile table: every step an ``xla.compile`` record ended
  inside, with the functions compiled — a silent mid-run recompile
  names its step and its function;
* the SPMD compile-hygiene reports found beside the span files
  (``compile-hygiene-rank<r>.json``).

For a timeline, ``tools/dump_metrics.py to-perfetto`` wraps the same
span file: steps and phases lie in it beside the request spans.

Exit status: 0 with output, 1 when no step span was found.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _load_jsonl(path: str) -> List[Dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    except OSError:
        return []
    return out


def collect(paths: List[str]) -> List[Dict]:
    """Step reports (``trace.step_report``) from directories and/or
    explicit span files, by rank and start. A directory contributes
    every ``trace-rank*.jsonl`` under it; each file is one process's
    spans and is read on its own (span ids are a process's)."""
    from multiverso_tpu.telemetry.trace import step_report
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            files += sorted(glob.glob(os.path.join(p, "trace-rank*.jsonl")))
        elif "compile-hygiene" not in os.path.basename(p):
            files.append(p)
    steps: List[Dict] = []
    for f in files:
        steps += step_report([r for r in _load_jsonl(f)
                              if "ph" in r and "ts" in r])
    steps.sort(key=lambda r: (r["rank"], r["ts"]))
    return steps


def collect_hygiene(paths: List[str]) -> List[Dict]:
    """SPMD compile-hygiene reports (``compile-hygiene-rank<r>.json``,
    written by ``devstats.dump_hygiene`` — tools/bench_scale.py dumps
    one per run) from directories and/or explicit files."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            files += sorted(glob.glob(
                os.path.join(p, "compile-hygiene-rank*.json")))
        elif "compile-hygiene" in os.path.basename(p):
            files.append(p)
    out: List[Dict] = []
    for f in files:
        try:
            with open(f) as fh:
                rep = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(rep, dict) and "findings" in rep:
            rep.setdefault("_file", os.path.basename(f))
            out.append(rep)
    return out


def render_hygiene(reports: List[Dict]) -> str:
    """Compile-hygiene section: per rank the checked-scope log and
    every classified SPMD finding (clean reports say so explicitly —
    a silent section reads as 'not checked', which is the opposite)."""
    lines = []
    for rep in reports:
        head = (f"compile hygiene rank {rep.get('rank', '?')}: "
                + ("CLEAN" if rep.get("clean") else
                   f"{len(rep.get('findings') or [])} FINDING(S)")
                + f"  ({len(rep.get('checked') or [])} scoped compiles)")
        lines.append(head)
        for c in rep.get("checked") or []:
            lines.append(f"  checked {c.get('fn')} @ {c.get('mesh')}: "
                         f"{c.get('captured', 0)} captured, "
                         f"{c.get('findings', 0)} classified")
        for e in rep.get("findings") or []:
            lines.append(f"  FINDING [{e.get('category')}] "
                         f"{e.get('fn')} @ {e.get('mesh')}: "
                         f"{e.get('message')}")
    return "\n".join(lines)


# ---------------------------------------------------------------------- #
# report
# ---------------------------------------------------------------------- #
def _stall_histogram(steps: List[Dict], buckets=(5, 10, 20, 40, 100)
                     ) -> List[Tuple[str, int]]:
    """Stall-fraction distribution across steps, percent buckets."""
    out = []
    lo = 0
    for hi in buckets:
        n = sum(1 for r in steps
                if lo <= 100.0 * r.get("stall_fraction", 0.0) < hi)
        out.append((f"{lo:>3}-{hi:<3}%", n))
        lo = hi
    return out


def step_top_phase(rec: Dict) -> Tuple[Optional[str], float]:
    """(name, exclusive ms) of a step's critical-path phase —
    (None, 0.0) for a step with no span under it."""
    name, ms = None, 0.0
    for n, d in rec["phases"].items():
        if d["ms"] > ms:
            name, ms = n, d["ms"]
    return name, ms


def report_data(steps: List[Dict]) -> Dict:
    """The report as data (--json; the text renderer consumes this)."""
    by_rank: Dict[int, List[Dict]] = {}
    for r in steps:
        by_rank.setdefault(int(r["rank"]), []).append(r)
    out: Dict = {"ranks": {}}
    for rank, recs in sorted(by_rank.items()):
        wall = sum(r["wall_ms"] for r in recs)
        phases: Dict[str, float] = {}
        wins: Dict[str, int] = {}
        for r in recs:
            for n, d in r["phases"].items():
                phases[n] = phases.get(n, 0.0) + d["ms"]
            top, _ = step_top_phase(r)
            if top:
                wins[top] = wins.get(top, 0) + 1
        out["ranks"][str(rank)] = {
            "steps": len(recs),
            "wall_ms": round(wall, 2),
            "attributed_fraction": (
                round(sum(r["attributed_ms"] for r in recs) / wall, 4)
                if wall else 0.0),
            "stall_fraction": (
                round(sum(r["stall_ms"] for r in recs) / wall, 4)
                if wall else 0.0),
            "overlap_ms": round(sum(r["overlap_ms"] for r in recs), 2),
            "phases_ms": {n: round(v, 2) for n, v in sorted(phases.items())},
            "critical_path_wins": dict(
                sorted(wins.items(), key=lambda kv: -kv[1])),
            "stall_histogram": _stall_histogram(recs),
            "recompile_steps": [
                {"step": r["step"], "name": r["name"],
                 "compiles": len(r["compiles"]),
                 "steady": sum(c["steady"] for c in r["compiles"]),
                 "funs": sorted({c["fun"] for c in r["compiles"]})}
                for r in recs if r["compiles"]],
        }
    return out


def render_report(steps: List[Dict], max_steps: int = 20) -> str:
    data = report_data(steps)
    lines: List[str] = []
    for rank, d in sorted(data["ranks"].items(), key=lambda kv: int(kv[0])):
        lines.append(f"== rank {rank}: {d['steps']} steps, "
                     f"{d['wall_ms']:.1f} ms wall, "
                     f"attributed {100 * d['attributed_fraction']:.1f}%, "
                     f"stall {100 * d['stall_fraction']:.1f}%, "
                     f"overlap credit {d['overlap_ms']:.1f} ms ==")
        wins = d["critical_path_wins"]
        if wins:
            total = sum(wins.values())
            lines.append("critical path: " + "  ".join(
                f"{n} {c}/{total}" for n, c in wins.items()))
        lines.append("phase totals (exclusive ms): " + "  ".join(
            f"{n}={v}" for n, v in d["phases_ms"].items()))
        lines.append("stall histogram: " + "  ".join(
            f"{b}:{n}" for b, n in d["stall_histogram"]))
        if d["recompile_steps"]:
            lines.append("recompiles (step: compiles, steady / functions):")
            for e in d["recompile_steps"][:16]:
                lines.append(f"  step {e['step']} [{e['name']}]: "
                             f"{e['compiles']}, {e['steady']} steady  "
                             + ", ".join(e["funs"]))
        else:
            lines.append("recompiles: none")
        recs = [r for r in steps if str(r.get("rank", 0)) == rank]
        lines.append("")
        lines.append(f"{'step':>5} {'name':<18} {'wall_ms':>9} "
                     f"{'top phase':<24} {'stall%':>7} {'overlap':>8}")
        for r in recs[:max_steps]:
            top_n, top_ms = step_top_phase(r)
            top_s = f"{top_n} ({top_ms:.1f} ms)" if top_n else "-"
            lines.append(
                f"{r.get('step', '?'):>5} {r.get('name', '?'):<18} "
                f"{r.get('wall_ms', 0):>9.2f} {top_s:<24} "
                f"{100 * r.get('stall_fraction', 0):>6.1f}% "
                f"{r.get('overlap_ms', 0):>8.2f}")
        if len(recs) > max_steps:
            lines.append(f"  ... {len(recs) - max_steps} more steps "
                         "(--steps N to widen)")
        lines.append("")
    return "\n".join(lines).rstrip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mvprof", description="per-step critical-path report")
    ap.add_argument("paths", nargs="+",
                    help="metrics dir(s) and/or trace JSONL files")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of tables")
    ap.add_argument("--steps", type=int, default=20,
                    help="per-rank step rows shown in the report")
    args = ap.parse_args(argv)

    steps = collect(args.paths)
    hygiene = collect_hygiene(args.paths)
    if not steps and not hygiene:
        print("mvprof: no step span found (does the loop mark its steps, "
              "and is metrics_dir set?)", file=sys.stderr)
        return 1
    if args.json:
        data = report_data(steps) if steps else {}
        if hygiene:
            data["hygiene"] = hygiene
        print(json.dumps(data))
    else:
        parts = []
        if steps:
            parts.append(render_report(steps, args.steps))
        if hygiene:
            parts.append(render_hygiene(hygiene))
        print("\n\n".join(parts))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
