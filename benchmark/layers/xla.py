"""What a compiled program says of itself: the ``xla.program`` record a
trainer leaves once a program, after its first call
(``multiverso_tpu/telemetry/devstats.describe_program``), and the two
counts ``xla.compile`` carries of Python's part of a compile. Read from
the program's ring alone, like ``layers/lm.py``; set-up is what
``layers/prog.py`` says it is: the spans that began before the window's
first and were not recorded under the profiler.

``xla.scoped_ops_share.<group>``   100 x ``scoped`` / ``instructions`` of
    the set-up's last ``xla.program`` whose ``program`` is ``lm.step``:
    the share of the step's instructions BY COUNT, not by device time,
    that carry an ``mv.*`` scope. XLA's own copies and zero fills carry
    no metadata, are many and cost almost nothing: on the chip this
    reads 35 to 49% where the same runs' ``scopes`` tables file 95 to
    99% of BUSY TIME under a scope (PERF.md section 5, PR 52), and on a
    ``--cpu-tiny`` step it reads 95%. So it is a tripwire for a layer
    added without a ``jax.named_scope`` (it falls by that layer's
    instructions), and no target: the share by time is the trace's to
    give (ROADMAP Design 1f, ``scope.unscoped_device_share.lm``).
``xla.program_memory_gb.<group>``  (``argument_bytes`` + ``output_bytes``
    - ``alias_bytes`` + ``temp_bytes`` + ``code_bytes``) / 1e9 of that
    record: what the step's program needs of the chip, the results a
    block keeps and its temporaries included (``memory_peak_bytes``
    leaves out what a loaded program reserves).
``xla.temp_memory_gb.<group>``     ``temp_bytes`` / 1e9: the part a
    keep-or-make-again decision moves.
``xla.lower_s.setup``              the sum of ``trace_s`` + ``lower_s``
    over the set-up's ``xla.compile`` records: tracing and lowering,
    which ``prog.compile_s.setup`` (the compiler alone) leaves out.

A program without the record, or without the counts, answers ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.layers import prog

STEP_PROGRAM = "lm.step"


def setup_events(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    cut = min((e["ts"] for e in events if e.get("prof")),
              default=float("inf"))
    return [e for e in events if e["ts"] < cut and not e.get("prof")]


def read_events(name: str, events: List[Dict[str, Any]]) -> Optional[float]:
    what = name.split(".")[1]
    setup = setup_events(events)
    if what == "lower_s":
        led = [e["args"] for e in setup if e.get("name") == "xla.compile"
               and "trace_s" in e["args"] and "lower_s" in e["args"]]
        return (sum(float(a["trace_s"]) + float(a["lower_s"]) for a in led)
                if led else None)
    steps = [e["args"] for e in setup if e.get("name") == "xla.program"
             and e["args"].get("program") == STEP_PROGRAM]
    if not steps:
        return None
    a = steps[-1]
    if what == "scoped_ops_share":
        return (100.0 * a["scoped"] / a["instructions"]
                if a.get("instructions") else None)
    if what == "program_memory_gb":
        return (a["argument_bytes"] + a["output_bytes"] - a["alias_bytes"]
                + a["temp_bytes"] + a["code_bytes"]) / 1e9
    if what == "temp_memory_gb":
        return a["temp_bytes"] / 1e9
    return None


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    return read_events(name, prog.program_events())
