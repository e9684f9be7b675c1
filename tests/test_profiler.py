"""Steps, phases and their report, read off span records (ISSUE 9's
critical-path profiler, since ISSUE 58 a pure function of the one span
ring: ``telemetry/trace.step_report``).

The report's value IS its math — so the interval arithmetic
(``utils/intervals``) and the report over hand-written span lists are
checked against brute-force oracles, the recompile rule against a real
jit forced to retrace mid-run, the cross-thread attribution against
threads working beside another thread's step. The mvprof report smokes
on a LIVE 2-rank PS world, and ``tools/check_obs_surface.py`` (the
opcode/flag lint) runs here so tier-1 fails when an opcode or flag ships
without its observability/doc surface.
"""

import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from multiverso_tpu.telemetry import devstats  # noqa: E402
from multiverso_tpu.telemetry import trace as ttrace  # noqa: E402
from multiverso_tpu.utils import config, intervals  # noqa: E402


def _trace_ids():
    """The fine sites' gate: per-request and per-minibatch spans."""
    config.set_flag("trace_ids", True)
    ttrace.configure()


def _last_step(name=None):
    reports = [r for r in ttrace.step_report(ttrace.events())
               if name is None or r["name"] == name]
    return reports[-1]


# ---------------------------------------------------------------------- #
# interval math vs brute-force oracles
# ---------------------------------------------------------------------- #
def _oracle_union(ivs, hi=1000):
    covered = np.zeros(hi, bool)
    for a, b in ivs:
        covered[int(a):int(b)] = True
    return int(covered.sum())


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_union_length_vs_oracle(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 40))
    ivs = []
    for _ in range(n):
        a = int(rng.integers(0, 1000))
        b = int(rng.integers(0, 1000))
        ivs.append((min(a, b), max(a, b)))
    # integer endpoints -> the boolean-grid oracle is EXACT
    assert intervals.union_length(ivs) == _oracle_union(ivs)


@pytest.mark.parametrize("seed", [10, 11, 12])
def test_intersect_length_vs_oracle(seed):
    rng = np.random.default_rng(seed)
    ivs = []
    for _ in range(int(rng.integers(1, 30))):
        a = int(rng.integers(0, 1000))
        b = int(rng.integers(0, 1000))
        ivs.append((min(a, b), max(a, b)))
    s0, s1 = sorted(int(x) for x in rng.integers(0, 1000, 2))
    covered = np.zeros(1000, bool)
    for a, b in ivs:
        covered[a:b] = True
    oracle = int(covered[s0:s1].sum())
    assert intervals.intersect_length((s0, s1), ivs) == oracle


def test_union_degenerate_cases():
    assert intervals.union_length([]) == 0.0
    assert intervals.union_length([(5, 5), (7, 3)]) == 0.0  # empty/reversed
    assert intervals.union_length([(0, 10), (10, 20)]) == 20.0  # touching
    assert intervals.clip(0, 5, 5, 9) is None and intervals.clip(
        0, 7, 5, 9) == (5, 7)


def test_the_leaf_and_the_span_module_import_nothing_above_them():
    """``utils/intervals`` imports nothing of the package, and importing
    ``telemetry.trace`` loads no other module of ``telemetry`` (but the
    histogram every Dashboard monitor embeds). The package's own
    ``__init__`` imports everything, so the child stands plain modules
    in the packages' places."""
    code = """
import sys, types
for name in ("multiverso_tpu", "multiverso_tpu.telemetry",
             "multiverso_tpu.utils"):
    pkg = types.ModuleType(name)
    pkg.__path__ = [%r + "/" + name.replace(".", "/")]
    sys.modules[name] = pkg
import multiverso_tpu.utils.intervals
mine = [m for m in sys.modules if m.startswith("multiverso_tpu.")]
assert sorted(mine) == ["multiverso_tpu.telemetry", "multiverso_tpu.utils",
                        "multiverso_tpu.utils.intervals"], mine
import multiverso_tpu.telemetry.trace
print(sorted(m for m in sys.modules
             if m.startswith("multiverso_tpu.telemetry.")))
""" % _REPO
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == str(["multiverso_tpu.telemetry.histogram",
                                      "multiverso_tpu.telemetry.trace"])


# ---------------------------------------------------------------------- #
# the report over hand-written span lists vs a brute-force oracle
# ---------------------------------------------------------------------- #
def _sp(i, ts, dur, tid=1, parent=None, name="s", **args):
    return {"name": name, "cat": "prog", "id": i, "parent": parent,
            "tid": tid, "pid": 0, "request": None, "ts": float(ts),
            "dur": float(dur), "args": args}


def _compile(i, end, dur, fun, tid=1, parent=None):
    return _sp(i, end - dur, dur, tid, parent, "xla.compile", fun=fun,
               seconds=dur * 1e-6)


REPORT_CASES = {
    # b inside a inside the step; a span without a phase files under the
    # nearest one above it, and under its own name where there is none
    "phases_nest": [
        _sp(1, 0, 1000, name="train", step=1),
        _sp(2, 100, 500, parent=1, name="x.outer", phase="a"),
        _sp(3, 200, 200, parent=2, name="x.inner", phase="b"),
        _sp(4, 250, 50, parent=3, name="x.leaf"),
        _sp(5, 700, 100, parent=1, name="x.alone"),
        _sp(6, 720, 30, parent=5, name="x.alone.part")],
    # another thread's span beside the step: part of it under the step's
    # own span (overlap credit), part of it alone, and a gap nobody claims
    "beside_and_stall": [
        _sp(1, 0, 1000, name="train", step=1),
        _sp(2, 100, 400, parent=1, name="x.compute", phase="compute"),
        _sp(3, 50, 250, tid=2, name="client.get_rows"),
        _sp(4, 600, 100, tid=2, name="client.get_rows")],
    # a span that outlasts the step is clipped at its end, and says so
    "open_at_the_end": [
        _sp(1, 0, 1000, name="train", step=1),
        _sp(2, 800, 700, tid=2, name="client.add_rows"),
        _sp(3, 1200, 100, tid=2, name="late")],
    # two trainers at once: the other's phases are ITS step's, a producer
    # under no step is beside both
    "two_threads_two_steps": [
        _sp(1, 0, 1000, tid=1, name="train", step=1),
        _sp(2, 100, 300, tid=1, parent=1, name="x.compute",
            phase="compute"),
        _sp(3, 200, 1000, tid=2, name="train", step=1),
        _sp(4, 300, 600, tid=2, parent=3, name="x.compute",
            phase="compute"),
        _sp(5, 350, 300, tid=3, name="io.produce"),
        _sp(6, 1000, 100, tid=1, name="train", step=1)],
    # a phase under no step is in no step's phases; it is beside the step
    # it overlaps, like any span of a thread without one
    "phase_without_step": [
        _sp(1, 0, 50, tid=2, name="x.orphan", phase="compute"),
        _sp(2, 100, 400, tid=1, name="train", step=1),
        _sp(3, 300, 400, tid=2, name="x.orphan", phase="compute")],
    # the steps run inside a span that outlasts them (we.blocks round its
    # we.step's): a trainer thread's spans are nobody's work beside a
    # step, its own or another trainer's, or every step would be covered
    # whole by construction; the producer (no step of its own) is
    "steps_inside_a_call": [
        _sp(1, 0, 2000, tid=1, name="blocks"),
        _sp(2, 100, 800, tid=1, parent=1, name="train", step=1),
        _sp(3, 200, 300, tid=1, parent=2, name="x.compute",
            phase="compute"),
        _sp(4, 1000, 800, tid=1, parent=1, name="train", step=1),
        _sp(5, 1000, 100, tid=1, parent=4, name="x.wait", phase="io_wait"),
        _sp(6, 50, 1900, tid=2, name="blocks"),
        _sp(7, 60, 20, tid=2, parent=6, name="x.fill"),
        _sp(8, 150, 1700, tid=2, parent=6, name="between"),
        _sp(9, 300, 900, tid=2, parent=8, name="train", step=1),
        _sp(10, 600, 150, tid=3, name="we.prepare", phase="prepare"),
        _sp(11, 1500, 200, tid=3, name="we.prepare", phase="prepare")],
    # beside a step a thread's work counts once, by its top-level span:
    # what is nested lies inside it, and a span under one still open
    # (not among the records) waits for it
    "nested_beside": [
        _sp(1, 0, 1000, tid=1, name="train", step=1),
        _sp(2, 100, 600, tid=2, name="serve"),
        _sp(3, 200, 300, tid=2, parent=2, name="serve.apply"),
        _sp(4, 800, 100, tid=3, parent=99, name="under.an.open.span")],
    # compiles: in a thread's first step (warm-up), in its second
    # (steady), on a bare thread inside a steady step (steady, once,
    # though two steps are open), outside every step (neither)
    "compiles": [
        _sp(1, 0, 1000, tid=1, name="train", step=1),
        _compile(2, 500, 300, "f", parent=1),
        _sp(3, 1000, 1000, tid=1, name="train", step=1),
        _compile(4, 1500, 200, "g", parent=3),
        _sp(5, 1100, 600, tid=2, name="train", step=1),
        _sp(6, 1800, 700, tid=2, name="train", step=1),
        _compile(7, 1900, 50, "h", tid=3),
        _compile(8, 2700, 100, "never", tid=3)],
}


def _oracle_report(events):
    """The report a microsecond at a time (integer stamps), by the rules
    as ISSUE 58 words them and not as the code finds them: a step's own
    are the spans whose parents lead up to it; beside it is the work of
    OTHER threads, those that run no step themselves, a thread's work
    taken by its outermost spans."""
    by_id = {e["id"]: e for e in events}

    def above(e):                       # e's parent, its parent, ...
        out = []
        while e["parent"] in by_id:
            e = by_id[e["parent"]]
            out.append(e)
        return out

    def covers(e, t):
        return e["ts"] <= t < e["ts"] + e["dur"]

    def is_step(e):
        return "step" in e["args"]

    steps = sorted(filter(is_step, events), key=lambda e: e["ts"])
    trainers = {s["tid"] for s in steps}
    beside = [e for e in events
              if e["parent"] is None and e["tid"] not in trainers]
    firsts = {}
    for s in steps:
        firsts.setdefault(s["tid"], s)
    out = []
    for s in steps:
        lo, hi = int(s["ts"]), int(s["ts"] + s["dur"])
        tree = [e for e in events if s in above(e)]
        phases, attributed, overlap = {}, 0, 0
        for t in range(lo, hi):
            under = [e for e in tree if covers(e, t)]
            if under:
                inner = max(under, key=lambda e: len(above(e)))
                up = [inner] + above(inner)
                label = next((a["args"]["phase"] for a in up[:up.index(s)]
                              if "phase" in a["args"]), inner["name"])
                phases[label] = phases.get(label, 0) + 1
            by = [e for e in beside if covers(e, t)]
            attributed += bool(under or by)
            overlap += len(by) if under else 0
        compiles = {}
        for c in events:
            t = c["ts"] + c["dur"]
            if c["name"] == "xla.compile" and lo <= t <= hi:
                in_a_first = any(f["ts"] <= t <= f["ts"] + f["dur"]
                                 for f in firsts.values())
                compiles[c["args"]["fun"]] = not in_a_first
        out.append({
            "wall": hi - lo, "attributed": attributed, "overlap": overlap,
            "phases": phases, "compiles": compiles,
            "beside": {n: sum(min(e["ts"] + e["dur"], hi) - max(e["ts"], lo)
                              for e in beside if e["name"] == n
                              and e["ts"] < hi and e["ts"] + e["dur"] > lo)
                       for n in {e["name"] for e in beside}},
            "open": sum(1 for e in beside if e["ts"] < hi
                        and hi < e["ts"] + e["dur"])})
    return out


@pytest.mark.parametrize("case", sorted(REPORT_CASES))
def test_step_report_matches_brute_force(case):
    events = REPORT_CASES[case]
    got, want = ttrace.step_report(events), _oracle_report(events)
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g["wall_ms"] == pytest.approx(w["wall"] * 1e-3)
        assert g["attributed_ms"] == pytest.approx(w["attributed"] * 1e-3)
        assert g["stall_ms"] == pytest.approx(
            (w["wall"] - w["attributed"]) * 1e-3)
        assert g["stall_fraction"] == pytest.approx(
            1 - w["attributed"] / w["wall"])
        assert g["overlap_ms"] == pytest.approx(w["overlap"] * 1e-3)
        assert {n: d["ms"] for n, d in g["phases"].items() if d["ms"]} == (
            pytest.approx({n: us * 1e-3 for n, us in w["phases"].items()}))
        assert {n: d["ms"] for n, d in g["async"].items()} == pytest.approx(
            {n: us * 1e-3 for n, us in w["beside"].items() if us})
        assert sum(d["open"] for d in g["async"].values()) == w["open"]
        assert {c["fun"]: c["steady"] for c in g["compiles"]} == w["compiles"]
    # the sums: each steady compile once, every compile record counted
    totals = ttrace.step_totals(events)
    assert totals["steps"] == len(want)
    assert len(totals["steady"]) == len(
        {f for w in want for f, steady in w["compiles"].items() if steady})
    assert totals["compiles"] == sum(e["name"] == "xla.compile"
                                     for e in events)
    block = ttrace.profile_block(totals)
    assert sorted(block) == ["attributed_fraction", "compiles", "phases",
                             "stall_fraction", "steady_recompiles", "steps"]
    assert block["stall_fraction"] == pytest.approx(
        1 - sum(w["attributed"] for w in want) / sum(w["wall"] for w in want),
        abs=1e-4)


def test_a_step_is_not_covered_by_the_call_it_runs_inside():
    """REVIEW of PR 58: the span round the steps made every step's
    attributed fraction 1 and its stall 0 by construction."""
    first, other, second = ttrace.step_report(
        REPORT_CASES["steps_inside_a_call"])
    assert first["async"].keys() == {"we.prepare"}
    assert first["attributed_ms"] == pytest.approx(0.45)    # 300 + 150
    assert first["stall_fraction"] == pytest.approx(1 - 450 / 800)
    assert other["async"].keys() == {"we.prepare"}
    assert second["attributed_ms"] == pytest.approx(0.3)    # 100 + 200
    [nested] = ttrace.step_report(REPORT_CASES["nested_beside"])
    assert nested["async"] == {"serve": {
        "ms": pytest.approx(0.6), "overlap_ms": 0.0, "count": 1, "open": 0}}


def test_report_names_the_counts_a_phase_carries():
    [r] = ttrace.step_report(REPORT_CASES["phases_nest"])
    # the leaf is filed under b but is no mark of it
    assert r["phases"]["b"]["count"] == 1 and r["phases"]["a"]["count"] == 1
    assert r["phases"]["x.alone"]["count"] == 1
    assert r["phases"]["x.alone.part"]["count"] == 1
    assert ttrace.step_report([]) == []
    assert ttrace.profile_block(ttrace.step_totals([])) is None


# ---------------------------------------------------------------------- #
# step / phase / beside semantics on live spans
# ---------------------------------------------------------------------- #
def test_nested_phase_exclusive_time():
    with ttrace.span("t.step", step=1):
        with ttrace.span("t.outer", phase="outer"):
            time.sleep(0.04)
            with ttrace.span("t.inner", phase="inner"):
                time.sleep(0.03)
    r = _last_step()
    outer = r["phases"]["outer"]["ms"]
    inner = r["phases"]["inner"]["ms"]
    # inner's span debits outer: exclusive outer ~40 ms, inner ~30 ms
    assert 25 <= inner <= 60
    assert 25 <= outer <= 60
    # the union math still counts the overlapping second once
    assert r["attributed_ms"] <= r["wall_ms"] * 1.001
    assert r["attributed_fraction"] > 0.9


def test_overlap_credit_and_stall():
    def wire():                         # a round trip on a peer's thread
        with ttrace.span("t.get"):
            time.sleep(0.05)

    with ttrace.span("t.step", step=1):
        t = threading.Thread(target=wire)
        t.start()
        with ttrace.span("t.compute", phase="compute"):
            time.sleep(0.05)
        t.join(5)
        time.sleep(0.04)    # deliberate unmarked gap = stall
    r = _last_step()
    # the wire span ran concurrently with compute: near-full credit
    assert r["async"]["t.get"]["overlap_ms"] == pytest.approx(
        r["phases"]["compute"]["ms"], rel=0.25)
    # the 40 ms gap is stall, not attributed
    assert r["stall_ms"] > 25
    assert 0.25 < r["stall_fraction"] < 0.65
    # the block, added up as the step closed, is the report's own sums
    assert ttrace.step_summary() == ttrace.profile_block(
        ttrace.step_totals(ttrace.events()))


def test_span_open_at_step_end_is_clipped():
    started = threading.Event()

    def wire():
        with ttrace.span("t.add"):
            started.set()
            time.sleep(0.06)

    t = threading.Thread(target=wire)
    with ttrace.span("t.step", step=1):
        t.start()
        started.wait(5)
        time.sleep(0.02)
        # NOT ended before the step closes
    t.join(5)
    r = _last_step()
    d = r["async"]["t.add"]
    assert d["open"] == 1
    assert d["ms"] <= r["wall_ms"] * 1.001


def test_cross_thread_work_lands_beside_the_step():
    with ttrace.span("t.consumer", step=1):
        def producer():
            with ttrace.span("t.produce"):
                time.sleep(0.03)
            t1 = time.time_ns()
            ttrace.record("t.batch", t1 - 10_000_000, t1)

        t = threading.Thread(target=producer)
        t.start()
        with ttrace.span("t.compute", phase="compute"):
            time.sleep(0.04)
        t.join(5)
    r = _last_step()
    # the producer thread's work landed on the consumer's step
    assert r["async"]["t.produce"]["ms"] > 20
    assert "t.batch" in r["async"]
    # and overlapped compute (both slept concurrently)
    assert r["attributed_ms"] < (r["async"]["t.produce"]["ms"]
                                 + r["phases"]["compute"]["ms"]) * 1.001


def test_a_bare_threads_span_counts_beside_the_open_step():
    """A thread with NO step of its own (sample_reader's producer) is
    beside whichever step it overlapped; a fine site says so only with
    ``trace_ids`` on."""
    for traced in (False, True):
        if traced:
            _trace_ids()
        with ttrace.span("t.train", step=1):
            t0 = time.time()
            time.sleep(0.01)
            t = threading.Thread(target=lambda: ttrace.add_span(
                "io.produce", t0, time.time(), cat="io"))
            t.start()
            t.join(5)
        assert ("io.produce" in _last_step()["async"]) is traced


def test_phase_without_step_is_in_no_report():
    with ttrace.span("t.orphan", phase="compute"):
        time.sleep(0.001)
    assert ttrace.step_report(ttrace.events()) == []
    assert ttrace.step_summary() is None


# ---------------------------------------------------------------------- #
# recompiles, read off the xla.compile records
# ---------------------------------------------------------------------- #
def test_recompile_attribution_mid_run():
    import jax
    import jax.numpy as jnp
    devstats.configure(0)

    def doubled_plus_one(x):
        return x * 2 + 1

    f = jax.jit(doubled_plus_one)
    for name, n in (("warm", 8), ("steady", 8), ("retrace", 9)):
        with ttrace.span(name, step=1):
            float(f(jnp.ones(n))[0])    # 9: new shape -> forced retrace
    recs = {r["name"]: r for r in ttrace.step_report(ttrace.events())}
    # a step that holds an xla.compile record names its function
    assert any("doubled_plus_one" in c["fun"]
               for c in recs["warm"]["compiles"])
    assert not any(c["steady"] for c in recs["warm"]["compiles"])
    # the steady step triggered NOTHING
    assert recs["steady"]["compiles"] == []
    # the retrace is attributed to the step that triggered it
    assert any("doubled_plus_one" in c["fun"] and c["steady"]
               for c in recs["retrace"]["compiles"])
    # steady-state recompiles (past each thread's first step) in the block
    block = ttrace.step_summary()
    assert block["steady_recompiles"] >= 1
    assert block["compiles"] >= 2 and block["steps"] == 3


def test_concurrent_warmup_compiles_are_not_steady():
    """Two trainer threads whose FIRST steps overlap share one warm
    compile of the same jitted fn — a rule per step's window would
    count it (possibly twice) as a steady recompile; the per-compile
    rule (no steady inside any thread's first step) must not."""
    import jax
    import jax.numpy as jnp
    devstats.configure(0)
    f = jax.jit(lambda x: x * 3)
    start = threading.Barrier(2)

    def trainer():
        start.wait(5)
        with ttrace.span("train", step=1):
            float(f(jnp.ones(16))[0])   # both threads race the compile
            time.sleep(0.05)            # keep the steps overlapping

    with ttrace.span("main_warm", step=1):   # the MAIN thread's warmup
        pass
    ts = [threading.Thread(target=trainer) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert ttrace.step_summary()["steady_recompiles"] == 0
    # but a compile fired AFTER every first step closed IS steady
    # (the main thread already spent its warmup exemption above)
    with ttrace.span("later", step=1):
        float(f(jnp.ones(17))[0])       # new shape -> real retrace
    assert ttrace.step_summary()["steady_recompiles"] >= 1


def test_donation_warning_and_transfers_have_one_home_each():
    """What the step record's ``jax`` dict carried: a rejected donation
    is a finding of ``devstats.capture_hygiene``, host-to-device bytes
    are ``devstats``' per-direction counter."""
    devstats.note_transfer(1 << 20, "h2d")
    with devstats.capture_hygiene("t.fn"):
        warnings.warn("Some donated buffers were not usable: f32[8]")
    assert devstats.stats_snapshot()["transfers"]["h2d"]["bytes"] == 1 << 20
    [finding] = devstats.hygiene_report()["findings"]
    assert finding["category"] == "donation" and finding["fn"] == "t.fn"


# ---------------------------------------------------------------------- #
# records / dumps / stats surfaces
# ---------------------------------------------------------------------- #
def test_the_profile_block_outlives_a_dump(tmp_path):
    """The exporter drains the ring into ``trace-rank<r>.jsonl``; the
    MSG_STATS block counts the drained steps on, each once, and leaves
    out the one step whose spans a dump took from under it."""
    def step(n=1):
        for _ in range(n):
            with ttrace.span("t.step", step=1):
                with ttrace.span("t.p", phase="p"):
                    time.sleep(0.001)

    step(3)
    before = ttrace.step_summary()
    assert ttrace.dump_to(str(tmp_path)) == 6
    assert ttrace.events() == [] and ttrace.dump_to(str(tmp_path)) == 0
    assert ttrace.step_summary() == before and before["steps"] == 3
    with ttrace.span("t.step", step=1):
        with ttrace.span("t.p", phase="p"):
            time.sleep(0.001)
        assert ttrace.dump_to(str(tmp_path)) == 1       # t.p, drained
    step(2)
    block = ttrace.step_summary()
    assert block["steps"] == 5 and block["phases"]["p"] >= 5 * 1.0
    assert block["attributed_fraction"] > 0.5
    ttrace.dump_to(str(tmp_path))
    assert ttrace.step_summary() == block
    # the file holds every span, the straddled step's among them
    path = tmp_path / "trace-rank0.jsonl"
    recs = [json.loads(x) for x in path.read_text().splitlines()]
    assert sum("step" in r["args"] for r in recs) == 6


def test_the_profile_block_outlives_the_rings_bound(monkeypatch):
    """The block is added up as each step closes, so it neither shrinks
    to the ring's tail nor forgets which step was a thread's first; a
    step that alone outgrows the ring is left out of the sums."""
    monkeypatch.setattr(ttrace, "_MAX_EVENTS", 8)
    tr = ttrace.Tracer()

    def step(spans, compile_fun=None):
        with tr.span("t.step", step=1):
            for _ in range(spans):
                with tr.span("t.p", phase="p"):
                    pass
            if compile_fun:
                t1 = time.time_ns()
                tr.record("xla.compile", t1 - 1000, t1, fun=compile_fun)

    step(2, "warm")                     # the thread's first: warm-up
    for _ in range(10):
        step(2)
    assert len(tr.events()) == 8 and tr.step_summary()["steps"] == 11
    step(2, "again")                    # the first step left the ring long ago
    block = tr.step_summary()
    assert (block["steps"], block["compiles"],
            block["steady_recompiles"]) == (12, 2, 1)
    step(20)                            # its first spans are gone at its close
    assert tr.step_summary()["steps"] == 12


def test_stats_snapshot_shape_and_service_payload(two_ranks):
    with ttrace.span("t.step", step=1):
        with ttrace.span("t.compute", phase="compute"):
            time.sleep(0.002)
    snap = ttrace.step_summary()
    assert sorted(snap) == ["attributed_fraction", "compiles", "phases",
                            "stall_fraction", "steady_recompiles", "steps"]
    assert snap["steps"] >= 1
    assert 0.0 <= snap["stall_fraction"] <= 1.0
    assert "compute" in snap["phases"]
    # the MSG_STATS payload carries the block (local + over the socket)
    payload = two_ranks[0].service.stats_payload()
    assert payload["profile"]["steps"] >= 1
    remote = two_ranks[0].service.stats(1)
    assert remote["profile"]["steps"] >= 1


def test_merge_cluster_passes_profile_and_mvtop_renders():
    from multiverso_tpu.telemetry import aggregator
    stats = {0: {"rank": 0, "addr": "h:1", "pid": 11, "monitors": {},
                 "shards": {},
                 "profile": {"steps": 5, "stall_fraction": 0.25,
                             "attributed_fraction": 0.9,
                             "steady_recompiles": 2, "compiles": 7,
                             "phases": {"compute": 10.0}}},
             1: {"rank": 1, "addr": "h:2", "pid": 12, "monitors": {},
                 "shards": {}}}
    health = {0: {"status": "ok", "addr": "h:1"},
              1: {"status": "ok", "addr": "h:2"}}
    rec = aggregator.merge_cluster(stats, health, world=2)
    assert rec["profile"]["0"]["steps"] == 5
    assert rec["ranks"]["0"]["stall_pct"] == 25.0
    assert rec["ranks"]["0"]["recompiles"] == 2
    assert "stall_pct" not in rec["ranks"]["1"]
    # compact record keeps the block for bench extra
    assert aggregator.compact_record(rec)["profile"]["0"]["steps"] == 5
    # mvtop's rank table shows the columns
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import mvtop
    text = mvtop.render(rec)
    assert "stall%" in text and "recomp" in text
    assert "25.0" in text


def test_dump_metrics_renders_the_profile_block(tmp_path, capsys):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import dump_metrics
    # a span file's steps lie on the timeline to-perfetto wraps
    p = tmp_path / "trace-rank0.jsonl"
    p.write_text("".join(json.dumps(e) + "\n"
                         for e in REPORT_CASES["phases_nest"]))
    out = tmp_path / "timeline.json"
    assert dump_metrics.main(["to-perfetto", str(p), str(out)]) == 0
    wrapped = json.loads(out.read_text())["traceEvents"]
    assert [e["name"] for e in wrapped if "step" in e["args"]] == ["train"]
    assert {e["args"]["phase"] for e in wrapped
            if "phase" in e["args"]} == {"a", "b"}
    # per-rank stats records render an embedded profile block
    srec = {"rank": 0, "monitors": {}, "shards": {},
            "profile": {"steps": 3, "stall_fraction": 0.1,
                        "attributed_fraction": 0.9,
                        "steady_recompiles": 0,
                        "phases": {"compute": 12.0}}}
    out = dump_metrics.format_record(srec)
    assert "profile:" in out and "compute" in out


# ---------------------------------------------------------------------- #
# mvprof on a live 2-rank world (report + perfetto smoke)
# ---------------------------------------------------------------------- #
def test_mvprof_live_two_rank_world(tmp_path, two_ranks):
    from multiverso_tpu.ps.tables import AsyncMatrixTable
    from multiverso_tpu.telemetry import trace as ttrace
    mdir = tmp_path / "metrics"
    config.set_flag("metrics_dir", str(mdir))
    _trace_ids()
    # a send window pins the client to the python conns (the native
    # fast path is untraced by design), so spans exist on BOTH fixture
    # planes — and the windowed ps.add async span path is exercised
    t0 = AsyncMatrixTable(64, 8, name="prof_t", send_window_ms=1.0,
                          ctx=two_ranks[0])
    AsyncMatrixTable(64, 8, name="prof_t", ctx=two_ranks[1])
    rng = np.random.default_rng(0)
    for i in range(3):
        with ttrace.span("t.train", step=1):
            ids = rng.integers(32, 64, 4)   # remote rank's rows
            with ttrace.span("t.prepare", phase="prepare"):
                vals = rng.normal(size=(4, 8)).astype(np.float32)
            mid = t0.add_rows_async(ids, vals)
            with ttrace.span("t.compute", phase="compute"):
                time.sleep(0.005)
            with ttrace.span("t.wait", phase="ps_wait"):
                t0.wait(mid)
                rows = t0.get_rows(ids)
        assert rows.shape == (4, 8)
    recs = ttrace.step_report(ttrace.events())
    assert len(recs) == 3
    # the table layer's request spans lie beside the steps that sent them
    assert any(n.startswith(("client.", "ps.", "window."))
               for r in recs for n in r["async"])
    ttrace.dump_to(str(mdir))

    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import mvprof
    steps = mvprof.collect([str(mdir)])
    assert len(steps) == 3
    assert [r["phases"] for r in steps] == [r["phases"] for r in recs]
    report = mvprof.render_report(steps)
    assert "critical path" in report and "rank 0" in report
    data = mvprof.report_data(steps)
    assert data["ranks"]["0"]["steps"] == 3
    assert data["ranks"]["0"]["attributed_fraction"] > 0.5
    assert {"prepare", "compute", "ps_wait"} <= set(
        data["ranks"]["0"]["phases_ms"])
    assert mvprof.main([str(mdir)]) == 0
    assert mvprof.main([str(mdir), "--json"]) == 0


def test_mvprof_no_records_exits_1(tmp_path):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import mvprof
    assert mvprof.main([str(tmp_path)]) == 1


def test_logreg_pipeline_steps_reach_io_wait(tmp_path):
    """The shipped LR file-training loop brackets steps, so the
    sample_reader io_wait phase (and the producer's io.produce spans)
    are reachable from a real pipeline — not only from tests."""
    import multiverso_tpu as mv
    from multiverso_tpu.apps.logistic_regression import (LogReg,
                                                         LogRegConfig)
    rng = np.random.default_rng(0)
    train = tmp_path / "train.txt"
    with open(train, "w") as f:
        for _ in range(200):
            w = rng.normal(size=6)
            f.write(f"{int(w[0] > 0)} " + " ".join(
                f"{i}:{v:.3f}" for i, v in enumerate(w)) + "\n")
    mv.init()
    _trace_ids()
    cfg = LogRegConfig({"input_size": "6", "output_size": "2",
                        "minibatch_size": "64", "learning_rate": "0.1",
                        "train_epoch": "1", "objective_type": "softmax",
                        "train_file": str(train)})
    LogReg(cfg).train_file()
    recs = [r for r in ttrace.step_report(ttrace.events())
            if r["name"] == "lr.minibatch"]
    assert recs, "LR file training recorded no step spans"
    assert all("io_wait" in r["phases"] for r in recs)
    # the producer's per-batch spans are in the ring (beside a step where
    # they overlapped one: the reader may be done before the first step)
    assert any(e["name"] == "io.produce" for e in ttrace.events())


def test_dlrm_train_step_profiled(two_ranks):
    """The DLRM serving train_step is one whole step span:
    prepare/ps_wait/compute/push phases + the table layer's
    send-to-reply spans beside them, attribution near 1."""
    from multiverso_tpu.apps.dlrm_serving import DLRMServing
    from multiverso_tpu.models import dlrm
    from multiverso_tpu.ps.tables import AsyncMatrixTable
    _trace_ids()
    cfg = dlrm.DLRMConfig(vocab_sizes=(32, 16), embed_dim=8,
                          dense_dim=4, bottom_mlp=(8,), top_mlp=(8, 1))
    app = DLRMServing(cfg, ctx=two_ranks[0], name="prof_dlrm", lr=0.2,
                      staleness_s=30.0, start_replica=False)
    peer = AsyncMatrixTable(dlrm.total_rows(cfg), cfg.embed_dim,
                            updater="adagrad", seed=0, init_scale=0.05,
                            name=app.emb.name, ctx=two_ranks[1])
    cat, dense, labels = dlrm.synthetic_ctr(cfg, 64, seed=3)
    for _ in range(2):
        app.train_step(cat, dense, labels)
    recs = [r for r in ttrace.step_report(ttrace.events())
            if r["name"] == "dlrm.train_step"]
    assert len(recs) == 2
    r = recs[-1]
    for ph in ("prepare", "ps_wait", "compute", "push"):
        assert ph in r["phases"], r["phases"]
    if not config.get_flag("ps_native"):
        # the native plane's served ops are not traced, by design
        assert "client.get_rows" in r["async"], r["async"]
        assert "client.add_rows" in r["async"], r["async"]
    assert r["attributed_fraction"] > 0.9
    app.close()
    del peer


# ---------------------------------------------------------------------- #
# PR-8 coverage gap closed: snapshot serves / replica pulls on the tape
# ---------------------------------------------------------------------- #
def test_replica_pull_and_snapshot_serve_on_the_timeline(two_ranks):
    """MSG_SNAPSHOT serves and ReadReplica refreshes must emit PR-3
    trace spans and flightrec events like gets/adds (the satellite that
    motivated the check_obs_surface lint)."""
    from multiverso_tpu.ps.tables import AsyncMatrixTable
    from multiverso_tpu.serving.replica import ReadReplica
    from multiverso_tpu.telemetry import flightrec
    from multiverso_tpu.telemetry import trace as ttrace
    config.set_flag("trace_ids", True)
    ttrace.configure()
    t0 = AsyncMatrixTable(64, 8, name="rp_t", ctx=two_ranks[0])
    AsyncMatrixTable(64, 8, name="rp_t", ctx=two_ranks[1])
    t0.add_rows(np.arange(40, 44), np.ones((4, 8), np.float32))
    rep = ReadReplica(t0, start=False)
    try:
        rep.refresh()
        kinds = {s[2] for s in flightrec.RECORDER.snapshot()}
        assert flightrec.EV_REPLICA_PULL in kinds
        # both ranks live in this process: the serve side's event is on
        # the same ring (remote rank 1's shard served a real socket
        # snapshot; rank 0's local shard served in-process)
        assert flightrec.EV_SNAPSHOT_SERVE in kinds
        names = {e["name"] for e in ttrace.TRACER.events()}
        assert "replica.pull" in names
        assert "snapshot.serve" in names
        # the refresh's spans share ONE trace id (client/shard stitch)
        pulls = [e for e in ttrace.TRACER.events()
                 if e["name"] == "replica.pull"]
        serves = [e for e in ttrace.TRACER.events()
                  if e["name"] == "snapshot.serve"]
        assert pulls and serves
        assert any(s["args"].get("trace") == pulls[-1]["args"]["trace"]
                   for s in serves)
    finally:
        rep.close()


# ---------------------------------------------------------------------- #
# the obs-surface lint (satellite: tier-1 wraps the static check)
# ---------------------------------------------------------------------- #
def test_check_obs_surface_clean():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import check_obs_surface
    findings = check_obs_surface.check()
    assert findings == [], "\n".join(findings)
    # the scanners actually see the surface (not vacuously clean)
    ops = check_obs_surface.wire_opcodes()
    assert "MSG_SNAPSHOT" in ops and "MSG_BATCH" in ops
    flags = check_obs_surface.defined_flags()
    assert "trace_ids" in flags and "ps_timeout" in flags
    assert "step_" + "profile" not in flags


def test_check_obs_surface_catches_gaps(monkeypatch, tmp_path):
    """A new opcode without coverage / a new flag without a TUNING row
    must be findings — the lint's reason to exist."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import check_obs_surface
    monkeypatch.setattr(
        check_obs_surface, "wire_opcodes",
        lambda: check_obs_surface.__dict__["_FAKE_OPS"], raising=False)
    check_obs_surface._FAKE_OPS = (
        sorted(set(list(__import__(
            "multiverso_tpu.telemetry.flightrec",
            fromlist=["x"]).MSG_EV_COVERAGE) + ["MSG_BRAND_NEW"])))
    findings = check_obs_surface.check()
    assert any("MSG_BRAND_NEW" in f for f in findings)


# ---------------------------------------------------------------------- #
# run_bench regression flags (satellite 6)
# ---------------------------------------------------------------------- #
def test_run_bench_flags_stall_and_recompiles():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import run_bench

    def hl(stall, recompiles):
        return {"extra": {"profile": {"stall_fraction": stall,
                                      "steady_recompiles": recompiles}}}

    # >2x stall growth flagged
    out = run_bench.flag_regressions(hl(0.05, 0), hl(0.15, 0))
    assert any("stall" in f for f in out)
    # within band: silent
    assert run_bench.flag_regressions(hl(0.05, 0), hl(0.08, 0)) == []
    # a healthy 0.0 baseline must NOT suppress the flag forever: the
    # comparison floors the prior at _STALL_BASELINE_FLOOR
    out = run_bench.flag_regressions(hl(0.0, 0), hl(0.35, 0))
    assert any("stall" in f for f in out)
    assert run_bench.flag_regressions(hl(0.0, 0), hl(0.08, 0)) == []
    # ANY nonzero steady recompile count flagged, even with no prior
    out = run_bench.flag_regressions(None, hl(0.05, 3))
    assert any("recompile" in f for f in out)
    # never fails (returns strings, raises nothing) and zero is quiet
    assert run_bench.flag_regressions(hl(0.05, 0), hl(0.05, 0)) == []
