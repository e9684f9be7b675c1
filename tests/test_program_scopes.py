"""The compiled program's map (telemetry/devstats.py): which ``mv.*``
scope and which pass every instruction of a compiled program belongs to
(``program_map``, ``place_of``), the one ``xla.program`` record a trainer
leaves a program (``describe_program``: the language-model ``Trainer``,
``train_fused``, ``train_ps_blocks``), the two counts ``xla.compile``
carries of Python's part of a compile, the join of a device trace's
operations with the maps (``scope_seconds``) and the operator's command
over it (``tools/dump_metrics.py scopes``)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

import multiverso_tpu as mv
from multiverso_tpu.telemetry import devstats
from multiverso_tpu.telemetry import trace as ttrace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import dump_metrics  # noqa: E402

PLACES = {
    "jit(f)/jvp(mv.lm.head)/reduce_sum": ("mv.lm.head", "fwd"),
    "jit(f)/transpose(jvp(mv.outer))/mul": ("mv.outer", "bwd"),
    "jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
    "rematted_computation/mv.lm.scan/cos": ("mv.lm.scan", "remat"),
    "jit(f)/jvp()/while/body/add": (devstats.UNSCOPED, "fwd"),
    "jit(step)/jvp()/mv.lm.moe.experts/mv.lm.moe.route/top_k":
        ("mv.lm.moe.route", "fwd"),
    "jit(step)/mv.lm.update/mv.rowapply.rule/jit(_pad)/pad":
        ("mv.rowapply.rule", "fwd"),
    # a name that merely holds "mv." is no scope; no path at all
    "jit(f)/jvp(rmv.x)/transpose": (devstats.UNSCOPED, "fwd"),
    "": (devstats.UNSCOPED, "fwd"),
}


@pytest.mark.parametrize("op_name", sorted(PLACES))
def test_place_of_reads_scope_and_pass_off_the_path(op_name):
    assert devstats.place_of(op_name) == PLACES[op_name]


def _scoped_fn():
    def inner(c, x):
        with jax.named_scope("mv.t.scan"):
            # a product stands alone: the one made again keeps its path
            return c + jnp.sin(c @ x) @ x, None

    def f(w, x):
        def loss(w):
            with jax.named_scope("mv.t.outer"):
                h = jnp.tanh(w) * 2
            c, _ = jax.lax.scan(jax.checkpoint(inner), h, x)
            with jax.named_scope("mv.t.outer"):
                with jax.named_scope("mv.t.head"):
                    return jnp.sum(jnp.exp(c @ c.T))
        return jax.value_and_grad(loss)(w)

    return jax.jit(f, donate_argnums=(0,)), (jnp.ones((8, 8)),
                                             jnp.ones((5, 8, 8)))


def _rows(scopes):
    return {(scope, pas): {name for name, _ in rows}
            for scope, by in scopes.items() for pas, rows in by.items()}


def test_program_map_files_forward_remat_and_backward_under_their_scopes():
    fn, args = _scoped_fn()
    text = fn.lower(*args).compile().as_text()
    got = devstats.program_map(text)
    rows = _rows(got["scopes"])
    assert got["module"] == "jit_f"
    # the scan's body runs forward, is made again and runs backward
    assert {("mv.t.scan", "fwd"), ("mv.t.scan", "remat"),
            ("mv.t.scan", "bwd")} <= set(rows)
    # the nested scope wins over the one round it; neither is made again
    assert ("mv.t.head", "fwd") in rows and ("mv.t.head", "bwd") in rows
    assert ("mv.t.head", "remat") not in rows
    assert not any(scope == "mv.t.outer" and pas == "remat"
                   for scope, pas in rows)
    # the loops themselves and their counters carry no scope
    unscoped = set().union(*(names for (scope, _), names in rows.items()
                             if scope == devstats.UNSCOPED))
    assert any(name.startswith("while") for name in unscoped)
    listed = [r for by in got["scopes"].values() for rs in by.values()
              for r in rs]
    assert got["instructions"] == len(listed)
    assert got["scoped"] == sum(len(names) for (scope, _), names
                                in rows.items()
                                if scope != devstats.UNSCOPED)
    assert 0 < got["scoped"] < got["instructions"]
    # shapes as a trace prints them
    assert all(shape == "" or shape[-1] == "]" and "{" not in shape
               for _, shape in listed)


def test_program_map_leaves_out_the_insides_of_fused_computations():
    text = """HloModule jit_g, is_scheduled=true

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %sin.1 = f32[8]{0} sine(%p0), metadata={op_name="jit(g)/mv.a/sin"}
}

%fused_two (p1: f32[8]) -> (f32[8], f32[8]) {
  %p1 = f32[8]{0} parameter(0)
  %cos.2 = f32[8]{0} cosine(%p1), metadata={op_name="jit(g)/jvp()/while/body/mv.a/mv.a.rot/cos"}
  %neg.3 = f32[8]{0} negate(%p1), metadata={op_name="jit(g)/jvp()/while/body/mv.a/mv.a.rot/neg"}
  ROOT %tuple.8 = (f32[8]{0}, f32[8]{0}) tuple(%cos.2, %neg.3)
}

%add_region (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y), metadata={op_name="jit(g)/mv.a/reduce_sum"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %v = f32[8]{0:T(256)} get-tuple-element(%t), index=1
  %fusion.7 = f32[8]{0:T(256)} fusion(%v), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(g)/jvp()/while/body/mv.a/sin"}
  %fusion.8 = (f32[8]{0:T(256)}, f32[8]{0:T(256)}) fusion(%v), kind=kLoop, calls=%fused_two
  %flash.2 = bf16[8]{0:T(256)} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(g)/jvp()/while/body/mv.a/pallas_call"}
  %alloc.3 = f32[8]{0} custom-call(), custom_call_target="AllocateBuffer"
  %copy-start.1 = (f32[8]{0:T(256)}, f32[8]{0:T(256)S(1)}, u32[]{:S(2)}) copy-start(%fusion.7)
  %copy-done.1 = f32[8]{0:T(256)} copy-done(%copy-start.1)
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%i, %copy-done.1)
}

%cond (t: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%t.1), index=0
  %c = s32[] constant(5)
  ROOT %lt.2 = pred[] compare(%i.1, %c), direction=LT
}

%never_called (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  ROOT %neg.4 = f32[8]{0} negate(%q), metadata={op_name="jit(g)/mv.b/neg"}
}

ENTRY %main.1 (a: f32[8]) -> f32[] {
  %a = f32[8]{0} parameter(0)
  %z = s32[] constant(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%z, %a)
  %while.5 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(g)/jvp()/while"}
  %out = f32[8]{0} get-tuple-element(%while.5), index=1
  %b = f32[8]{0} bitcast(%out)
  ROOT %reduce.6 = f32[] reduce(%b, %z), dimensions={0}, to_apply=%add_region, metadata={op_name="jit(g)/transpose(jvp(mv.a))/reduce_sum"}
}
"""
    got = devstats.program_map(text)
    assert got["module"] == "jit_g"
    assert got["scopes"] == {
        "mv.a": {"fwd": [["fusion.7", "f32[8]"]],
                 "bwd": [["reduce.6", "f32[]"]]},
        # a fusion of two results has no path of its own: its insides'
        "mv.a.rot": {"fwd": [["fusion.8", "f32[8]"]]},
        # a Pallas kernel apart from XLA's instructions of its scope
        "mv.a" + devstats.KERNEL: {"fwd": [["flash.2", "bf16[8]"]]},
        devstats.UNSCOPED: {"fwd": [["while.5", "s32[]"],
                                    ["alloc.3", "f32[8]"],
                                    ["copy-start.1", "f32[8]"],
                                    ["copy-done.1", "f32[8]"],
                                    ["lt.2", "pred[]"]]}}
    assert (got["instructions"], got["scoped"]) == (9, 4)


def _programs(events):
    return [e for e in events if e["name"] == devstats.PROGRAM_SPAN]


def _compiles(events):
    return [e for e in events if e["name"] == "xla.compile"]


def test_describe_program_after_the_first_call_starts_no_compile():
    mv.init()
    fn, (w, x) = _scoped_fn()
    _, grad = fn(w, x)
    before = len(ttrace.events())
    heard = devstats.DEVSTATS.compile_events()
    got = devstats.describe_program("t.step", fn, grad, x)
    assert devstats.DEVSTATS.compile_events() == heard
    new = ttrace.events()[before:]
    assert _compiles(new) == []
    [rec] = _programs(new)
    a = rec["args"]
    assert a == got and a["recompiled"] == 0 and a["program"] == "t.step"
    assert a["module"] == "jit_f"
    assert {"mv.t.scan", "mv.t.head", devstats.UNSCOPED} <= set(a["scopes"])
    # the donated argument is aliased to a result, and counted as such
    assert a["argument_bytes"] >= 8 * 8 * 4 + 5 * 8 * 8 * 4
    assert a["alias_bytes"] >= 8 * 8 * 4 and a["output_bytes"] > 0
    assert a["temp_bytes"] >= 0 and a["code_bytes"] >= 0
    assert rec["dur"] > 0 and json.loads(json.dumps(rec)) == rec


def test_describe_program_is_silent_with_the_flag_off_or_no_program():
    mv.init()
    fn, (w, x) = _scoped_fn()
    before = len(ttrace.events())
    devstats.DEVSTATS.enabled = False
    try:
        assert devstats.describe_program("t.off", fn, w, x) is None
    finally:
        devstats.DEVSTATS.enabled = True
    assert devstats.describe_program("t.none", lambda *a: 0, w, x) is None
    assert _programs(ttrace.events()[before:]) == []


def test_xla_compile_carries_what_python_spent_before_it():
    mv.init()
    before = len(ttrace.events())

    @jax.jit
    def fresh(x):
        return jnp.cumsum(jnp.tanh(x) * 5 + 2)

    fresh(jnp.arange(11.0)).block_until_ready()
    ours = [e for e in _compiles(ttrace.events()[before:])
            if e["args"]["fun"] == "jit(fresh)"]
    assert len(ours) == 1
    a = ours[0]["args"]
    assert a["trace_s"] > 0 and a["lower_s"] > 0 and a["seconds"] > 0
    # a describe in between is a trace event with no compile: it is not
    # counted into the next compile
    fn, (w, x) = _scoped_fn()
    fn(w, x)
    devstats.describe_program("t.between", fn, w, x)
    assert devstats.DEVSTATS._take_led() == (0.0, 0.0)


def test_a_program_that_will_not_describe_itself_says_so_in_the_log(capsys):
    mv.init()
    _, (w, x) = _scoped_fn()
    before = len(ttrace.events())

    class Refuses:
        def lower(self, *args):
            raise ValueError("no text today")

    assert devstats.describe_program("t.refuses", Refuses(), w, x) is None
    assert _programs(ttrace.events()[before:]) == []
    said = capsys.readouterr()
    assert "t.refuses" in said.out + said.err
    assert "no text today" in said.out + said.err


# ---------------------------------------------------------------------- #
# the trainers' sites
# ---------------------------------------------------------------------- #
def test_trainer_records_its_steps_program_exactly_once():
    from multiverso_tpu.models import mla_moe
    mv.init()
    cfg = mla_moe.MLAMoEConfig(vocab=64, n_moe_layers=1, attn="xla",
                               loss_chunk=32, compute_dtype=jnp.float32)
    tables = mla_moe.make_tables(cfg, 0, 0.1, updater="adam")
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, cfg.vocab)
    trainer = mla_moe.Trainer(cfg, tables)
    before = len(ttrace.events())
    trainer.step(tokens)
    first = len(ttrace.events())
    trainer.step(tokens)
    trainer.step(tokens)
    trainer.adopt()
    events = ttrace.events()[before:]
    [rec] = _programs(events)
    a = rec["args"]
    assert a["program"] == "lm.step" and a["module"] == "jit_step"
    assert {"mv.lm.head", "mv.lm.attn", "mv.lm.moe.experts",
            "mv.rowapply.rule", "mv.lm.norm.pre", "mv.lm.params",
            "mv.lm.update", "mv.lm.embed"} <= set(a["scopes"])
    assert {"fwd", "remat", "bwd"} == set(a["scopes"]["mv.lm.attn"])
    # the sorted buffer's passes have derivative rules of their own
    # (``parallel/moe._dispatch``, ``_combine``): a rule's instructions
    # are filed where its call stands, its loops' bodies among them
    for scope, passes in (("mv.lm.moe.dispatch", {"fwd", "remat", "bwd"}),
                          ("mv.lm.moe.combine", {"fwd", "bwd"})):
        assert passes <= set(a["scopes"][scope])
        assert all(any(name.startswith("while") for name, _ in rows)
                   for pas, rows in a["scopes"][scope].items()
                   if pas in passes)
    assert set(a["scopes"]["mv.rowapply.rule"]) == {"fwd"}
    assert 0.5 < a["scoped"] / a["instructions"] <= 1.0
    # recorded inside the first step's span, before anything of a window
    step = next(e for e in events if e["name"] == "lm.step")
    assert rec["parent"] == step["id"] and rec["prof"] is False
    # the tables' states are aliased to the program's results
    assert a["alias_bytes"] > 0.9 * a["output_bytes"]
    # where the first call's arguments differ from every later call's (a
    # table hands its state over sharded by name, the program returns it
    # otherwise), the SECOND call's program is what the record describes
    # and what the describing compiled: the later steps compile nothing
    assert a["recompiled"] in (0, 1)
    assert [e for e in _compiles(ttrace.events()[first:])
            if e["args"]["fun"] == "jit(step)"] == []
    assert json.loads(json.dumps(rec)) == rec
    # a record stays small beside the ring (the chip's largest: 275 KB)
    assert len(json.dumps(rec)) < 2_000_000


def _tiny_we(**kw):
    from multiverso_tpu.apps.word_embedding import (WEConfig, WordEmbedding,
                                                    synthetic_corpus)
    from multiverso_tpu.data.dictionary import Dictionary

    mv.init()
    tokens = synthetic_corpus(6_000, vocab=60, seed=0)
    cfg = WEConfig(**{**dict(size=8, min_count=1, batch_size=64, negative=2,
                             window=2, epoch=1, sample=0), **kw})
    we = WordEmbedding(cfg, Dictionary.build(tokens, 1))
    return we, we.prepare_ids(tokens)


@pytest.mark.parametrize("mode", ["sg_shared", "sg"])
def test_train_fused_records_its_epoch_program_once(mode):
    we, ids = _tiny_we(**({} if mode == "sg_shared"
                          else {"shared_negatives": 0}))
    before = len(ttrace.events())
    we.train_fused(ids, epochs=2)
    we.train_fused(ids, epochs=1)
    events = ttrace.events()[before:]
    [rec] = _programs(events)
    a = rec["args"]
    assert a["program"] == "we.fused" and a["recompiled"] == 0
    assert {"mv.fused.gather", "mv.fused.grad",
            "mv.fused.scatter"} <= set(a["scopes"])
    call = next(e for e in events if e["name"] == "we.fused")
    assert rec["parent"] == call["id"]


def test_train_ps_blocks_records_its_block_program_once():
    we, ids = _tiny_we(data_block_size=2000)
    before = len(ttrace.events())
    we.train_ps_blocks(ids, epochs=1)
    events = ttrace.events()[before:]
    assert len([e for e in events if e["name"] == "we.block.dispatch"]) > 1
    # the block's program and the one that made its plans ahead of it
    rec, ahead = _programs(events)
    a = rec["args"]
    assert a["program"] == "we.blocks" and a["recompiled"] == 0
    assert {"mv.pull", "mv.scan", "mv.rowapply.rule"} <= set(a["scopes"])
    # the push's instructions lie in the row apply's own scopes
    assert any(s.startswith("mv.rowapply.") for s in a["scopes"])
    assert ahead["args"]["program"] == "we.blocks.ahead"
    assert ahead["args"]["recompiled"] == 0
    assert "mv.scan.plan" in ahead["args"]["scopes"]


# ---------------------------------------------------------------------- #
# the join
# ---------------------------------------------------------------------- #
def _record(program, scopes):
    return {"name": devstats.PROGRAM_SPAN, "args": {
        "program": program, "module": "jit_" + program, "scopes": scopes,
        "instructions": 9, "scoped": 7, "recompiled": 0,
        "argument_bytes": 4e9, "output_bytes": 4e9, "alias_bytes": 4e9,
        "temp_bytes": 2e9, "code_bytes": 1e8}, "dur": 300e3}


STEP = _record("lm.step", {
    "mv.lm.attn": {"fwd": [["flash.1", "bf16[2,8]"]],
                   "bwd": [["flash.2", "bf16[2,8]"], ["fusion.4", "f32[8]"]]},
    "mv.lm.head": {"fwd": [["fusion.9", "f32[8,64]"]]},
    devstats.UNSCOPED: {"fwd": [["while.1", "s32[]"], ["copy.3", "f32[8]"],
                                ["fusion.5", "f32[8]"]]}})
OTHER = _record("lm.forward", {
    "mv.lm.embed": {"fwd": [["fusion.5", "f32[8]"]]},      # claimed twice
    "mv.lm.head": {"fwd": [["fusion.9", "f32[8,64]"]]}})   # and agreed on


def _op(name, shape, start, dur):
    return (name, f"{shape}{{0:T(256)}} fusion(f32[4]{{0}} %x)", start, dur)


CHIP = [
    _op("while.1", "(s32[], f32[8])", 0.0, 10.0),   # holds the next four
    _op("flash.1", "bf16[2,8]", 0.0, 3.0),
    _op("flash.2", "bf16[2,8]", 3.0, 2.0),
    _op("fusion.4", "f32[8]", 5.0, 1.0),
    _op("copy.3", "f32[8]", 6.0, 4.0),
    _op("fusion.9", "f32[8,64]", 10.0, 2.0),
    _op("fusion.9", "f32[8,128]", 12.0, 1.0),       # another shape: no map
    _op("fusion.77", "f32[8]", 13.0, 0.5),          # in no map
    _op("fusion.5", "f32[8]", 14.0, 0.25),          # two programs differ
]


def test_scope_seconds_files_the_operations_that_hold_no_other():
    got = devstats.scope_seconds({"/device:TPU:0": CHIP}, [STEP, OTHER])
    assert got["chips"] == 1
    assert got["seconds"] == {
        "mv.lm.attn": {"fwd": 3.0, "bwd": 3.0},
        devstats.UNSCOPED: {"fwd": 4.0},            # not the while's 10
        "mv.lm.head": {"fwd": 2.0},
        devstats.UNKNOWN: {devstats.NO_PASS: 1.5},
        devstats.AMBIGUOUS: {devstats.NO_PASS: 0.25}}
    assert got["busy_s"] == pytest.approx(14.25 - 0.5)    # a gap at 13.5
    assert got["filed_s"] == pytest.approx(12.0)
    assert got["longest"]["mv.lm.attn"] == [
        ["flash.1", "bf16[2,8]", 3.0], ["flash.2", "bf16[2,8]", 2.0],
        ["fusion.4", "f32[8]", 1.0]]
    assert got["longest"][devstats.UNKNOWN][0] == ["fusion.9", "f32[8,128]",
                                                   1.0]


def test_scope_seconds_is_a_mean_over_the_chips_that_ran_anything():
    half = [(n, t, s, d / 2) for n, t, s, d in CHIP]
    got = devstats.scope_seconds(
        {"/device:TPU:0": CHIP, "/device:TPU:1": half, "/device:TPU:2": []},
        [STEP])
    assert got["chips"] == 2
    assert got["seconds"]["mv.lm.attn"] == {"fwd": 2.25, "bwd": 2.25}
    # with the one program alone nothing is ambiguous
    assert devstats.AMBIGUOUS not in got["seconds"]
    assert got["seconds"][devstats.UNSCOPED] == {"fwd": 0.75 * 4.25}
    assert got["longest"]["mv.lm.head"] == [["fusion.9", "f32[8,64]", 1.5]]
    # and with no program at all everything is unknown
    none = devstats.scope_seconds({"/device:TPU:0": CHIP}, [])
    assert set(none["seconds"]) == {devstats.UNKNOWN}
    assert none["filed_s"] == 0.0
    empty = devstats.scope_seconds({}, [STEP])
    assert empty["chips"] == 0 and empty["busy_s"] == 0.0


def test_the_join_of_a_real_program_with_operations_named_from_its_text():
    """The map of a compiled program joined with operations made from the
    same text, as a trace would name them: everything is filed."""
    fn, args = _scoped_fn()
    text = fn.lower(*args).compile().as_text()
    rec = {"args": devstats.program_map(text)}
    ops, t = [], 0.0
    for line in text.splitlines():
        m = devstats._INSTR.match(line)
        if m and " fusion(" in line:
            ops.append((m.group(1), line[m.end():], t, 1.0))
            t += 1.0
    assert ops
    got = devstats.scope_seconds({"chip": ops}, [rec])
    assert devstats.UNKNOWN not in got["seconds"]
    assert got["filed_s"] == pytest.approx(len(ops))
    assert "mv.t.scan" in got["seconds"]


# ---------------------------------------------------------------------- #
# the operator's command
# ---------------------------------------------------------------------- #
def _span_file(tmp_path, steps=2):
    events = [STEP, OTHER] + [
        {"name": "lm.step.device", "cat": "device", "prof": True,
         "ts": 1e6 * k, "dur": 9e5, "args": {}} for k in range(steps)]
    events.append({"name": "lm.step.device", "cat": "device", "prof": False,
                   "ts": 0.0, "dur": 9e5, "args": {}})      # the warm-up
    path = tmp_path / "trace-rank0.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in events))
    return str(path)


def test_dump_metrics_scopes_prints_the_table_a_step(tmp_path, monkeypatch,
                                                     capsys):
    from benchmark import trace_reduce
    from benchmark.trace_reduce import Op, Span

    ops = {"/device:TPU:0": [Op(n, s, d, t) for n, t, s, d in CHIP]
           + [Op("fusion.4", 20.0, 5.0, "f32[8] fusion()")]}  # past the window
    spans = [Span(trace_reduce.WINDOW_SPAN, 0.0, 14.5)]
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d + "/x.pb")
    monkeypatch.setattr(trace_reduce, "read_xplane",
                        lambda path: (ops, spans))
    assert dump_metrics.main(["scopes", str(tmp_path), _span_file(tmp_path),
                              "--steps-from", "lm.step.device"]) == 0
    out = capsys.readouterr().out
    lines = {line.split()[0]: line.split() for line in out.splitlines()
             if line.startswith("  mv.") or line.startswith("  _")}
    # 2 steps: ms a step is half the window's; busy 13.75 s
    assert lines["mv.lm.attn"][1:] == ["1500.000", "0.000", "1500.000",
                                       "3000.000", "43.64"]
    assert lines[devstats.UNSCOPED][4] == "2000.000"
    assert lines[devstats.UNKNOWN][4] == "750.000"
    assert "(2 step(s), 1 chip(s), busy 6875.000 ms)" in out
    assert "coverage: 87.27% of busy filed" in out
    assert "_ambiguous_ 1.82%" in out
    assert "mv.lm.attn: flash.1 bf16[2,8] 1500.000" in out
    assert ("lm.step (jit_lm.step): 4.000 4.000 4.000 2.000 0.100; 9 7; "
            "300.0 ms, recompiled 0") in out


def test_dump_metrics_scopes_says_what_is_missing(tmp_path):
    ops = {"/device:TPU:0": CHIP}
    none = dump_metrics.format_scopes(ops, [{"name": "lm.step"}])
    assert "no xla.program record" in none
    assert "no lm.step.device device span" in dump_metrics.format_scopes(
        ops, [STEP], "lm.step.device")
    whole = dump_metrics.format_scopes(ops, [STEP])
    assert "ms the window (1 step(s)" in whole
