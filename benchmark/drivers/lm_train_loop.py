"""The ninth language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/ouro`` (a stack of sandwich-normed dense
blocks run four times with the same tables as ONE loop of the program, an
exit gate after every pass, a loss that is the expected cross-entropy over
the four exits less an entropy term; no expert layer) on
``models/mla_moe``'s decoder path, tables, step and ``Trainer``. The load
(``lm_train.lm_batches``) and the window's loop are ``drivers/lm_train``'s;
there is no router, so nothing is calibrated and no forward-only program is
compiled. What is this file's own is the model's configuration, what
``layers/loop``, ``layers/attn`` and ``layers/attnmix`` ask of the window,
and the comparison (another reference, classes of tables, limits and
controls of its own). ``benchmark/LM_LOOP.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice: the one program compiled); Adam's state back to
zero and the comparison with ``reference/ouro`` on one pool batch through
the measured step.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import attn_shapes, loop_shapes
from benchmark.drivers import lm_train
from benchmark.layers import attn as attn_layer
from benchmark.layers import attnmix, loop
from benchmark.reference import ouro as ref

# The limits of the comparison, set as ``lm_train``'s are and for its
# reason (bfloat16 operands against a float32 reference, here through 32
# block applications forward, made again and backward). There is no router:
# nothing is discontinuous, and what is compared beside the loss and the
# gradients is what the loop and its exits are: each pass's mean loss, the
# mean exit distribution and the largest ``|p - p_ref|`` of any position.
# Four classes of table (:func:`table_class`): ``vocab`` (the embedding and
# the head, whose rows most tokens never touch); ``norms`` (a gain's
# gradient is a sum over every position, of every pass for a layer's);
# ``gate`` (``exit.w`` with ``exit.b``, held as a KIND: the bias is ONE
# number, a sum over 4,095 positions x 3 passes of signed terms, which on
# some seed nearly cancels, and its own size is then no yardstick: the
# 2,049 numbers are one vector); and the rest, ``plain``. Each limit but one
# lies between two readings on the chip (benchmark/LM_LOOP.md has the table
# with its seeds; PERF.md section 6, PR 64): the largest the program showed
# over 15 seeds of the driver's range, and the smallest the CONTROLS showed
# over 2: the reference computed as a faulty program would, in the measured
# step's place (``benchmark/lm_loop_control.py``), each of which has to come
# out as not agreeing. ``untrained_weights`` leaves the forward pass as it
# is and reads 0 on the first four lines by design: the gradients hold it
# (the gate's kind 0.82 and more). Program's largest / controls' smallest
# (which) / limit:
#   TOL_LOSS       1.86e-4 / 7.15e-4 (``one_pass_less``; ``operands_float8``
#                  9.07e-4) / 4e-4.
#   TOL_PASS_LOSS  6.52e-4 (one seed of 15; the next 4.2e-4) / 0.038
#                  (``no_renorm``; ``one_pass_less`` 1.0) / 2e-3: the one
#                  limit NOT under every control's reading:
#                  ``operands_float8`` read 1.15e-3 on one seed of two (7.1e-3
#                  on the other), 1.76 times the program's largest, and a
#                  limit between those two would refuse a seed sooner than
#                  it told the precision, which every class of gradient
#                  tells 8 times over. It is there for a pass left out or
#                  computed wrongly.
#   TOL_P_MEAN     1.76e-3 / 8.7e-3 (``operands_float8``) / 4e-3.
#   TOL_P          8.0e-3 / 0.0747 (``operands_float8``) / 0.025.
#   TOL_NORM       plain 0.0336 / 0.3445 (``operands_float8``) / 0.11; vocab
#                  0.0276 / 0.1482 (``one_pass_less``) / 0.064; norms 0.0285 /
#                  0.282 (``operands_float8``) / 0.09; gate 0.0150 / 0.1158
#                  (``operands_float8``) / 0.042.
#   TOL_ELEM       plain 0.0457 / 0.3263 (``one_pass_less``) / 0.12; vocab
#                  0.0316 / 0.1322 (``one_pass_less``) / 0.065; norms 0.0432 /
#                  0.2887 (``operands_float8``) / 0.11; gate 0.0140 / 0.111
#                  (``operands_float8``) / 0.04.
#   TOL_MOVE       ``lm_train``'s: seen 0.247 of the limit.
TOL_LOSS = 4e-4
TOL_PASS_LOSS = 2e-3        # |l_t - ref| / max(|ref|, 1), the worst pass
TOL_P_MEAN = 4e-3           # |mean p_t - ref|, the worst pass
TOL_P = 0.025               # |p_t(i) - ref|, the worst position and pass
TOL_NORM = {"plain": 0.11, "vocab": 0.064, "norms": 0.09, "gate": 0.042}
TOL_ELEM = {"plain": 0.12, "vocab": 0.065, "norms": 0.11, "gate": 0.04}
TOL_MOVE = lm_train.TOL_MOVE
# what stands in the measured step's place, by name: the context under
# which the reference is traced
CONTROLS = {
    "operands_float8": lambda: ref.rounded_operands(lm_train.CONTROL),
    "one_pass_less": lambda: ref.loop_control("one_pass_less"),
    "no_renorm": lambda: ref.loop_control("no_renorm"),
    "untrained_weights": lambda: ref.loop_control("untrained_weights")}
GATE = ("exit.w", "exit.b")
# a host that stands still leaves the device the step it runs and the one
# queued ahead: the kernels a trace may lack of those a window ran are two
# steps' (``lm_train_hybrid.KERNELS_A_TRACE_MAY_LOSE``, at this cell's
# count a step)
STEPS_A_TRACE_MAY_LOSE = 2


def table_class(name: str) -> str:
    if name in GATE:
        return "gate"
    if name in ("embed", "head"):
        return "vocab"
    return "norms" if name.endswith("norm") else "plain"


def _model_config(cell):
    from multiverso_tpu.models import ouro

    c = cell.config
    if set(c["layer_types"][:int(c["num_hidden_layers"])]) != {
            "full_attention"}:
        raise ValueError("every layer of the looped stack attends in full")
    return ouro.OuroConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), n_layers=int(c["num_hidden_layers"]),
        passes=int(c["total_ut_steps"]),
        exit_coef=float(c["exit_entropy_coef"]),
        rope_theta=float(c["rope_theta"]),
        dense_ffn=int(c["intermediate_size"]), eps=float(c["rms_norm_eps"]))


def setup(cell, controls=()) -> Dict[str, Any]:
    """``lm_train.setup``'s order without a router's part. ``controls``:
    names of :data:`CONTROLS`, see :func:`_compare`
    (``lm_loop_control.py`` gives them)."""
    from multiverso_tpu.models import mla_moe
    from multiverso_tpu.updaters import AdamUpdater, AddOption

    c, tr = cell.config, cell.traffic
    cfg = _model_config(cell)
    with cell.timed("tables_from_seed"):
        tables = mla_moe.make_tables(
            cfg, cell.seed, float(c["init_scale"]),
            updater=AdamUpdater(beta1=float(c["adam_beta1"]),
                                beta2=float(c["adam_beta2"]),
                                eps=float(c["adam_eps"])),
            scales={k: float(v) for k, v in c["init_scales"].items()})
    with cell.timed("batches"):
        pool = jax.block_until_ready(jnp.asarray(lm_train.lm_batches(
            cfg.vocab, int(tr["sequences"]), int(tr["positions"]),
            int(tr["batch_pool"]), float(tr["zipf_a"]),
            tr["document_tokens"], int(tr["end_of_document_id"]),
            cell.seed)))
    opt = AddOption(learning_rate=float(c["learning_rate"]))
    trainer = mla_moe.Trainer(cfg, tables, opt)
    state = {"cell": cell, "cfg": cfg, "tables": tables, "pool": pool,
             "trainer": trainer, "opt": opt}
    with cell.timed("warmup"):
        for k in range(2):          # fresh buffers, then the donated ones
            trainer.step(pool[k % pool.shape[0]])
    with cell.timed("reference_check"):
        state["verdict"] = _compare(state, controls)
    return state


# ---------------------------------------------------------------------- #
# the window
# ---------------------------------------------------------------------- #
def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """``lm_train.window``'s loop (a step dispatched ahead of the last
    one's read-back) without a router's counts; what the step hands back of
    its exits, as means over the window; and what the readers ask: the
    flash kernels a window's steps run (four a core a pass) and their
    operations, the stack's and the exits' products (``loop_shapes``)."""
    from multiverso_tpu.models import mla_moe

    trainer, pool, cfg = state["trainer"], state["pool"], state["cfg"]
    c = state["cell"].config
    whole, losses, exits = [], [], []

    def took(done, last):
        if done is not None:
            losses.append(done[0])
            exits.append(trainer.exits)
            whole.append((time.perf_counter() - last) * 1e3)

    t0 = now = time.perf_counter()
    i = 0
    while now - t0 < seconds:
        with jax.profiler.TraceAnnotation("bench.step"):
            took(trainer.step_ahead(pool[i % pool.shape[0]]), now)
        i += 1
        now = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.step"):
        took(trainer.drain(), now)
    now = time.perf_counter()
    sequences, positions = int(pool.shape[1]), int(pool.shape[2])
    # the window's means of what the steps handed back, as the spans say
    # a step's (``mla_moe.exit_facts``)
    means = {key: np.mean([np.asarray(e[key], np.float64) for e in exits],
                          axis=0) for key in ("loss", "p_mean", "entropy")}
    facts = {"steps": i, "tokens_a_step": sequences * positions,
             "loss_first": losses[0], "loss_last": losses[-1],
             "loop_passes": cfg.passes, "loop_layers": cfg.n_layers,
             "loop_block_runs": i * cfg.passes * cfg.n_layers,
             "exit_entropy_most": math.log(cfg.passes),
             **mla_moe.exit_facts(means)}
    kernels = 4 * i * cfg.n_layers * cfg.passes
    return {"work": i * sequences * (positions - 1), "elapsed_s": now - t0,
            "attempted": i,
            "failed": int(sum(1 for x in losses if not np.isfinite(x))),
            "losses": losses, "spans_ms": {"step": whole}, "facts": facts,
            # a block's attention core is four kernels a pass: forward,
            # forward again in the backward pass, dQ, dK with dV
            "attention_kernels": kernels,
            "attnmix_kernels": {"full": kernels},
            "attnmix_flops": {
                "full": i * cfg.n_layers * cfg.passes
                * attn_shapes.core_flops(sequences, cfg.n_heads, positions,
                                         cfg.head_dim)},
            "loop_flops": {
                "stack": i * loop_shapes.stack_flops(c, sequences, positions),
                "head": i * loop_shapes.head_flops(c, sequences, positions)}}


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """The comparison made in set-up, and after the window: every loss
    finite, the last under the same batch's a turn of the pool earlier; the
    states adopted back into their tables.

    ``run.py`` calls this between stopping the trace and reducing it, and
    deletes the trace before a reader runs: on a traced run the flash
    kernels' sums (``layers/attn``, ``layers/attnmix``) and the join by
    scope (``layers/loop``) are made here and ``run`` carries them to their
    readers. Where the trace lacks a stretch of the device's line (a host
    that stood still drains no trace buffer: ``lm_train_hybrid.check``) its
    kernels are fewer than the steps ran; the stretch is missing from the
    busy time too, so up to :data:`STEPS_A_TRACE_MAY_LOSE` steps short the
    counts and the operations expected are those of the kernels seen."""
    state["trainer"].adopt()
    detail = dict(state["verdict"])
    losses = run["losses"]
    detail["losses_finite"] = bool(np.all(np.isfinite(losses)))
    turn = int(state["pool"].shape[0])
    detail["loss_fell"] = bool(len(losses) <= turn
                               or losses[-1] < losses[-1 - turn])
    name = state["cell"].name
    run["attention_s"] = attn_layer.kernel_seconds(name)
    run["attnmix_s"] = attnmix.kernel_seconds(name)
    run["loop_s"] = loop.scope_seconds(name)
    seen = int(run["attention_s"].get("kernels", 0))
    expected = int(run["attention_kernels"])
    detail["attention_kernels"] = {"seen": seen, "expected": expected}
    a_step = expected // max(int(run["attempted"]), 1)
    if 0 < expected - seen <= STEPS_A_TRACE_MAY_LOSE * a_step:
        run["attention_kernels"] = seen
        run["attnmix_kernels"] = {"full": seen}
        run["attnmix_flops"] = {
            "full": run["attnmix_flops"]["full"] * seen // expected}
        run["loop_flops"] = {k: v * seen // expected
                             for k, v in run["loop_flops"].items()}
    if run["loop_s"]:
        # the whole join, for a reader of the log: where the step's time
        # goes by scope and pass (what ``dump_metrics.py scopes`` prints)
        detail["scope_s"] = {k: run["loop_s"][k] for k in (
            "every_scope", "filed_s", "busy_s")}
    return {"correct": bool(detail["step_agrees"] and detail["losses_finite"]
                            and detail["loss_fell"]),
            "detail": detail}


# ---------------------------------------------------------------------- #
# the comparison with the reference
# ---------------------------------------------------------------------- #
def _held_to(want: Dict[str, Any], got: Dict[str, Any], grad_of,
             passes: int) -> Dict[str, Any]:
    """A step's loss, exits (``got``: ``loss``, and ``exits`` as the step
    hands them back) and gradients (``grad_of(name)``: the compared rows of
    that table's) against the reference's ``want``, each over its limit:
    whatever stands in the measured step's place goes through here. A
    stand-in of fewer passes is held as one whose missing exits read 0."""
    worst = {"norm": (0.0, ""), "elem": (0.0, "")}
    by_kind: Dict[str, List[float]] = {}    # raw errors, for the record
    by_class: Dict[str, List[float]] = {}
    gate = [0.0, 0.0, 0.0, 0.0]     # the gate's two tables as one vector

    def held(name, cls, kind, e_norm, g_norm, e_max, g_max):
        for seen in (by_kind.setdefault(kind, [0.0, 0.0]),
                     by_class.setdefault(cls, [0.0, 0.0])):
            seen[0] = max(seen[0], e_norm / (g_norm + 1e-30))
            seen[1] = max(seen[1], e_max / (g_max + 1e-30))
        worst["norm"] = max(worst["norm"], (
            e_norm / (TOL_NORM[cls] * g_norm + 1e-30), name))
        worst["elem"] = max(worst["elem"], (
            e_max / (TOL_ELEM[cls] * g_max + 1e-30), name))

    for n, g in want["grads"].items():
        e_norm, g_norm, e_max, g_max = (
            float(x) for x in lm_train._errors(grad_of(n), g))
        if n in GATE:
            gate[0], gate[1] = gate[0] + e_norm ** 2, gate[1] + g_norm ** 2
            gate[2], gate[3] = max(gate[2], e_max), max(gate[3], g_max)
        else:
            held(n, table_class(n), n.split(".")[-1], e_norm, g_norm, e_max,
                 g_max)
    held("exit.*", "gate", "exit", gate[0] ** 0.5, gate[1] ** 0.5, gate[2],
         gate[3])

    def padded(x):      # [T', ...] as [passes, ...]
        x = np.asarray(x, np.float64)
        return np.concatenate(
            [x, np.zeros((passes - x.shape[0],) + x.shape[1:])])

    exits, ref_exits = got["exits"], want["exits"]
    pass_loss, ref_loss = padded(exits["loss"]), padded(ref_exits["loss"])
    ratios = {
        "loss_err_over_tol": abs(got["loss"] - want["loss"]) / (
            TOL_LOSS * max(abs(want["loss"]), 1.0)),
        "pass_loss_err_over_tol": float(np.max(
            np.abs(pass_loss - ref_loss)
            / (TOL_PASS_LOSS * np.maximum(np.abs(ref_loss), 1.0)))),
        "p_mean_err_over_tol": float(np.max(np.abs(
            padded(exits["p_mean"]) - padded(ref_exits["p_mean"])))
            / TOL_P_MEAN),
        "p_err_over_tol": float(np.max(np.abs(
            padded(exits["p"]) - padded(ref_exits["p"]))) / TOL_P),
        "grad_norm_err_over_tol": worst["norm"][0],
        "grad_elem_err_over_tol": worst["elem"][0]}
    return dict(
        ratios, loss=got["loss"], loss_ref=want["loss"],
        exit_loss=[float(x) for x in exits["loss"]],
        exit_loss_ref=[float(x) for x in ref_exits["loss"]],
        exit_p=[float(x) for x in exits["p_mean"]],
        exit_p_ref=[float(x) for x in ref_exits["p_mean"]],
        exit_entropy=float(exits["entropy"]),
        exit_entropy_ref=float(ref_exits["entropy"]),
        worst_tables={k: v[1] for k, v in worst.items()},
        by_kind=by_kind, by_class=by_class,
        agrees=bool(all(np.isfinite(r) and r <= 1.0
                        for r in ratios.values())))


def _compare(state: Dict[str, Any], controls=()) -> Dict[str, Any]:
    """One pool batch at the timed sizes through the measured step from
    zero Adam state, against ``reference/ouro`` on the same tables:
    ``lm_train._compare``'s procedure (the reference first, on the live
    tables' values, with Adam's moments set aside; then the moments back as
    zeros placed as they were, the measured step, what it handed back of
    its exits, and each table's stored gradient ``m / (1 - beta1)``
    compared on the device).

    ``controls``: names of :data:`CONTROLS`. The reference computed as each
    such faulty program would is also put in the measured step's place, and
    what the comparison says of it is returned under ``"controls"``: each
    has to be ``agrees: False``."""
    from multiverso_tpu.models import mla_moe

    cell, cfg, trainer = state["cell"], state["cfg"], state["trainer"]
    tables, tokens = state["tables"], state["pool"][0]
    shapes = mla_moe.param_shapes(cfg)
    c = cell.config
    lr = float(state["opt"].learning_rate)
    b1, b2, eps = (float(c[k]) for k in
                   ("adam_beta1", "adam_beta2", "adam_eps"))

    placed = {n: jax.tree.map(lambda x: (x.shape, x.dtype, x.sharding),
                              st["ustate"])
              for n, st in trainer.states.items()}
    for st in trainer.states.values():
        for leaf in jax.tree.leaves(st["ustate"]):
            leaf.delete()
    datas = {n: st["data"] for n, st in trainer.states.items()}

    def run_reference(how=None):
        def reference(datas, tokens):
            params = {n: datas[n][:mla_moe.table_shape(shapes[n])[0]]
                      for n in shapes}
            with (CONTROLS[how]() if how else ref.loop_control(None)):
                loss, exits, grads = ref.loss_and_grads(
                    params, tokens, c, lean=True)
            return loss, exits, {
                n: g.reshape(mla_moe.table_shape(shapes[n]))[
                    ::lm_train._stride(shapes[n])] for n, g in grads.items()}

        t0 = time.perf_counter()
        compiled = jax.jit(reference).lower(datas, tokens).compile()
        t1 = time.perf_counter()
        loss, exits, grads = jax.device_get(compiled(datas, tokens))
        return {"loss": float(loss), "exits": exits, "grads": grads,
                "compile_s": t1 - t0, "run_s": time.perf_counter() - t1}

    want = run_reference()
    stand_ins = {how: run_reference(how) for how in controls}

    rows_of = {n: lm_train._move_rows(int(t.shape[0]))
               for n, t in tables.items()}
    old = {n: np.asarray(st["data"][rows_of[n]])
           for n, st in trainer.states.items()}
    for n in tables:
        trainer.states[n]["ustate"] = jax.tree.map(
            lambda spec: jax.device_put(jnp.zeros(spec[0], spec[1]), spec[2]),
            placed[n], is_leaf=lambda x: isinstance(x, tuple))
    t_step = time.perf_counter()
    loss, _ = trainer.step(tokens)
    t_step = time.perf_counter() - t_step

    def stored_gradient(n):
        m = trainer.states[n]["ustate"]["m"]
        return m[:int(tables[n].shape[0]):lm_train._stride(shapes[n])] / (
            1.0 - b1)

    verdict = _held_to(want, {"loss": loss, "exits": trainer.exits},
                       stored_gradient, cfg.passes)
    worst_move = (0.0, "")
    for n in tables:
        st = trainer.states[n]
        new, m, v = (np.asarray(a[rows_of[n]], np.float64) for a in (
            st["data"], st["ustate"]["m"], st["ustate"]["v"]))
        want_new, _, v_want, _ = ref.adam_step(
            old[n], 0.0, 0.0, 0, m / (1.0 - b1), lr, b1, b2, eps)
        tol = 2.0 ** -22 * np.abs(old[n]) + TOL_MOVE * lr
        r_move = float(np.max(np.abs(new - want_new) / tol))
        r_v = float(np.max(np.abs(v - v_want) / (1e-5 * v_want + 1e-37)))
        worst_move = max(worst_move, (max(r_move, r_v), n))
    verdict["move_err_over_tol"] = worst_move[0]
    verdict["worst_tables"]["move"] = worst_move[1]
    verdict.update(
        tolerance={"loss": TOL_LOSS, "pass_loss": TOL_PASS_LOSS,
                   "p_mean": TOL_P_MEAN, "p": TOL_P, "norm": TOL_NORM,
                   "elem": TOL_ELEM, "move": TOL_MOVE},
        tables=len(tables), reference_s=want["run_s"],
        reference_compile_s=want["compile_s"], measured_step_s=t_step,
        step_agrees=bool(verdict.pop("agrees") and worst_move[0] <= 1.0))
    if stand_ins:
        verdict["controls"] = {how: dict(_held_to(
            want, stand_in, lambda n, stand_in=stand_in: stand_in["grads"][n],
            cfg.passes), compile_s=stand_in["compile_s"],
            run_s=stand_in["run_s"]) for how, stand_in in stand_ins.items()}
    return verdict
