"""The tenth language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/granite_h`` (two-branch blocks: a Mamba-2
state-space mixer whose 64 heads read ONE group's ``B`` and ``C``, or one
layer in ten a grouped-query attention without positions, and a dense gated
MLP behind every mixer; four published multipliers; the head tied to the
embedding; no expert layer) on ``models/mla_moe``'s decoder path, tables,
step and ``Trainer``. The load (``lm_train.lm_batches``), the comparison's
helpers and the window's loop are ``drivers/lm_train``'s; there is no
router, so nothing is calibrated and no forward-only program is compiled
(``lm_train.window`` reads a router's counts: the loop is
``lm_train_loop``'s, without the exits). What is this file's own is the
model's configuration, what ``layers/ssm``, ``layers/ffn``, ``layers/attn``
and ``layers/attnmix`` ask of the window (this is the first driver that
hands the traced SCOPE seconds of ``mv.lm.ssm*`` and ``mv.lm.dense`` to a
reader) and the comparison (another reference, classes of tables, limits
and controls of its own). ``benchmark/LM_SSM.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice: the one program compiled); Adam's state back to
zero and the comparison with ``reference/granite_h`` on one pool batch
through the measured step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import attn_shapes, ssm_shapes, ssmblock_shapes
from benchmark.drivers import lm_train
from benchmark.layers import attn as attn_layer
from benchmark.layers import attnmix, ssm
from benchmark.reference import granite_h as ref

# The limits of the comparison, set as ``lm_train``'s are and for its
# reason (bfloat16 operands against a float32 reference, here through ten
# two-branch blocks forward, made again and backward). There is no router:
# nothing is discontinuous, and what is compared is the loss and every
# table's gradient. Four classes of table (:func:`table_class`): ``vocab``
# (the ONE tied table: the embedding's rows most tokens never touch, plus
# the head's part of every row); ``norms`` (a gain's gradient is a sum over
# every position; the gated norm's gain among them); ``scan`` (the
# mixers' small per-head and per-channel tables: ``a_log``, ``dt_bias``,
# ``skip``, ``conv_w``, ``conv_b``, each a sum over all positions of what
# the scan's bfloat16 products rounded); and the rest, ``plain``. Each
# limit lies between two readings on the chip (benchmark/LM_SSM.md has the
# table with its seeds; PERF.md section 6, PR 66): the largest the program
# showed over 10 seeds of the driver's range, and the smallest the CONTROLS
# showed: the reference computed as a faulty program would, in the measured
# step's place (``benchmark/lm_granite_control.py``), each of which has to
# come out as not agreeing, by one of the limits and not by each
# (``softmax_sqrt`` moves the ONE attention block's matrices and is held by
# the plain class alone, 7.7 of a gradient's norm; the scan's two faults,
# ``sums_bfloat16`` over three seeds and ``no_carry`` over two, are the
# smallest readings everywhere else). Program's largest / controls'
# smallest (which) / limit:
#   TOL_NORM  plain 0.0190 / 0.0728 (``sums_bfloat16``) / 0.038; vocab
#             0.0182 / 0.0692 (the same) / 0.036; norms 0.0199 / 0.0691
#             (the same) / 0.038; scan 0.0408 / 0.574 (``no_carry``) / 0.12.
#   TOL_ELEM  plain 0.0417 (one seed of 10; the next 0.021) / 0.138
#             (``no_carry``) / 0.10; vocab 0.0207 / 0.0607
#             (``sums_bfloat16``) / 0.05; norms 0.0315 / 0.0733 (the same)
#             / 0.065; scan 0.0754 / 0.997 (``no_carry``) / 0.25: nearer
#             the controls' readings than the norms' limits are, because
#             one element's error swings with the seed and both faults of
#             the scan are held five times over by the scan class and
#             twice by every norm.
#   TOL_LOSS  3.3e-6 / 1.8e-6 to 2.4e-5 for the scan's two faults and
#             ``softmax_sqrt`` (a mean over 8,191 positions hardly moves),
#             3.4e-3 (``residual_1``), 0.063 (``logits_unscaled``) /
#             ``lm_train_hybrid``'s 2e-4, which leaves the program 60 times
#             of room and holds the two multipliers.
#   TOL_MOVE  ``lm_train``'s: seen 0.248 of the limit.
TOL_LOSS = 2e-4
TOL_NORM = {"plain": 0.038, "vocab": 0.036, "norms": 0.038, "scan": 0.12}
TOL_ELEM = {"plain": 0.10, "vocab": 0.05, "norms": 0.065, "scan": 0.25}
TOL_MOVE = lm_train.TOL_MOVE
# what stands in the measured step's place: ``reference.control``'s faults
CONTROLS = ref.CONTROLS
SCAN_TABLES = ("a_log", "dt_bias", "skip", "conv_w", "conv_b")
# a host that stands still leaves the device the step it runs and the one
# queued ahead: the kernels a trace may lack of those a window ran are two
# steps' (``lm_train_hybrid.KERNELS_A_TRACE_MAY_LOSE``, at this cell's
# count a step)
STEPS_A_TRACE_MAY_LOSE = 2


def table_class(name: str) -> str:
    if name == "embed":
        return "vocab"
    kind = name.split(".")[-1]
    if kind in SCAN_TABLES:
        return "scan"
    return "norms" if kind.endswith("norm") else "plain"


def _model_config(cell):
    from multiverso_tpu.models import granite_h

    c = cell.config
    heads = int(c["num_attention_heads"])
    if (int(c["mamba_n_heads"]) * int(c["mamba_d_head"])
            != int(c["mamba_expand"]) * int(c["hidden_size"])
            or not c["tie_word_embeddings"]
            or c["position_embedding_type"] != "nope"
            or int(c["num_local_experts"])):
        raise ValueError("the mixer's inner width is expand x hidden, the "
                         "head is tied, no layer takes positions and no "
                         "layer routes")
    return granite_h.GraniteHConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        layer_types=ref.layer_kinds(c),
        ssm_heads=int(c["mamba_n_heads"]),
        ssm_head_dim=int(c["mamba_d_head"]),
        ssm_groups=int(c["mamba_n_groups"]),
        ssm_state=int(c["mamba_d_state"]),
        conv_kernel=int(c["mamba_d_conv"]), chunk=int(c["mamba_chunk_size"]),
        time_step_min=float(c["time_step_min"]),
        time_step_max=float(c["time_step_max"]),
        time_step_floor=float(c["time_step_floor"]),
        a_init=tuple(float(a) for a in c["a_init_range"]),
        n_heads=heads, n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["hidden_size"]) // heads,
        dense_ffn=int(c["shared_intermediate_size"]),
        embed_scale=float(c["embedding_multiplier"]),
        residual_scale=float(c["residual_multiplier"]),
        softmax_scale=float(c["attention_multiplier"]),
        logit_scale=1.0 / float(c["logits_scaling"]),
        eps=float(c["rms_norm_eps"]))


def setup(cell, controls=()) -> Dict[str, Any]:
    """``lm_train.setup``'s order without a router's part. ``controls``:
    names of :data:`CONTROLS`, see :func:`_compare`
    (``lm_granite_control.py`` gives them)."""
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
    one's read-back) without a router's counts, and what the readers ask:
    the flash kernels a window's steps run (four a core) and their
    operations, and the operations and bytes of the mixers' scans and
    projections and of the MLPs (``ssm_shapes``, ``ssmblock_shapes``)."""
    trainer, pool, cfg = state["trainer"], state["pool"], state["cfg"]
    c = state["cell"].config
    whole, losses = [], []

    def took(done, last):
        if done is not None:
            losses.append(done[0])
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
    layers = cfg.layers()
    mixers = sum(layer.attn == "ssm" for layer in layers)
    cores = sum(layer.attn == "full" for layer in layers)
    facts = {"steps": i, "tokens_a_step": sequences * positions,
             "loss_first": losses[0], "loss_last": losses[-1],
             "ssm_layers": mixers, "attention_layers": cores,
             "dense_layers": len(layers)}
    scan = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state)
    return {"work": i * sequences * (positions - 1), "elapsed_s": now - t0,
            "attempted": i,
            "failed": int(sum(1 for x in losses if not np.isfinite(x))),
            "losses": losses, "spans_ms": {"step": whole}, "facts": facts,
            # a block's attention core is four kernels: forward, forward
            # again in the backward pass, dQ, dK with dV
            "attention_kernels": 4 * i * cores,
            "attnmix_kernels": {"full": 4 * i * cores},
            "attnmix_flops": {
                "full": i * cores * attn_shapes.core_flops(
                    sequences, cfg.n_heads, positions, cfg.head_dim)},
            # what ``layers/ssm`` and ``layers/ffn`` hold their scopes'
            # seconds against: the window's steps, every mixer or layer
            "ssm_work": {
                "steps": i,
                "scan_flops": i * mixers * ssm_shapes.scan_flops(
                    sequences, positions, *scan, cfg.chunk),
                "scan_bytes": i * mixers * ssm_shapes.scan_bytes(
                    sequences, positions, *scan),
                "proj_flops": i * ssmblock_shapes.proj_flops(
                    c, sequences, positions),
                "dense_flops": i * ssmblock_shapes.dense_flops(
                    c, sequences, positions)}}


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """The comparison made in set-up, and after the window: every loss
    finite, the last under the same batch's a turn of the pool earlier; the
    states adopted back into their tables.

    ``run.py`` calls this between stopping the trace and reducing it, and
    deletes the trace before a reader runs: on a traced run the flash
    kernels' sums (``layers/attn``, ``layers/attnmix``) and the join by
    scope (``layers/ssm``, which ``layers/ffn`` reads too) are made here
    and ``run`` carries them to their readers. Where the trace lacks a
    stretch of the device's line (a host that stood still drains no trace
    buffer: ``lm_train_hybrid.check``) its kernels are fewer than the steps
    ran; the stretch is missing from the busy time too, so up to
    :data:`STEPS_A_TRACE_MAY_LOSE` steps short the counts and the work
    expected are those of the kernels seen."""
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
    run["ssm_s"] = ssm.scope_seconds(name)
    seen = int(run["attention_s"].get("kernels", 0))
    expected = int(run["attention_kernels"])
    detail["attention_kernels"] = {"seen": seen, "expected": expected}
    a_step = expected // max(int(run["attempted"]), 1)
    if 0 < expected - seen <= STEPS_A_TRACE_MAY_LOSE * a_step:
        run["attention_kernels"] = seen
        run["attnmix_kernels"] = {"full": seen}
        run["attnmix_flops"] = {
            "full": run["attnmix_flops"]["full"] * seen // expected}
        run["ssm_work"] = {k: v * seen // expected
                           for k, v in run["ssm_work"].items()}
    if run["ssm_s"]:
        # the whole join, for a reader of the log: where the step's time
        # goes by scope and pass (what ``dump_metrics.py scopes`` prints)
        detail["scope_s"] = {k: run["ssm_s"][k] for k in (
            "every_scope", "filed_s", "busy_s")}
    return {"correct": bool(detail["step_agrees"] and detail["losses_finite"]
                            and detail["loss_fell"]),
            "detail": detail}


# ---------------------------------------------------------------------- #
# the comparison with the reference
# ---------------------------------------------------------------------- #
def _held_to(want: Dict[str, Any], loss: float, grad_of) -> Dict[str, Any]:
    """A step's loss and gradients (``grad_of(name)``: the compared rows of
    that table's) against the reference's ``want``, each over its limit:
    whatever stands in the measured step's place goes through here."""
    worst = {"norm": (0.0, ""), "elem": (0.0, "")}
    by_kind: Dict[str, List[float]] = {}    # raw errors, for the record
    by_class: Dict[str, List[float]] = {}
    for n, g in want["grads"].items():
        e_norm, g_norm, e_max, g_max = (
            float(x) for x in lm_train._errors(grad_of(n), g))
        cls = table_class(n)
        for seen in (by_kind.setdefault(n.split(".")[-1], [0.0, 0.0]),
                     by_class.setdefault(cls, [0.0, 0.0])):
            seen[0] = max(seen[0], e_norm / (g_norm + 1e-30))
            seen[1] = max(seen[1], e_max / (g_max + 1e-30))
        worst["norm"] = max(worst["norm"], (
            e_norm / (TOL_NORM[cls] * g_norm + 1e-30), n))
        worst["elem"] = max(worst["elem"], (
            e_max / (TOL_ELEM[cls] * g_max + 1e-30), n))
    ratios = {"loss_err_over_tol": abs(loss - want["loss"]) / (
                  TOL_LOSS * max(abs(want["loss"]), 1.0)),
              "grad_norm_err_over_tol": worst["norm"][0],
              "grad_elem_err_over_tol": worst["elem"][0]}
    return dict(
        ratios, loss=loss, loss_ref=want["loss"],
        worst_tables={k: v[1] for k, v in worst.items()},
        by_kind=by_kind, by_class=by_class,
        agrees=bool(all(np.isfinite(r) and r <= 1.0
                        for r in ratios.values())))


def _compare(state: Dict[str, Any], controls=()) -> Dict[str, Any]:
    """One pool batch at the timed sizes through the measured step from
    zero Adam state, against ``reference/granite_h`` on the same tables:
    ``lm_train._compare``'s procedure (the reference first, on the live
    tables' values, with Adam's moments set aside; then the moments back as
    zeros placed as they were, the measured step, and each table's stored
    gradient ``m / (1 - beta1)`` compared on the device).

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
            with ref.control(how):
                loss, grads = ref.loss_and_grads(params, tokens, c, lean=True)
            return loss, {
                n: g.reshape(mla_moe.table_shape(shapes[n]))[
                    ::lm_train._stride(shapes[n])] for n, g in grads.items()}

        t0 = time.perf_counter()
        compiled = jax.jit(reference).lower(datas, tokens).compile()
        t1 = time.perf_counter()
        loss, grads = jax.device_get(compiled(datas, tokens))
        return {"loss": float(loss), "grads": grads, "compile_s": t1 - t0,
                "run_s": time.perf_counter() - t1}

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

    verdict = _held_to(want, loss, stored_gradient)
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
        tolerance={"loss": TOL_LOSS, "norm": TOL_NORM, "elem": TOL_ELEM,
                   "move": TOL_MOVE},
        tables=len(tables), reference_s=want["run_s"],
        reference_compile_s=want["compile_s"], measured_step_s=t_step,
        step_agrees=bool(verdict.pop("agrees") and worst_move[0] <= 1.0))
    if stand_ins:
        verdict["controls"] = {how: dict(_held_to(
            want, stand_in["loss"],
            lambda n, stand_in=stand_in: stand_in["grads"][n]),
            compile_s=stand_in["compile_s"], run_s=stand_in["run_s"])
            for how, stand_in in stand_ins.items()}
    return verdict
