"""The seventh language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/qwen3_next`` (three gated delta-rule
linear-attention layers over ``ops/delta_rule.py``'s chunked rule to one
gated, q/k-normed, partly rotary grouped-query layer at a head of 256,
every layer 32 held experts of 512 under a softmax route over 512 beside a
gated shared expert) on ``models/mla_moe``'s decoder path, tables, step and
``Trainer``. The load and the window are ``drivers/lm_train``'s, the
routers' calibration by their balance term ``drivers/lm_train_window``'s,
the allowance for a trace that lost a stretch ``drivers/lm_train_hybrid``'s,
all used as they are; what is this file's own is the model's
configuration, what ``layers/attnmix`` and ``layers/delta`` ask of the
window, and the comparison (another reference, classes, limits and controls
of its own). ``benchmark/LM_DELTA.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice, the balance pass once: every program compiled);
calibration of the routers by balance passes over the pool; Adam's state
back to zero and the comparison with ``reference/qwen3_next`` on one pool
batch through the measured step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import attn_shapes, delta_shapes
from benchmark.drivers import lm_train, lm_train_hybrid, lm_train_window
from benchmark.layers import attnmix, delta
from benchmark.reference import qwen3_next as ref

# The limits of the comparison, set as ``lm_train``'s are and for its
# reasons (bfloat16 operands against a float32 reference; a token whose
# tenth and eleventh probabilities lie within the activations' rounding goes
# to another expert than in the reference and takes its part of that
# expert's and the router's gradient with it). Each but the loss's and the
# router's worst element lies between two readings on the chip
# (benchmark/LM_DELTA.md has the table with its seeds; PERF.md section 6, PR
# 56): the largest the program showed over its seeds, and the smallest the
# CONTROLS showed: the reference computed as a faulty program would, in the
# measured step's place (``benchmark/lm_delta_control.py``), each of which
# has to come out as not agreeing. The tables whose gradient comes through
# the rule's DECAY alone (``a_log``, ``dt_bias``: a number a head each) are a
# class of their own: at the family's first values most heads forget within
# a position, and what a faulty rule does to the few that remember shows
# there (0.13 of the norm or more) and hardly anywhere else (the matrices
# 0.03 to 0.14). Program's largest over 17 readings / controls' smallest
# over 5 seeds, 2 for ``operands_float8`` (worst table of the class, as the
# limit is applied) / limit:
#   TOL_NORM   plain 0.0125 / 0.112 (``operands_float8``; ``rope_whole``
#              0.75) / 0.06; decay 0.0154 / 0.130 (``no_correction``;
#              ``no_carry`` 0.134) / 0.045; experts 0.0615 / 0.181
#              (``rope_whole``) / 0.11; router 0.0791 / 0.159 / 0.125.
#   TOL_ELEM   plain 0.0117 / 0.107 / 0.08; decay 0.0174 / 0.166 / 0.055;
#              experts 0.1115 / 0.228 / 0.17; router 0.133 / 0.130
#              (``rope_whole`` on one seed; ``operands_float8`` 0.2485) /
#              0.22, the one limit NOT between all its readings: seventeen
#              readings are 0.043 to 0.133 (mean 0.074, sd 0.023: the
#              quotient is set by the largest element of a router's small
#              gradient, as ``lm_train_window``'s), and the one control that
#              reads under it there is held by the plain class 12 times over.
#   TOL_COUNT  732 of 163,840 assignments a layer / 3,860
#              (``operands_float8``) / 2^-6 (2,560).
#   TOL_LOSS   1.2e-5 / 0 to 1.4e-4: the precision hardly moves a mean over
#              16,383 positions, so the limit is ``lm_train_window``'s 2e-4.
# A rule whose state and running sums are kept in bfloat16
# (``reference.rule_control("sums_bfloat16")``) is NOT told apart and is not
# among the controls: it moves the decay class by 0.0013 to 0.0020, a
# seventh of the program's own rounding (LM_DELTA.md).
TOL_LOSS = 2e-4
TOL_NORM = {"plain": 0.06, "experts": 0.11, "router": 0.125, "decay": 0.045}
TOL_ELEM = {"plain": 0.08, "experts": 0.17, "router": 0.22, "decay": 0.055}
TOL_COUNT = 2.0 ** -6
TOL_MOVE = lm_train.TOL_MOVE
TOL_BALANCE = lm_train_window.TOL_BALANCE
ROUTER_MARGIN = 0           # ref.MARGINS[0] = 1e-4
# what stands in the measured step's place, by name: the context under
# which the reference is traced
CONTROLS = {
    "no_carry": lambda: ref.rule_control("no_carry"),
    "no_correction": lambda: ref.rule_control("no_correction"),
    "rope_whole": lambda: ref.rule_control("rope_whole"),
    "operands_float8": lambda: ref.rounded_operands(lm_train.CONTROL)}
DECAY = ("a_log", "dt_bias")


def table_class(name: str) -> str:
    """``lm_train.table_class`` and this model's own: the two vectors a
    mixer that the rule's decay alone moves."""
    return ("decay" if name.split(".")[-1] in DECAY
            else lm_train.table_class(name))


def _model_config(cell):
    from multiverso_tpu.models import qwen3_next

    c = cell.config
    return qwen3_next.Qwen3NextConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        n_layers=int(c["num_hidden_layers"]),
        full_every=int(c["full_attention_interval"]),
        lin_key_heads=int(c["linear_num_key_heads"]),
        lin_value_heads=int(c["linear_num_value_heads"]),
        lin_key_dim=int(c["linear_key_head_dim"]),
        lin_value_dim=int(c["linear_value_head_dim"]),
        conv_kernel=int(c["linear_conv_kernel_dim"]),
        delta_chunk=int(c["chunk_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), rope_dim=ref.rotary_dim(c),
        rope_theta=float(c["rope_theta"]),
        moe_ffn=int(c["moe_intermediate_size"]),
        shared_ffn=int(c["shared_expert_intermediate_size"]),
        n_experts=int(c["published"]["num_experts"]),
        experts_held=int(c["num_experts"]),
        expert_offset=int(c["expert_offset"]),
        top_k=int(c["num_experts_per_tok"]),
        balance_coef=float(c["router_aux_loss_coef"]),
        eps=float(c["rms_norm_eps"]))


def setup(cell, controls=()) -> Dict[str, Any]:
    """``lm_train_window.setup``'s order under this model's configuration
    and comparison. ``controls``: names of :data:`CONTROLS`, see
    :func:`_compare` (``lm_delta_control.py`` gives them)."""
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
             "trainer": trainer, "opt": opt,
             "balance": jax.jit(mla_moe.make_balance_step(cfg, tables),
                                donate_argnums=(0,))}
    with cell.timed("warmup"):
        for k in range(2):          # fresh buffers, then the donated ones
            trainer.step(pool[k % pool.shape[0]])
        # compiled; moves nothing
        lm_train_window._balance_pass(state, pool[0], 0.0)
    with cell.timed("calibration"):
        state["calibration"] = lm_train_window._calibrate(state)
    with cell.timed("reference_check"):
        state["verdict"] = _compare(state, controls)
    return state


def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """``lm_train.window`` (through ``lm_train_hybrid._Blocks``: the blocks
    that have an attention core are the ``full`` ones alone); what
    ``layers/attnmix`` asks of a cell whose attention layers are all of the
    ``full`` kind (the kernels a window's steps run under
    ``mv.lm.attn.full``, four a core, and the operations those cores need:
    ``attn_shapes.core_flops`` at the head's 256); and what
    ``layers/delta`` asks: the operations the delta layers' chunked rule
    needs for the window's steps (``delta_shapes.rule_flops`` at the chunk
    the program runs)."""
    cfg, pool, c = state["cfg"], state["pool"], state["cell"].config
    blocks = lm_train_hybrid._Blocks(cfg)
    run = lm_train.window(dict(state, cfg=blocks), seconds)
    sequences, positions = int(pool.shape[1]), int(pool.shape[2])
    run["attnmix_kernels"] = {"full": run["attention_kernels"]}
    run["attnmix_flops"] = {
        "full": run["attempted"] * blocks.n_moe_layers
        * attn_shapes.core_flops(sequences, cfg.n_heads, positions,
                                 cfg.head_dim)}
    deltas = sum(layer.attn == "delta" for layer in cfg.layers())
    run["delta_flops"] = run["attempted"] * deltas * delta_shapes.rule_flops(
        c, sequences, positions, cfg.delta_chunk)
    return run


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """``lm_train_hybrid.check`` (``lm_train.check`` with the allowance for
    a trace that lost a stretch of the device's line: up to two steps'
    kernels short, the count expected becomes the count seen); the flash
    kernels' sums by scope from the trace that ``run.py`` has just stopped,
    for ``layers/attnmix``, and the delta mixers' device seconds by scope
    from the join of that trace with the step's ``xla.program`` record, for
    ``layers/delta``, both under the same allowance (the operations are
    then those of the steps seen)."""
    expected = int(run["attention_kernels"])
    verdict = lm_train_hybrid.check(state, run)
    run["attnmix_s"] = attnmix.kernel_seconds(state["cell"].name)
    run["delta_s"] = delta.scope_seconds(state["cell"].name)
    seen = int(run["attention_kernels"])
    if seen != expected:
        run["attnmix_kernels"] = {"full": seen}
        run["attnmix_flops"] = {
            "full": run["attnmix_flops"]["full"] * seen // expected}
        run["delta_flops"] = run["delta_flops"] * seen // expected
    if run["delta_s"]:
        # the whole join, for a reader of the log: where the step's time
        # goes by scope and pass (what ``dump_metrics.py scopes`` prints)
        verdict["detail"]["scope_s"] = {
            k: run["delta_s"][k] for k in ("every_scope", "filed_s",
                                           "busy_s")}
    return verdict


# ---------------------------------------------------------------------- #
# the comparison with the reference: lm_train's procedure, under this
# model's reference, classes, limits and controls (a file the benchmark has
# is not edited, and reference and limits are that file's module constants)
# ---------------------------------------------------------------------- #
def _held_to(want: Dict[str, Any], loss: float, counts: np.ndarray,
             grad_of, cfg, tokens_n: int) -> Dict[str, Any]:
    """A step's loss, routing counts [layers, E + 1] and gradients
    (``grad_of(name)``: the compared rows of that table's) against the
    reference's ``want``, each over its limit: whatever stands in the
    measured step's place goes through here."""
    worst = {"norm": (0.0, ""), "elem": (0.0, "")}
    # raw errors, for the record: the worst table of a kind and of a class
    by_kind: Dict[str, List[float]] = {}
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
    counts = np.asarray(counts)
    c_got = counts[:, :cfg.n_experts]
    routed = tokens_n * cfg.top_k
    count_l1 = np.abs(c_got - want["counts"]).sum(1)
    identities = bool(np.all(c_got.sum(1) == routed)
                      and np.all(want["counts"].sum(1) == routed)
                      and int(counts[:, cfg.n_experts:].sum()) == 0)
    ratios = {"loss_err_over_tol": abs(loss - want["loss"]) / (
                  TOL_LOSS * max(abs(want["loss"]), 1.0)),
              "grad_norm_err_over_tol": worst["norm"][0],
              "grad_elem_err_over_tol": worst["elem"][0],
              "count_err_over_tol": float(count_l1.max())
              / (TOL_COUNT * routed)}
    return dict(
        ratios, loss=loss, loss_ref=want["loss"],
        worst_tables={k: v[1] for k, v in worst.items()},
        count_l1=[int(x) for x in count_l1], count_identities=identities,
        by_kind=by_kind, by_class=by_class,
        agrees=bool(identities and all(
            np.isfinite(r) and r <= 1.0 for r in ratios.values())))


def _compare(state: Dict[str, Any], controls=()) -> Dict[str, Any]:
    """One pool batch at the timed sizes through the measured step from
    zero Adam state, against ``reference/qwen3_next`` on the same tables and
    the calibrated routers: ``lm_train._compare``'s procedure (the reference
    first, on the live tables' values, with Adam's moments set aside; then
    the moments back as zeros placed as they were, the measured step, and
    each table's stored gradient ``m / (1 - beta1)`` compared on the
    device).

    ``controls``: names of :data:`CONTROLS`. The reference computed as each
    such faulty program would is also put in the measured step's place, and
    what the comparison says of it is returned under ``"controls"``: each
    has to be ``agrees: False``."""
    from multiverso_tpu.models import mla_moe
    from multiverso_tpu.parallel import moe

    cell, cfg, trainer = state["cell"], state["cfg"], state["trainer"]
    tables, tokens = state["tables"], state["pool"][0]
    shapes = mla_moe.param_shapes(cfg)
    c = cell.config
    tokens_n = int(np.prod(tokens.shape))
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
            with (CONTROLS[how]() if how else ref.rule_control(None)):
                loss, counts, ties, terms, grads = ref.loss_and_grads(
                    params, tokens, c, lean=True)
            return loss, counts, ties, terms, {
                n: g.reshape(mla_moe.table_shape(shapes[n]))[
                    ::lm_train._stride(shapes[n])] for n, g in grads.items()}

        t0 = time.perf_counter()
        compiled = jax.jit(reference).lower(datas, tokens).compile()
        t1 = time.perf_counter()
        loss, counts, ties, terms, grads = jax.device_get(
            compiled(datas, tokens))
        return {"loss": float(loss), "counts": np.asarray(counts),
                "ties": np.asarray(ties), "terms": np.asarray(terms),
                "grads": grads, "compile_s": t1 - t0,
                "run_s": time.perf_counter() - t1}

    want = run_reference()
    stand_ins = {how: run_reference(how) for how in controls}
    # the program's router alone on a float32 input of the timed size
    route_in = jax.random.normal(jax.random.key(cell.seed % (2 ** 31)),
                                 (tokens_n, cfg.dim))
    router = datas["L0.router"][:cfg.n_experts]
    _, _, counts_alone, term_alone = jax.jit(
        lambda u, w: moe.softmax_route(
            u, w, mla_moe.held(cfg, tokens_n)))(route_in, router)
    counts_alone_ref, ties_alone, term_alone_ref = jax.device_get(jax.jit(
        lambda u, w: ref.route_alone(u, w, c))(route_in, router))
    router_flips = int(np.abs(np.asarray(counts_alone)
                              - counts_alone_ref).sum())
    router_allowed = 2 * int(ties_alone[ROUTER_MARGIN])
    balance_err = abs(float(term_alone) - float(term_alone_ref)) / max(
        abs(float(term_alone_ref)), 1.0)

    rows_of = {n: lm_train._move_rows(int(t.shape[0]))
               for n, t in tables.items()}
    old = {n: np.asarray(st["data"][rows_of[n]])
           for n, st in trainer.states.items()}
    for n in tables:
        trainer.states[n]["ustate"] = jax.tree.map(
            lambda spec: jax.device_put(jnp.zeros(spec[0], spec[1]), spec[2]),
            placed[n], is_leaf=lambda x: isinstance(x, tuple))
    t_step = time.perf_counter()
    loss, counts = trainer.step(tokens)
    t_step = time.perf_counter() - t_step

    def stored_gradient(n):
        m = trainer.states[n]["ustate"]["m"]
        return m[:int(tables[n].shape[0]):lm_train._stride(shapes[n])] / (
            1.0 - b1)

    verdict = _held_to(want, loss, counts, stored_gradient, cfg, tokens_n)
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
                   "count": TOL_COUNT, "move": TOL_MOVE,
                   "balance": TOL_BALANCE,
                   "router_margin": ref.MARGINS[ROUTER_MARGIN]},
        near_ties=want["ties"].tolist(), router_flips=router_flips,
        router_flips_allowed=router_allowed,
        balance_err_over_tol=balance_err / TOL_BALANCE,
        balance_terms_ref=[float(x) for x in want["terms"]],
        tables=len(tables), reference_s=want["run_s"],
        reference_compile_s=want["compile_s"], measured_step_s=t_step,
        step_agrees=bool(verdict.pop("agrees") and worst_move[0] <= 1.0
                         and router_flips <= router_allowed
                         and balance_err <= TOL_BALANCE))
    if stand_ins:
        verdict["controls"] = {how: dict(_held_to(
            want, stand_in["loss"],
            np.pad(stand_in["counts"], ((0, 0), (0, 1))),
            lambda n, stand_in=stand_in: stand_in["grads"][n], cfg, tokens_n),
            compile_s=stand_in["compile_s"], run_s=stand_in["run_s"])
            for how, stand_in in stand_ins.items()}
    return verdict
