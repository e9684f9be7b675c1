"""The fifth language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/lfm2_moe`` (gated short-convolution mixers
three to one q/k-normed grouped-query layer of 64-wide heads, a leading
dense layer, experts chosen by a sigmoid under a selection bias and no
shared one, the head tied to the embedding) on ``models/mla_moe``'s decoder
path, tables, step and ``Trainer``. The load, the bias calibration, the
window and the check after it are ``drivers/lm_train``'s, used as they are;
what is this file's own is the model's configuration, the attention
kernels' sums by layer kind for ``layers/attnmix`` and the comparison
(another reference, limits and controls of its own).
``benchmark/LM_CONV.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice, the forward pass once: every program compiled);
calibration of the routers' selection biases by forward-only passes over
the pool; Adam's state back to zero and the comparison with
``reference/lfm2_moe`` on one pool batch through the measured step.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import attn_shapes
from benchmark.drivers import lm_train, lm_train_hybrid
from benchmark.layers import attnmix
from benchmark.reference import lfm2_moe as ref

# The limits of the comparison, set as ``lm_train``'s are and for its
# reasons (bfloat16 operands against a float32 reference; a token whose
# fourth and fifth scores lie within the activations' rounding goes to
# another expert than in the reference and takes its part of that expert's
# and the router's gradient with it). Five classes of table
# (:func:`table_class`): the routers; the held experts' matrices and an
# expert block's ``ffn_norm``; the TIED table (one gradient is the sum of
# the lookup's rows and the chunked loss's float32 head gradient); the
# convolutions' TAPS (three rows of 2,048 a mixer, each element a sum over
# every position of both sequences); and the rest. Each limit but the
# loss's lies between two readings on the chip (benchmark/LM_CONV.md;
# PERF.md section 6, PR 50), near their geometric mean: the largest the
# program showed over 17 seeds, and the smallest the CONTROL showed over 3:
# the reference computed as a float8_e4m3 step would
# (``reference.rounded_operands``), in the measured step's place
# (``benchmark/lm_conv_control.py``), which has to come out as not agreeing
# and did, by every limit but the loss's. Program's largest / control's
# smallest (worst table of the class, as the limit is applied) / limit:
#   TOL_NORM   plain 0.0489 / 0.341 / 0.13; taps 0.0494 / 0.343 / 0.12; tied
#              0.0456 / 0.303 / 0.12; experts 0.1858 / 0.623 / 0.33; router
#              0.2495 / 0.763 / 0.42.
#   TOL_ELEM   plain 0.0606 / 0.401 / 0.15; taps 0.0524 / 0.294 / 0.11; tied
#              0.0530 / 0.301 / 0.12; experts 0.3446 (one seed's ``eu``; the
#              next 0.2763) / 0.607 / 0.46, the tightest: 1.33 and 1.32 of
#              room; router 0.2655 / 0.722 / 0.41.
#   TOL_COUNT  252 of 65,536 assignments a layer / 960 / 2^-7 (512).
#   TOL_LOSS   7.1e-5 / 3.0e-5 (to 4.6e-4): the precision hardly moves a
#              mean over 16,382 positions, so no limit lies between, and the
#              limit is ``lm_train``'s 6e-4 (the same traffic's).
# A second control, the taps read in the other order
# (``reference.conv_control``), is no rounding and fails every limit (every
# class 1.39 to 1.51 of its norm off, the counts by 5,256 to 15,662).
TOL_LOSS = lm_train.TOL_LOSS
TOL_NORM = {"plain": 0.13, "experts": 0.33, "router": 0.42, "tied": 0.12,
            "taps": 0.12}
TOL_ELEM = {"plain": 0.15, "experts": 0.46, "router": 0.41, "tied": 0.12,
            "taps": 0.11}
TOL_COUNT = 2.0 ** -7
TOL_MOVE = lm_train.TOL_MOVE
ROUTER_MARGIN = 0          # ref.MARGINS[0] = 1e-4
# what stands in the measured step's place, by name: the context under
# which the reference is traced
CONTROLS = {
    "operands_float8": lambda: ref.rounded_operands(lm_train.CONTROL),
    "taps_reversed": lambda: ref.conv_control("taps_reversed")}
KINDS = {"conv": "conv", "full_attention": "full"}


def table_class(name: str, expert_layers=()) -> str:
    """``lm_train.table_class`` and this model's own: the tied table, the
    taps, and among the experts an expert block's ``ffn_norm`` (a layer in
    ``expert_layers``): with no shared expert beside them, that norm's
    whole gradient comes through the held experts' products and the
    router, and a token that goes to another expert than in the reference
    takes its part of it along, as it does of an expert's matrix."""
    layer, _, kind = name.rpartition(".")
    if kind == "ffn_norm" and layer in expert_layers:
        return "experts"
    return {"embed": "tied", "conv_w": "taps"}.get(
        kind) or lm_train.table_class(name)


def _model_config(cell):
    from multiverso_tpu.models import lfm2_moe

    c = cell.config
    return lfm2_moe.LFM2MoEConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        layer_kinds=tuple(KINDS[k] for k in ref.layer_kinds(c)),
        n_dense_layers=int(c["num_dense_layers"]),
        conv_taps=int(c["conv_L_cache"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["hidden_size"]) // int(c["num_attention_heads"]),
        rope_theta=float(c["rope_theta"]),
        dense_ffn=int(c["intermediate_size"]),
        moe_ffn=int(c["moe_intermediate_size"]),
        n_experts=int(c["published"]["num_experts"]),
        experts_held=int(c["num_experts"]),
        expert_offset=int(c["expert_offset"]),
        top_k=int(c["num_experts_per_tok"]),
        routed_scale=float(c["routed_scaling_factor"]),
        bias_speed=float(c["bias_update_speed"]),
        eps=float(c["norm_eps"]))


def setup(cell, controls=()) -> Dict[str, Any]:
    """``lm_train.setup``'s order under this model's configuration and
    comparison. ``controls``: names of :data:`CONTROLS`, see
    :func:`_compare` (``lm_conv_control.py`` gives them)."""
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
             "forward": jax.jit(mla_moe.make_forward(cfg))}
    with cell.timed("warmup"):
        for k in range(2):          # fresh buffers, then the donated ones
            trainer.step(pool[k % pool.shape[0]])
        jax.block_until_ready(state["forward"](
            trainer.states, trainer.bias, pool[0]))
    with cell.timed("calibration"):
        state["calibration"] = lm_train._calibrate(state)
    with cell.timed("reference_check"):
        state["verdict"] = _compare(state, controls)
    return state


def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """``lm_train.window`` (through ``lm_train_hybrid._Blocks``: the blocks
    that have an attention core are the ``full`` ones alone); and what
    ``layers/attnmix`` asks of a cell whose attention layers are all of
    the ``full`` kind: the kernels a window's steps run under
    ``mv.lm.attn.full`` (four a core: forward, forward again in the
    backward pass, dQ, dK with dV) and the operations those cores need
    (``attn_shapes.core_flops`` at the head's 64)."""
    cfg, pool = state["cfg"], state["pool"]
    blocks = lm_train_hybrid._Blocks(cfg)
    run = lm_train.window(dict(state, cfg=blocks), seconds)
    run["attnmix_kernels"] = {"full": run["attention_kernels"]}
    run["attnmix_flops"] = {
        "full": run["attempted"] * blocks.n_moe_layers
        * attn_shapes.core_flops(int(pool.shape[1]), cfg.n_heads,
                                 int(pool.shape[2]), cfg.head_dim)}
    return run


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """``lm_train_hybrid.check`` (``lm_train.check`` with the allowance
    for a trace that lost a stretch of the device's line: up to two steps'
    kernels short, the count expected becomes the count seen), and the
    flash kernels' sums by scope from the trace that ``run.py`` has just
    stopped, for ``layers/attnmix``, under the same allowance: the
    operations are then those of the cores seen."""
    expected = int(run["attention_kernels"])
    verdict = lm_train_hybrid.check(state, run)
    run["attnmix_s"] = attnmix.kernel_seconds(state["cell"].name)
    seen = int(run["attention_kernels"])
    if seen != expected:
        run["attnmix_kernels"] = {"full": seen}
        run["attnmix_flops"] = {
            "full": run["attnmix_flops"]["full"] * seen // expected}
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
    from multiverso_tpu.models import mla_moe

    routed = mla_moe.expert_layers(cfg)
    worst = {"norm": (0.0, ""), "elem": (0.0, "")}
    # raw errors, for the record: the worst table of a kind and of a class
    by_kind: Dict[str, List[float]] = {}
    by_class: Dict[str, List[float]] = {}
    for n, g in want["grads"].items():
        e_norm, g_norm, e_max, g_max = (
            float(x) for x in lm_train._errors(grad_of(n), g))
        cls = table_class(n, routed)
        kind = n.split(".")[-1]
        for seen in (by_kind.setdefault(
                kind + ".experts" if (kind, cls) == ("ffn_norm", "experts")
                else kind, [0.0, 0.0]),
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
    zero Adam state, against ``reference/lfm2_moe`` on the same tables and
    the calibrated biases: ``lm_train._compare``'s procedure (the reference
    first, on the live tables' values, with Adam's moments set aside; then
    the moments back as zeros placed as they were, the measured step, and
    each table's stored gradient ``m / (1 - beta1)`` compared on the
    device). The tied table's stored gradient is the sum of its two parts,
    as the reference's autodiff gives it.

    ``controls``: names of :data:`CONTROLS`. The reference computed as each
    such faulty step would is also put in the measured step's place, and
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
    bias = trainer.bias

    def run_reference(how=None):
        def reference(datas, bias, tokens):
            params = {n: datas[n][:mla_moe.table_shape(shapes[n])[0]]
                      for n in shapes}
            with CONTROLS[how]() if how else contextlib.nullcontext():
                loss, counts, ties, grads = ref.loss_and_grads(
                    params, bias, tokens, c, lean=True)
            return loss, counts, ties, {
                n: g.reshape(mla_moe.table_shape(shapes[n]))[
                    ::lm_train._stride(shapes[n])] for n, g in grads.items()}

        t0 = time.perf_counter()
        compiled = jax.jit(reference).lower(datas, bias, tokens).compile()
        t1 = time.perf_counter()
        loss, counts, ties, grads = jax.device_get(
            compiled(datas, bias, tokens))
        return {"loss": float(loss), "counts": np.asarray(counts),
                "ties": np.asarray(ties), "grads": grads,
                "compile_s": t1 - t0, "run_s": time.perf_counter() - t1}

    want = run_reference()
    stand_ins = {how: run_reference(how) for how in controls}
    # the program's router alone on a float32 input of the timed size
    route_in = jax.random.normal(jax.random.key(cell.seed % (2 ** 31)),
                                 (tokens_n, cfg.dim))
    first = mla_moe.expert_layers(cfg)[0]
    router = datas[first + ".router"][:cfg.n_experts]
    _, _, counts_alone = jax.jit(
        lambda u, w, b: moe.sigmoid_route(
            u, w, b, mla_moe.held(cfg, tokens_n)))(route_in, router, bias[0])
    counts_alone_ref, ties_alone = jax.device_get(jax.jit(
        lambda u, w, b: ref.route_alone(u, w, b, c))(
            route_in, router, bias[0]))
    router_flips = int(np.abs(np.asarray(counts_alone)
                              - counts_alone_ref).sum())
    router_allowed = 2 * int(ties_alone[ROUTER_MARGIN])

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
                   "router_margin": ref.MARGINS[ROUTER_MARGIN]},
        near_ties=want["ties"].tolist(), router_flips=router_flips,
        router_flips_allowed=router_allowed, tables=len(tables),
        reference_s=want["run_s"], reference_compile_s=want["compile_s"],
        measured_step_s=t_step,
        step_agrees=bool(verdict.pop("agrees") and worst_move[0] <= 1.0
                         and router_flips <= router_allowed))
    if stand_ins:
        verdict["controls"] = {how: dict(_held_to(
            want, stand_in["loss"],
            np.pad(stand_in["counts"], ((0, 0), (0, 1))),
            lambda n, stand_in=stand_in: stand_in["grads"][n], cfg, tokens_n),
            compile_s=stand_in["compile_s"], run_s=stand_in["run_s"])
            for how, stand_in in stand_ins.items()}
    return verdict
