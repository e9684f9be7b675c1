"""The third language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/afmoe`` (gated, q/k-normed grouped-query
heads with rotary positions in the window layers alone, four norms a
block, a leading dense layer, experts chosen by a sigmoid under a
selection bias beside a shared one) on ``models/mla_moe``'s decoder path,
tables, step and ``Trainer``. The load, the bias calibration and the
window are ``drivers/lm_train``'s and the attention's counts by layer kind
``drivers/lm_train_window``'s, used as they are; what is this file's own
is the model's configuration and the comparison (another reference, limits
of its own).
``benchmark/LM_AFMOE.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice, the forward pass once: every program compiled);
calibration of the routers' selection biases by forward-only passes over
the pool; Adam's state back to zero and the comparison with
``reference/afmoe`` on one pool batch through the measured step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import lm_train, lm_train_window
from benchmark.reference import afmoe as ref

# The limits of the comparison, set as ``lm_train``'s are and for its
# reasons (bfloat16 operands against a float32 reference; a token whose
# eighth and ninth scores lie within the activations' rounding goes to
# another expert than in the reference and takes its part of that expert's
# and the router's gradient with it). Each lies between two readings on the
# chip (benchmark/LM_AFMOE.md; PERF.md section 6, PR 39): the largest the
# program showed over 25 seeds, all of the driver's range (2147483104 to
# 2147483403), and the smallest the CONTROL showed over 8 of them (the
# reference computed as a float8_e4m3 step would, in the measured step's
# place: ``benchmark/lm_afmoe_control.py``), which has to come out as not
# agreeing and did on all 8, by every limit but the loss's. Program's
# largest / control's smallest (worst table of the class, as the limit is
# applied) / limit:
#   TOL_LOSS   |loss - ref| / max(|ref|, 1): 2.97e-5 (mean 1.15e-5, sd
#              6.5e-6) / 7.6e-5 (7.6e-5 to 3.1e-4) / 6e-5. The precision
#              moves a mean over 16,383 positions little; the limit is twice
#              the program's largest and the control's smallest is 1.27 of it.
#   TOL_NORM   ||g - g_ref|| / ||g_ref||, every table, by its class: plain
#              0.0151 / 0.094 / 0.04; experts 0.0631 / 0.221 / 0.11; router
#              0.0993 / 0.214 / 0.14.
#   TOL_ELEM   max |g - g_ref| / max |g_ref|, every table: plain 0.0218 /
#              0.108 / 0.045; experts 0.1146 / 0.328 / 0.18; router 0.1145 /
#              0.228 / 0.17 (the tightest: 1.48 over the program's largest,
#              the control's smallest 1.34 over it; a router's worst element
#              rides on the tokens that flipped).
#   TOL_COUNT  per expert layer, sum over the 128 experts of |c - c_ref|
#              over the layer's tokens x 8 assignments: 846 of 131,072 =
#              0.0065 / 8,834 = 0.067 (its least-moved layer 6,894) / 2^-6
#              (2,048). The identities hold exactly: every layer's counts
#              sum to tokens x 8, nothing overflowed.
#   TOL_MOVE   the value's move against NumPy's Adam on the gradient the
#              step stored, eight rows of every table, as ``lm_train``'s
#              (seen 0.247 of it).
#   router     ``moe.sigmoid_route`` alone on a seeded float32 input of the
#              timed size against the reference's: float32 at the highest
#              precision on both sides, so at most the tokens whose choice
#              hangs by under 1e-4 may differ (seen 0 of an allowed 300 to
#              330: 128 sigmoid scores lie close).
TOL_LOSS = 6e-5
TOL_NORM = {"plain": 0.04, "experts": 0.11, "router": 0.14}
TOL_ELEM = {"plain": 0.045, "experts": 0.18, "router": 0.17}
TOL_COUNT = 2.0 ** -6
TOL_MOVE = lm_train.TOL_MOVE
ROUTER_MARGIN = 0          # ref.MARGINS[0] = 1e-4
CONTROL = lm_train.CONTROL
KINDS = lm_train_window.KINDS


def _model_config(cell):
    from multiverso_tpu.models import afmoe

    c = cell.config
    return afmoe.AFMoEConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), window=int(c["sliding_window"]),
        layer_kinds=tuple(KINDS[k] for k in ref.layer_kinds(c)),
        n_dense_layers=int(c["num_dense_layers"]),
        rope_theta=float(c["rope_theta"]),
        dense_ffn=int(c["intermediate_size"]),
        moe_ffn=int(c["moe_intermediate_size"]),    # the shared expert's too
        n_experts=int(c["published"]["num_experts"]),
        experts_held=int(c["num_experts"]),
        expert_offset=int(c["expert_offset"]),
        top_k=int(c["num_experts_per_tok"]),
        routed_scale=float(c["route_scale"]),
        bias_speed=float(c["load_balance_coeff"]),
        embed_scale=ref.embed_scale(c), eps=float(c["rms_norm_eps"]))


def setup(cell, control=None) -> Dict[str, Any]:
    """``lm_train.setup``'s order under this model's configuration and
    comparison. ``control``: see :func:`_compare` (``lm_afmoe_control.py``
    gives it)."""
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
        state["verdict"] = _compare(state, control)
    return state


# The window and the check after it are ``lm_train_window``'s: ``lm_train``'s
# loop and facts, and the layers' attention cores by kind for
# ``layers/attnmix``, the dense layer's among them (``cfg.layer_kinds``
# names every layer).
window = lm_train_window.window
check = lm_train_window.check


# ---------------------------------------------------------------------- #
# the comparison with the reference: lm_train's procedure, under this
# model's reference and limits (a file the benchmark has is not edited,
# and those two are that file's module constants)
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
        cls = lm_train.table_class(n)
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


def _compare(state: Dict[str, Any], control=None) -> Dict[str, Any]:
    """One pool batch at the timed sizes through the measured step from
    zero Adam state, against ``reference/afmoe`` on the same tables and
    the calibrated biases: ``lm_train._compare``'s procedure (the reference
    first, on the live tables' values, with Adam's moments set aside; then
    the moments back as zeros placed as they were, the measured step, and
    each table's stored gradient ``m / (1 - beta1)`` compared on the
    device).

    ``control``: a dtype. The reference computed as a step in that
    precision would (``reference.rounded_operands``) is also put in the
    measured step's place, and what the comparison says of it is returned
    under ``"control"``: it has to be ``agrees: False``."""
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

    def run_reference(operands=None):
        def reference(datas, bias, tokens):
            params = {n: datas[n][:mla_moe.table_shape(shapes[n])[0]]
                      for n in shapes}
            with ref.rounded_operands(operands):
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
    stand_in = None if control is None else run_reference(control)
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
    if stand_in is not None:
        verdict["control"] = dict(_held_to(
            want, stand_in["loss"],
            np.pad(stand_in["counts"], ((0, 0), (0, 1))),
            lambda n: stand_in["grads"][n], cfg, tokens_n),
            operands=jnp.dtype(control).name,
            compile_s=stand_in["compile_s"], run_s=stand_in["run_s"])
    return verdict
