"""The second language model trained through Adam tables, closed loop,
one trainer: ``multiverso_tpu/models/gqa_moe`` (grouped-query heads,
window layers to a full one, routed experts alone under a softmax
router) on ``models/mla_moe``'s decoder path, tables, step and
``Trainer``. The load, the window and the check after it are
``drivers/lm_train``'s, used as they are; what differs is set-up's
balance (this family has no selection bias: its load-balance term moves
the routers) and the comparison (another reference, limits of its own).
``benchmark/LM_WINDOW.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice, the balance pass once: every program compiled);
calibration of the routers by balance passes over the pool; Adam's state
back to zero and the comparison with ``reference/gqa_window_moe`` on one
pool batch through the measured step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import attn_shapes
from benchmark.drivers import lm_train
from benchmark.layers import attnmix
from benchmark.reference import gqa_window_moe as ref

# The limits of the comparison, set as ``lm_train``'s are and for its
# reasons (bfloat16 operands against a float32 reference; a token whose
# eighth and ninth probabilities lie within the activations' rounding
# goes to another expert than in the reference and takes its part of
# that expert's and the router's gradient with it). Each but the loss's
# lies between two
# readings on the chip (benchmark/LM_WINDOW.md; PERF.md section 6, PR 35): the
# largest the program showed over its seeds, and the smallest the CONTROL
# showed (the reference computed as a float8_e4m3 step would, in the
# measured step's place: ``benchmark/lm_window_control.py``), which has to
# come out as not agreeing. Program's largest over 45 seeds / control's
# smallest over 11 (worst table of the class, as the limit is applied) /
# limit:
#   TOL_LOSS   |loss - ref| / max(|ref|, 1): 8.8e-5 / 2.3e-5 / 2e-4. The
#              precision hardly moves a mean over 16,382 positions: the
#              control's nine read 2.3e-5 to 1.2e-3 (median 3.5e-4), so no
#              limit lies between. 2e-4 is 2.3 times the program's largest
#              (27 readings, sd 4e-5) and the control passes it on 2 seeds
#              of 9; the gradients' and the counts' limits fail it on all.
#   TOL_NORM   ||g - g_ref|| / ||g_ref||, every table, by its class:
#              plain 0.053 / 0.168 / 0.1; experts 0.054 / 0.161 / 0.09;
#              router 0.087 / 0.189 / 0.12.
#   TOL_ELEM   max |g - g_ref| / max |g_ref|, every table: plain 0.056 /
#              0.173 / 0.095; experts 0.063 / 0.157 / 0.09; router 0.119 /
#              0.151 / 0.24, the one limit NOT between its readings. A
#              router's worst element is off by 0.8e-4 to 2.1e-4 in every
#              layer of every seed read (32 tables), while max |g_ref|
#              goes from 1.3e-2 (layer 0) to 1.3e-3 (the last layer of
#              one seed): the quotient is set by what it is divided by,
#              and 19 seeds from the driver's range read 0.027 to 0.119
#              (0.1 stood here on 26 seeds over 2^32, largest 0.059, and
#              failed seed 2147489203). Twice the largest; under it only
#              a router gradient that is wrong, not one that is rounded:
#              the control reads 0.151 or more here and is held on every
#              seed by the norms' limits and the counts'.
#   TOL_COUNT  per layer, sum over the 64 experts of |c - c_ref| over the
#              layer's tokens x 8 assignments: 556 of 131,072 = 0.0042 /
#              4,118 = 0.031 / 2^-6 (2,048). The identities hold exactly:
#              every layer's counts sum to tokens x 8, nothing overflowed.
#   TOL_MOVE   the value's move against NumPy's Adam on the gradient the
#              step stored, eight rows of every table, as ``lm_train``'s
#              (seen 0.25 of it).
#   router     ``moe.softmax_route`` alone on a seeded float32 input of the
#              timed size against the reference's: float32 at the highest
#              precision on both sides, so at most the tokens whose choice
#              hangs by under 1e-4 may differ (seen 0 of an allowed 2,750),
#              and the load-balance term agrees to TOL_BALANCE (seen 0).
TOL_LOSS = 2e-4
TOL_NORM = {"plain": 0.1, "experts": 0.09, "router": 0.12}
TOL_ELEM = {"plain": 0.095, "experts": 0.09, "router": 0.24}
TOL_COUNT = 2.0 ** -6
TOL_MOVE = lm_train.TOL_MOVE
TOL_BALANCE = 1e-5
ROUTER_MARGIN = 0          # ref.MARGINS[0] = 1e-4
CONTROL = lm_train.CONTROL
KINDS = {"sliding_attention": "window", "full_attention": "full"}


def _model_config(cell):
    from multiverso_tpu.models import gqa_moe, mla_moe

    c = cell.config
    full = c["rope_parameters"]["full_attention"]
    kinds = ref.layer_kinds(c)
    return gqa_moe.GQAMoEConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), window=int(c["sliding_window"]),
        layer_kinds=tuple(KINDS[k] for k in kinds),
        rope_theta=float(c["rope_parameters"]["sliding_attention"]
                         ["rope_theta"]),
        yarn=mla_moe.Yarn(*(full[k] for k in mla_moe.Yarn._fields)),
        moe_ffn=int(c["moe_intermediate_size"]),
        n_experts=int(c["published"]["num_experts"]),
        experts_held=int(c["num_experts"]),
        expert_offset=int(c["expert_offset"]),
        top_k=int(c["num_experts_per_tok"]),
        balance_coef=float(c["router_aux_loss_coef"]),
        eps=float(c["rms_norm_eps"]))


def setup(cell, control=None) -> Dict[str, Any]:
    """``control``: see :func:`_compare` (``lm_window_control.py`` gives
    it)."""
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
        _balance_pass(state, pool[0], 0.0)      # compiled; moves nothing
    with cell.timed("calibration"):
        state["calibration"] = _calibrate(state)
    with cell.timed("reference_check"):
        state["verdict"] = _compare(state, control)
    return state


# ---------------------------------------------------------------------- #
# the routers' balance before the window
# ---------------------------------------------------------------------- #
def _router_names(state) -> List[str]:
    from multiverso_tpu.models import mla_moe

    return [n + ".router" for n in mla_moe.expert_layers(state["cfg"])]


def _balance_pass(state, tokens, rate: float) -> np.ndarray:
    """One pass of the load-balance terms alone over ``tokens``: the
    routers' tables move at ``rate``, nothing else does. Returns the
    pass's counts [layers, E + 1]."""
    trainer = state["trainer"]
    names = _router_names(state)
    routers = {n: trainer.states.pop(n) for n in names}
    routers, counts, _ = state["balance"](
        routers, trainer.states, trainer.bias, tokens,
        jnp.asarray(rate, jnp.float32))
    trainer.states.update(routers)
    return np.asarray(counts)


def _calibrate(state: Dict[str, Any]) -> Dict[str, Any]:
    """Balance passes over the pool at a rate that starts at
    ``start_rate`` and is multiplied by ``shrink`` every
    ``passes_per_rate`` passes down to ``floor_rate``; ends when, at the
    floor, the loads of one whole turn of the pool (every batch the window
    will feed, summed) have every layer's busiest expert within
    ``load_max_over_mean`` of the mean and every layer's held share within
    ``held_share_within`` of an even share. Then the routers' Adam state
    goes back to zero. No other table is touched."""
    cfg, trainer = state["cfg"], state["trainer"]
    cal, pool = state["cell"].traffic["calibration"], state["pool"]
    turn, even = int(pool.shape[0]), 100.0 * cfg.experts_held / cfg.n_experts
    rate, floor = float(cal["start_rate"]), float(cal["floor_rate"])
    history: List[np.ndarray] = []
    at_floor, ok = 0, False

    def readings(counts):
        return lm_train._layer_readings(counts, cfg)

    def held_rows_most(counts) -> int:      # the fullest layer's buffer
        lo = cfg.expert_offset
        return int(counts[:, lo:lo + cfg.experts_held].sum(1).max())

    for k in range(int(cal["max_passes"])):
        history.append(_balance_pass(state, pool[k % turn], rate))
        at_floor = at_floor + 1 if rate <= floor else 0
        if at_floor >= turn:
            last = readings(np.sum(history[-turn:], axis=0))
            ok = (float(last["max_over_mean"].max())
                  <= float(cal["load_max_over_mean"])
                  and float(np.abs(last["held_share"] - even).max())
                  <= float(cal["held_share_within"]))
            if ok:
                break
        if (k + 1) % int(cal["passes_per_rate"]) == 0:
            rate = max(rate * float(cal["shrink"]), floor)
    for n in _router_names(state):
        st = trainer.states[n]
        st["ustate"] = jax.tree.map(
            lambda x: jax.device_put(jnp.zeros(x.shape, x.dtype), x.sharding),
            st["ustate"])
    last = readings(np.sum(history[-turn:], axis=0))
    return {"passes": len(history), "balanced": bool(ok),
            "first_max_over_mean": [float(x) for x in readings(
                history[0])["max_over_mean"]],
            "first_held_share": [float(x) for x in readings(
                history[0])["held_share"]],
            "first_held_rows_most": held_rows_most(history[0]),
            "last_turn_max_over_mean": [float(x)
                                        for x in last["max_over_mean"]],
            "last_turn_held_share": [float(x) for x in last["held_share"]],
            "last_turn_worst_batch": max(float(readings(
                h)["max_over_mean"].max()) for h in history[-turn:]),
            "last_turn_held_rows_most": max(held_rows_most(h)
                                            for h in history[-turn:])}


# ---------------------------------------------------------------------- #
# the window and the check after it: lm_train's, with the layers by kind
# ---------------------------------------------------------------------- #
class _Blocks:
    """``cfg`` as ``lm_train.window`` reads it: that driver counts a
    step's blocks under ``MLAMoEConfig``'s three fields, which the model's
    own configuration does not carry; here every layer is an expert
    layer."""
    n_dense_layers = n_mtp = 0

    def __init__(self, cfg):
        self._cfg, self.n_moe_layers = cfg, len(cfg.layers())

    def __getattr__(self, name):
        return getattr(self._cfg, name)


def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    cfg, pool = state["cfg"], state["pool"]
    run = lm_train.window(dict(state, cfg=_Blocks(cfg)), seconds)
    sequences, positions = int(pool.shape[1]), int(pool.shape[2])
    steps = run["attempted"]
    layers = {kind: cfg.layer_kinds.count(kind) for kind in attnmix.SCOPES}
    # a block's attention core is four kernels: forward, forward again in
    # the backward pass, dQ, dK with dV
    run["attnmix_kernels"] = {k: 4 * steps * n for k, n in layers.items()}
    run["attnmix_flops"] = {
        k: steps * n * attn_shapes.core_flops(
            sequences, cfg.n_heads, positions, cfg.head_dim,
            cfg.window if k == "window" else None)
        for k, n in layers.items()}
    return run


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """``lm_train.check``; and, as it does for ``layers/attn``, the sums
    of the attention kernels by scope from the trace that ``run.py`` has
    just stopped, for ``layers/attnmix``."""
    verdict = lm_train.check(state, run)
    run["attnmix_s"] = attnmix.kernel_seconds(state["cell"].name)
    return verdict


# ---------------------------------------------------------------------- #
# the comparison with the reference
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
    zero Adam state, against ``reference/gqa_window_moe`` on the same
    tables: ``lm_train._compare``'s procedure (the reference first, on the
    live tables' values, with Adam's moments set aside; then the moments
    back as zeros placed as they were, the measured step, and each
    table's stored gradient ``m / (1 - beta1)`` compared on the device).

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

    def run_reference(operands=None):
        def reference(datas, tokens):
            params = {n: datas[n][:mla_moe.table_shape(shapes[n])[0]]
                      for n in shapes}
            with ref.rounded_operands(operands):
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
    stand_in = None if control is None else run_reference(control)
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
    if stand_in is not None:
        verdict["control"] = dict(_held_to(
            want, stand_in["loss"],
            np.pad(stand_in["counts"], ((0, 0), (0, 1))),
            lambda n: stand_in["grads"][n], cfg, tokens_n),
            operands=jnp.dtype(control).name,
            compile_s=stand_in["compile_s"], run_s=stand_in["run_s"])
    return verdict
