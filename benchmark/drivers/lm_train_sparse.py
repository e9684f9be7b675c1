"""The sixth language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/keye_moe`` (grouped-query attention over
the 2,048 keys a learned indexer selects for every query, the indexer
trained beside it by a term of its own in the loss, routed experts alone
under a softmax route) on ``models/mla_moe``'s decoder path, tables, step
and ``Trainer``. The load and the window are ``drivers/lm_train``'s, the
routers' calibration by their balance term ``drivers/lm_train_window``'s,
the allowance for a trace that lost a stretch ``drivers/lm_train_hybrid``'s,
all used as they are; what is this file's own is the model's
configuration, the flash kernels' sums under ``mv.lm.attn.sparse`` for
``layers/sparse`` and the comparison, which has three parts.
``benchmark/LM_SPARSE.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice, the balance pass once: every program compiled);
calibration of the routers by balance passes over the pool; Adam's state
back to zero and the comparison with ``reference/keye_moe`` on one pool
batch through the measured step.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import sparse_shapes
from benchmark.drivers import lm_train, lm_train_hybrid, lm_train_window
from benchmark.layers import prog, sparse
from benchmark.reference import keye_moe as ref

# The limits of the comparison (benchmark/LM_SPARSE.md has the table with
# its reasons; PERF.md section 6, PR 54). Part (a), the program's selection
# against the float32 reference's own on the same inputs: TOL_DIFFER on the
# share of a layer's selected keys that differ; TOL_FAR on the distance of
# every differing key's reference score from its row's reference threshold,
# in units of the row's spread, in the FIRST layer (a near-tie that
# bfloat16 operands decide either way; a key far from the threshold is a
# fault); TOL_BEYOND, every layer, on the share of keys that differ and
# lie beyond a tenth of a spread.
# Part (b), the reference run UNDER THE PROGRAM'S SELECTION: the loss, the
# indexer's term and the gradients by class of table, set as ``lm_train``'s
# are and for its reasons; the five indexer tensors are a class of their
# own (``index``), since the indexer's term alone moves them. Each limit
# but the losses' lies between the largest the program showed over its
# seeds on the chip and the smallest the CONTROL showed (the reference
# computed as a float8_e4m3 step would, ``benchmark/lm_sparse_control.py``),
# which has to come out as not agreeing and did, on both seeds, by every
# limit but the loss's. Program's largest over 11 seeds / control's smallest
# over 2 (worst table of the class, least-moved layer, as the limit is
# applied; my chip runs, PR 54) / limit, near their geometric mean:
#   TOL_NORM   plain 0.0552 / 0.164 / 0.095; experts 0.0540 / 0.164 / 0.095;
#              router 0.0648 / 0.237 / 0.12; index 0.0146 / 0.134 / 0.045.
#   TOL_ELEM   plain 0.0609 / 0.230 / 0.12; experts 0.0739 / 0.263 / 0.14;
#              router 0.1000 (one seed's last layer; the next 0.051) / 0.233
#              / 0.15; index 0.0248 / 0.164 / 0.065.
#   TOL_COUNT  908 of 131,072 assignments a layer / 8,282 / 2^-6 (2,048).
#   TOL_DIFFER 0.00844 of a layer's 31,458,304 selected keys / 0.0782 /
#              0.026.
#   TOL_FAR    the FIRST layer's farthest differing key, 0.0411 of its row's
#              spread / 0.493 / 0.14. (Layers 2 to 4 read 0.39 to 0.60 in
#              the program too: a token routed otherwise brings another
#              hidden state, and its whole row of scores with it.)
#   TOL_BEYOND the share of a layer's selected keys that differ AND lie
#              beyond ``reference.BEYOND`` (0.1 of the row's spread) of the
#              threshold: 0.00032 / 0.0122 / 0.002.
#   TOL_INDEX_LOSS  4.7e-4 / 4.9e-3 / 1.5e-3.
#   TOL_LOSS   7.0e-5 / 1.7e-4 and 4.2e-4: the precision hardly moves a mean
#              over 16,383 positions, so the limit is ``lm_train_window``'s
#              2e-4 (3.6 times the first reading's 5.6e-5); one control
#              seed of two passes it and is held by every other limit.
#   TOL_HELD_SHARE  part (c): a layer's held share of the window's routed
#              rows strayed 0.40 to 1.07 points from the even 12.5 over nine
#              windows; 2 points (4 x the calibration's own 0.5). The buffer
#              holds twice the even share: what a run may not have is a row
#              over it (``overflow_rows``).
TOL_LOSS = 2e-4
TOL_INDEX_LOSS = 1.5e-3
TOL_NORM = {"plain": 0.095, "experts": 0.095, "router": 0.12, "index": 0.045}
TOL_ELEM = {"plain": 0.12, "experts": 0.14, "router": 0.15, "index": 0.065}
TOL_COUNT = 2.0 ** -6
TOL_DIFFER = 0.026
TOL_FAR = 0.14
TOL_BEYOND = 2e-3
TOL_MOVE = lm_train.TOL_MOVE
TOL_BALANCE = lm_train_window.TOL_BALANCE
TOL_HELD_SHARE = 4.0        # of the calibration's own limit (held_share_within)
ROUTER_MARGIN = 0           # ref.MARGINS[0] = 1e-4
CONTROL = lm_train.CONTROL
INDEXER = ("wq_i", "wk_i", "k_i_norm", "k_i_bias", "ww_i")


def table_class(name: str) -> str:
    """``lm_train.table_class`` and this model's own: the indexer's five
    tensors, which the indexer's term alone moves."""
    return ("index" if name.split(".")[-1] in INDEXER
            else lm_train.table_class(name))


def _model_config(cell):
    from multiverso_tpu.models import keye_moe

    c = cell.config
    sa = c["sa_config"]
    return keye_moe.KeyeMoEConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        layer_kinds=("sparse",) * int(c["num_hidden_layers"]),
        rope_theta=float(c["rope_theta"]),
        mrope_section=tuple(int(n) for n in
                            c["rope_scaling"]["mrope_section"]),
        moe_ffn=int(c["moe_intermediate_size"]),
        n_experts=int(c["published"]["num_experts"]),
        experts_held=int(c["num_experts"]),
        expert_offset=int(c["expert_offset"]),
        top_k=int(c["num_experts_per_tok"]),
        balance_coef=float(c["router_aux_loss_coef"]),
        eps=float(c["rms_norm_eps"]),
        index_heads=int(sa["indexer_num_heads"]),
        index_dim=int(sa["indexer_head_dim"]), index_topk=int(sa["topk"]),
        index_chunk=int(sa["q_chunk_size"]),
        index_coef=float(c["index_loss_coef"]))


def setup(cell, control=None) -> Dict[str, Any]:
    """``lm_train_window.setup``'s order under this model's configuration
    and comparison. ``control``: see :func:`_compare`
    (``lm_sparse_control.py`` gives it)."""
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
        state["verdict"] = _compare(state, control)
    return state


def _step_terms(n: int) -> List[float]:
    """``index_loss`` of the program's last ``n`` ``lm.step`` spans."""
    steps = [e["args"] for e in prog.program_events()
             if e.get("name") == "lm.step" and "index_loss" in e["args"]]
    return [float(a["index_loss"]) for a in steps[-n:]]


def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """``lm_train.window`` (through ``lm_train_window._Blocks``: every
    layer has an attention core and is an expert layer); and what
    ``layers/sparse`` asks: the kernels a window's steps run under
    ``mv.lm.attn.sparse`` (four a core: forward, forward again in the
    backward pass, dQ, dK with dV) and the operations those cores need over
    the SELECTED positions (``sparse_shapes.core_flops``). The facts gain
    the indexer's term of the first and the last step and the slowest
    step."""
    cfg, pool = state["cfg"], state["pool"]
    run = lm_train.window(
        dict(state, cfg=lm_train_window._Blocks(cfg)), seconds)
    steps = run["attempted"]
    run["sparse_kernels"] = run["attention_kernels"]
    run["sparse_flops"] = steps * len(cfg.layers()) * sparse_shapes.core_flops(
        int(pool.shape[1]), cfg.n_heads, int(pool.shape[2]), cfg.head_dim,
        cfg.index_topk)
    terms = _step_terms(steps)
    run["facts"].update(
        step_ms_max=max(run["spans_ms"]["step"]),
        index_loss_first=terms[0] if terms else None,
        index_loss_last=terms[-1] if terms else None)
    return run


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """``lm_train_hybrid.check`` (``lm_train.check`` with the allowance for
    a trace that lost a stretch of the device's line: up to two steps'
    kernels short, the count expected becomes the count seen); the flash
    kernels' sums under ``mv.lm.attn.sparse`` from the trace that
    ``run.py`` has just stopped, for ``layers/sparse``, under the same
    allowance (the operations are then those of the cores seen); and part
    (c) of the comparison: no row over the held experts' buffer
    (``lm_train.check``'s) and every layer's held share of the window's
    routed rows within ``TOL_HELD_SHARE`` times the calibration's own limit
    (the traffic's ``held_share_within``, in points) of the even share."""
    cfg = state["cfg"]
    expected = int(run["attention_kernels"])
    verdict = lm_train_hybrid.check(state, run)
    run["sparse_s"] = sparse.kernel_seconds(state["cell"].name)
    seen = int(run["attention_kernels"])
    if seen != expected:
        run["sparse_kernels"] = seen
        run["sparse_flops"] = run["sparse_flops"] * seen // expected
    even = 100.0 * cfg.experts_held / cfg.n_experts
    off = max(abs(x - even) for x in run["facts"]["held_share"])
    verdict["detail"]["held_share_off_even"] = off
    within = TOL_HELD_SHARE * float(
        state["cell"].traffic["calibration"]["held_share_within"])
    verdict["correct"] = bool(verdict["correct"] and off <= within)
    return verdict


# ---------------------------------------------------------------------- #
# the comparison with the reference: lm_train's procedure, under this
# model's reference, classes and limits, with the selection's own part
# ---------------------------------------------------------------------- #
def _held_to(want: Dict[str, Any], loss: float, index_loss: float,
             counts: np.ndarray, grad_of, cfg, tokens_n: int
             ) -> Dict[str, Any]:
    """A step's loss, indexer's term, routing counts [layers, E + 1] and
    gradients (``grad_of(name)``: the compared rows of that table's)
    against the reference's ``want``, each over its limit: whatever stands
    in the measured step's place goes through here."""
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
              "index_loss_err_over_tol":
                  abs(index_loss - want["index_loss"]) / (
                      TOL_INDEX_LOSS * max(abs(want["index_loss"]), 1.0)),
              "grad_norm_err_over_tol": worst["norm"][0],
              "grad_elem_err_over_tol": worst["elem"][0],
              "count_err_over_tol": float(count_l1.max())
              / (TOL_COUNT * routed)}
    return dict(
        ratios, loss=loss, loss_ref=want["loss"], index_loss=index_loss,
        index_loss_ref=want["index_loss"],
        worst_tables={k: v[1] for k, v in worst.items()},
        count_l1=[int(x) for x in count_l1], count_identities=identities,
        by_kind=by_kind, by_class=by_class,
        agrees=bool(identities and all(
            np.isfinite(r) and r <= 1.0 for r in ratios.values())))


def _selection_held(differ, far, cfg, tokens) -> Dict[str, Any]:
    """Part (a): the keys in which the reference's own selection differs
    from the one it was given (a count a layer) as a share of a layer's
    selected keys; the farthest such key from its row's threshold in the
    FIRST layer (whose input is the embedding's rows, the same on both
    sides: there every differing key is a near-tie); and, every layer, the
    share of the selected keys that differ AND lie beyond
    ``reference.BEYOND`` of their row's threshold (from the second layer on
    a token routed otherwise arrives with another hidden state and takes
    its whole row of scores along); each over its limit."""
    positions = int(tokens.shape[1])
    selected = int(tokens.shape[0]) * sparse_shapes.selected_positions(
        positions, cfg.index_topk)
    share = [float(d) / selected for d in differ]
    beyond = [float(f[1]) / selected for f in far]
    return {"differ_keys": [int(d) for d in differ], "differ_share": share,
            "far": [float(f[0]) for f in far], "beyond_share": beyond,
            "differ_err_over_tol": max(share) / TOL_DIFFER,
            "far_err_over_tol": float(far[0][0]) / TOL_FAR,
            "beyond_err_over_tol": max(beyond) / TOL_BEYOND}


def _compare(state: Dict[str, Any], control=None) -> Dict[str, Any]:
    """One pool batch at the timed sizes through the measured step from
    zero Adam state, against ``reference/keye_moe`` on the same tables:
    ``lm_train._compare``'s procedure (the reference first, on the live
    tables' values, with Adam's moments set aside; then the moments back as
    zeros placed as they were, the measured step, and each table's stored
    gradient ``m / (1 - beta1)`` compared on the device), in three parts.
    (a) The program's selection of every layer
    (``keye_moe.layer_selections``: a forward pass of its own over the
    same tables) is handed to the reference, which makes its own from its
    float32 scores on the same inputs and says in how many keys the two
    differ and how far the farthest such key's score lies from its row's
    threshold. (b) The reference runs UNDER THE PROGRAM'S SELECTION, a row
    block at a time: loss, the indexer's term, counts and every table's
    gradient against the measured step's. (c) lies after the window
    (:func:`check`). And once, the rows whose selection a forward remade
    under ``jax.checkpoint`` makes differently from the forward before it
    in one program (``keye_moe.selection_remade``, the first layer's).

    ``control``: a dtype. The reference computed as a step in that
    precision would (``reference.rounded_operands``), its own selection in
    the program's place, is also put in the measured step's place, and what
    the comparison says of it is returned under ``"control"``: it has to be
    ``agrees: False``."""
    from multiverso_tpu.models import keye_moe, mla_moe
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

    def params_of(datas):
        return {n: datas[n][:mla_moe.table_shape(shapes[n])[0]]
                for n in shapes}

    t0 = time.perf_counter()
    chosen = jax.block_until_ready(jax.jit(
        lambda datas, tokens: keye_moe.layer_selections(
            params_of(datas), tokens, cfg))(datas, tokens))
    first = cfg.layers()[0].name

    def remade(datas, tokens):
        params = params_of(datas)
        p = mla_moe._sub(params, first)
        u = mla_moe.rms_norm(mla_moe._embed(params, tokens, cfg),
                             p["attn_norm"], cfg.eps)
        return keye_moe.selection_remade(u, p, cfg)

    rows_remade = int(np.sum(jax.device_get(jax.jit(remade)(datas, tokens))))
    selection_s = time.perf_counter() - t0

    def run_reference(operands=None, given=None):
        def reference(datas, tokens, given):
            with (ref.rounded_operands(operands) if operands is not None
                  else contextlib.nullcontext()):
                loss, aux, grads = ref.loss_and_grads(
                    params_of(datas), tokens, c, given, lean=True)
            return loss, aux, {
                n: g.reshape(mla_moe.table_shape(shapes[n]))[
                    ::lm_train._stride(shapes[n])] for n, g in grads.items()}

        t0 = time.perf_counter()
        compiled = jax.jit(reference).lower(datas, tokens, given).compile()
        t1 = time.perf_counter()
        loss, aux, grads = jax.device_get(compiled(datas, tokens, given))
        _, counts, ties, terms, index, differ, far = aux
        return {"loss": float(loss), "counts": np.asarray(counts),
                "ties": np.asarray(ties), "terms": np.asarray(terms),
                "index_loss": float(c["index_loss_coef"]) * float(
                    np.sum(index)),
                "differ": np.asarray(differ), "far": np.asarray(far),
                "grads": grads, "compile_s": t1 - t0,
                "run_s": time.perf_counter() - t1}

    want = run_reference(given=chosen)
    stand_in = None
    if control is not None:
        # a faulty step selects by its own scores: what it selects, held
        # to the float32 reference's own, is part (a)'s control
        stand_in = run_reference(control)

        def faulty(datas, tokens):
            with ref.rounded_operands(control):
                return ref.selections(params_of(datas), tokens, c, lean=True)

        held = jax.jit(faulty)(datas, tokens)
        stand_in["selection"] = run_reference(given=held)
        del held
    del chosen
    # the program's router alone on a float32 input of the timed size
    route_in = jax.random.normal(jax.random.key(cell.seed % (2 ** 31)),
                                 (tokens_n, cfg.dim))
    router = datas[first + ".router"][:cfg.n_experts]
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
    index_loss = _step_terms(1)[-1]

    def stored_gradient(n):
        m = trainer.states[n]["ustate"]["m"]
        return m[:int(tables[n].shape[0]):lm_train._stride(shapes[n])] / (
            1.0 - b1)

    verdict = _held_to(want, loss, index_loss, counts, stored_gradient, cfg,
                       tokens_n)
    chosen_held = _selection_held(want["differ"], want["far"], cfg, tokens)
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
        chosen_held,
        selection_rows_remade_differ=rows_remade, selection_s=selection_s,
        tolerance={"loss": TOL_LOSS, "index_loss": TOL_INDEX_LOSS,
                   "norm": TOL_NORM, "elem": TOL_ELEM, "count": TOL_COUNT,
                   "differ": TOL_DIFFER, "far": TOL_FAR,
                   "beyond": TOL_BEYOND, "move": TOL_MOVE,
                   "balance": TOL_BALANCE,
                   "router_margin": ref.MARGINS[ROUTER_MARGIN]},
        near_ties=want["ties"].tolist(), router_flips=router_flips,
        router_flips_allowed=router_allowed,
        balance_err_over_tol=balance_err / TOL_BALANCE,
        balance_terms_ref=[float(x) for x in want["terms"]],
        tables=len(tables), reference_s=want["run_s"],
        reference_compile_s=want["compile_s"], measured_step_s=t_step,
        step_agrees=bool(
            verdict.pop("agrees") and worst_move[0] <= 1.0
            and router_flips <= router_allowed
            and balance_err <= TOL_BALANCE
            and chosen_held["differ_err_over_tol"] <= 1.0
            and chosen_held["far_err_over_tol"] <= 1.0
            and chosen_held["beyond_err_over_tol"] <= 1.0))
    if stand_in is not None:
        faulty = stand_in.pop("selection")
        verdict["control"] = dict(
            _held_to(want, stand_in["loss"], stand_in["index_loss"],
                     np.pad(stand_in["counts"], ((0, 0), (0, 1))),
                     lambda n: stand_in["grads"][n], cfg, tokens_n),
            **_selection_held(faulty["differ"], faulty["far"], cfg, tokens),
            operands=jnp.dtype(control).name,
            compile_s=stand_in["compile_s"], run_s=stand_in["run_s"])
    return verdict
