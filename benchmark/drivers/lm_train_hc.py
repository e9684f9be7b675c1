"""The eighth language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/xing4`` (four residual streams mixed round
every sublayer by manifold-constrained hyper-connections; latent attention
at query and key heads of 192 and value heads of 128 under YaRN and a
softmax scale of its own; 8 held experts of 1,024 under a biased sigmoid
route over 64 beside a shared one) on ``models/mla_moe``'s decoder path,
tables, step and ``Trainer``. The load, the calibration of the routers'
biases and the window are ``drivers/lm_train``'s, the allowance for a trace
that lost a stretch ``drivers/lm_train_hybrid``'s, all used as they are;
what is this file's own is the model's configuration, what ``layers/hc``
asks of the window, and the comparison (another reference, a class of
tables, limits and controls of its own). ``benchmark/LM_HC.md`` has the
whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice, the forward pass once: every program compiled);
calibration of the routers' selection biases by forward-only passes over
the pool; Adam's state back to zero and the comparison with
``reference/xing4`` on one pool batch through the measured step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import hc_shapes
from benchmark.drivers import lm_train, lm_train_hybrid
from benchmark.layers import hc
from benchmark.reference import xing4 as ref

# The limits of the comparison, set as ``lm_train``'s are and for its
# reasons (bfloat16 operands against a float32 reference; a token whose
# fourth and fifth scores lie within the activations' rounding goes to
# another expert than in the reference and takes its part of that expert's
# and the router's gradient with it). The hyper-connections' tables
# (``hc_phi``, ``hc_b``, ``hc_alpha``) are a class of their own: their
# gradients are sums over every position of what 24 numbers a position do
# to everything after them, and a faulty map shows there first. They are
# held a KIND at a time over all ten sublayers (the 30 numbers of every
# ``hc_alpha`` as one vector, the 240 of ``hc_b``, the 3.4M of ``hc_phi``):
# a sublayer's three gains take a gradient that is a sum over 4,096
# positions of signed terms, which on some seed in some sublayer nearly
# cancels, and the table's own norm is then no yardstick (one table of 30
# read 1.44 and 1.23 of its own norm on two seeds of nine where the kind
# read 0.111 and 0.101: LM_HC.md; ``streams_by_table`` keeps the tables'
# own readings for the record). Each limit but two lies between two
# readings on the chip (benchmark/LM_HC.md has the table with its seeds;
# PERF.md section 6, PR 60): the largest the program showed over 14 seeds,
# and the smallest the CONTROLS showed on one: the reference computed as a
# faulty program would, in the measured step's place
# (``benchmark/lm_hc_control.py``), each of which has to come out as not
# agreeing. Program's largest / controls' smallest (worst table of the
# class, as the limit is applied) / limit:
#   TOL_NORM   plain 0.123 / 0.238 (``no_sinkhorn``; ``operands_float8``
#              0.473) / 0.18; experts 0.234 / 0.440 (``no_sinkhorn``) / 0.34;
#              router 0.303 / 0.582 / 0.42; streams 0.111 / 0.242
#              (``operands_float8``, read at 512 positions on the CPU, as
#              are the maps' own three: ``post_unscaled`` 0.356,
#              ``no_sinkhorn`` 0.468, ``static_maps`` 1.0; the chip's
#              readings of the kinds: LM_HC.md) / 0.2.
#   TOL_ELEM   plain 0.105 / 0.233 / 0.18; experts 0.306 / 0.275
#              (``no_sinkhorn``; the others 0.48 and more) / 0.42 and
#              router 0.368 / 0.485 (``no_sinkhorn``) / 0.5, the two that
#              do NOT lie under that one control's reading: it is held by
#              the plain class 1.3 times over; streams 0.156 / 0.191
#              (``operands_float8``, which the plain class holds 3 times
#              over; the maps' own 0.311 and more) / 0.25.
#   TOL_COUNT  240 of 16,384 assignments a layer = 0.47 of the limit / 518
#              (``no_sinkhorn``, 1.01 of it) / ``lm_train``'s 2^-5 (512).
#   TOL_LOSS   0.52 of ``lm_train``'s 6e-4 / 0.56 to 2.5.
TOL_LOSS = lm_train.TOL_LOSS
TOL_NORM = {"plain": 0.18, "experts": 0.34, "router": 0.42, "streams": 0.2}
TOL_ELEM = {"plain": 0.18, "experts": 0.42, "router": 0.5, "streams": 0.25}
TOL_COUNT = lm_train.TOL_COUNT
TOL_MOVE = lm_train.TOL_MOVE
ROUTER_MARGIN = 0           # ref.MARGINS[0] = 1e-4
# what stands in the measured step's place, by name: the context under
# which the reference is traced
CONTROLS = {
    "operands_float8": lambda: ref.rounded_operands(lm_train.CONTROL),
    "static_maps": lambda: ref.maps_control("static_maps"),
    "no_sinkhorn": lambda: ref.maps_control("no_sinkhorn"),
    "post_unscaled": lambda: ref.maps_control("post_unscaled")}
STREAMS = ("hc_phi", "hc_b", "hc_alpha")


def table_class(name: str) -> str:
    """``lm_train.table_class`` and this model's own: a sublayer's three
    hyper-connection tables."""
    return ("streams" if name.split(".")[-1] in STREAMS
            else lm_train.table_class(name))


def _model_config(cell):
    from multiverso_tpu.models import mla_moe, xing4

    c = cell.config
    sc = c["rope_scaling"]
    return xing4.Xing4Config(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        q_lora_rank=int(c["q_lora_rank"]), kv_lora_rank=int(c["kv_lora_rank"]),
        qk_nope_dim=int(c["qk_nope_head_dim"]),
        qk_rope_dim=int(c["qk_rope_head_dim"]),
        v_head_dim=int(c["v_head_dim"]), rope_theta=float(c["rope_theta"]),
        yarn=mla_moe.Yarn(
            float(sc["factor"]),
            int(sc["original_max_position_embeddings"]),
            float(sc["beta_fast"]), float(sc["beta_slow"]),
            ref.frequencies(int(c["qk_rope_head_dim"]), c)[1]),
        mscale_all_dim=float(sc["mscale_all_dim"]),
        dense_ffn=int(c["intermediate_size"]),
        n_dense_layers=int(c["first_k_dense_replace"]),
        n_moe_layers=int(c["num_hidden_layers"])
        - int(c["first_k_dense_replace"]),
        moe_ffn=int(c["moe_intermediate_size"]),
        n_experts=int(c["published"]["n_routed_experts"]),
        experts_held=int(c["n_routed_experts"]),
        expert_offset=int(c["expert_offset"]),
        top_k=int(c["num_experts_per_tok"]),
        routed_scale=float(c["routed_scaling_factor"]),
        n_mtp=int(c["num_nextn_predict_layers"]),
        mtp_weight=float(c["mtp_loss_weight"]),
        bias_speed=float(c["bias_update_speed"]),
        eps=float(c["rms_norm_eps"]), streams=int(c["hc_mult"]),
        sinkhorn_iters=int(c["hc_sinkhorn_iters"]),
        hc_eps=float(c["hc_eps"]),
        res_clamp=(float(c["mhc_h_res_clamp_min"]),
                   float(c["mhc_h_res_clamp_max"])))


def setup(cell, controls=()) -> Dict[str, Any]:
    """``lm_train.setup``'s order under this model's configuration and
    comparison. ``controls``: names of :data:`CONTROLS`, see
    :func:`_compare` (``lm_hc_control.py`` gives them)."""
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
    """``lm_train.window``; what ``layers/hc`` asks: the bytes the stream
    maps must move for the window's steps (``hc_shapes.step_bytes``); and
    the largest stream-mix error the window's steps read back."""
    trainer, pool = state["trainer"], state["pool"]
    trainer.hc_res_error = 0.0
    run = lm_train.window(state, seconds)
    run["hc_bytes"] = run["attempted"] * hc_shapes.step_bytes(
        state["cell"].config, int(pool.shape[1]), int(pool.shape[2]))
    run["facts"]["hc_res_error"] = float(trainer.hc_res_error)
    return run


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """``lm_train_hybrid.check`` (``lm_train.check`` with the allowance for
    a trace that lost a stretch of the device's line); the stream maps'
    device seconds by scope from the join of the trace that ``run.py`` has
    just stopped with the step's ``xla.program`` record, for ``layers/hc``,
    under the same allowance (the bytes are then those of the steps seen);
    and the window's largest stream-mix error (``hc_res_error``: the
    largest ``abs(row or column sum of H_res - 1)`` of any position of any
    sublayer of any step) held under the configuration's
    ``hc_res_error_limit``."""
    expected = int(run["attention_kernels"])
    verdict = lm_train_hybrid.check(state, run)
    run["hc_s"] = hc.scope_seconds(state["cell"].name)
    seen = int(run["attention_kernels"])
    if seen != expected:
        run["hc_bytes"] = run["hc_bytes"] * seen // expected
    if run["hc_s"]:
        # the whole join, for a reader of the log: where the step's time
        # goes by scope and pass (what ``dump_metrics.py scopes`` prints)
        verdict["detail"]["scope_s"] = {
            k: run["hc_s"][k] for k in ("every_scope", "filed_s", "busy_s")}
    error = float(run["facts"]["hc_res_error"])
    limit = float(state["cell"].config["hc_res_error_limit"])
    verdict["detail"].update(hc_res_error=error, hc_res_error_limit=limit)
    verdict["correct"] = bool(verdict["correct"] and 0.0 <= error <= limit)
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
    streams: Dict[str, List[float]] = {}    # the new class, table by table
    # the new class is held a KIND at a time over all sublayers (module
    # comment): sum of squared errors and norms, largest error and value
    pooled: Dict[str, List[float]] = {}

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
        cls, kind = table_class(n), n.split(".")[-1]
        if cls != "streams":
            held(n, cls, kind, e_norm, g_norm, e_max, g_max)
            continue
        streams[n] = [e_norm / (g_norm + 1e-30), e_max / (g_max + 1e-30)]
        acc = pooled.setdefault(kind, [0.0, 0.0, 0.0, 0.0])
        acc[0], acc[1] = acc[0] + e_norm ** 2, acc[1] + g_norm ** 2
        acc[2], acc[3] = max(acc[2], e_max), max(acc[3], g_max)
    for kind, (e2, g2, e_max, g_max) in pooled.items():
        held("*." + kind, "streams", kind, e2 ** 0.5, g2 ** 0.5, e_max, g_max)
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
        by_kind=by_kind, by_class=by_class, streams_by_table=streams,
        agrees=bool(identities and all(
            np.isfinite(r) and r <= 1.0 for r in ratios.values())))


def _map_spread(state: Dict[str, Any]) -> Dict[str, Any]:
    """What the draw of the hyper-connections gives on this batch, for the
    record (``assumed.hc_init``): of the first and the last block's
    attention sublayer, each map's standard deviation over the positions
    (the smallest and the largest entry's) and the mean ``H_res``'s
    distance from the identity and from the uniform mix."""
    from multiverso_tpu.models import mla_moe

    cfg, trainer = state["cfg"], state["trainer"]
    shapes = mla_moe.param_shapes(cfg)
    names = [layer.name for layer in cfg.layers() if layer.name != "mtp"]

    def maps(datas, tokens):
        params = {n: datas[n][:mla_moe.table_shape(shapes[n])[0]].reshape(
            shapes[n]) for n in shapes}
        x = mla_moe._expand(mla_moe._embed(params, tokens, cfg), cfg)
        seen = {}
        rows = {name: row for row, name in enumerate(
            mla_moe.expert_layers(cfg))}
        for layer in cfg.layers():
            if layer.name == "mtp":
                continue
            p = mla_moe._sub(params, layer.name)
            if layer.name in (names[0], names[-1]):
                seen[layer.name] = mla_moe.stream_maps(
                    x, p["attn.hc_phi"], p["attn.hc_b"], p["attn.hc_alpha"],
                    cfg)
            x = mla_moe._run_block(
                x, p, layer, trainer.bias[rows[layer.name]]
                if layer.name in rows else None, cfg, remat=False)[0]
        return seen

    datas = {n: st["data"] for n, st in trainer.states.items()}
    out = {}
    for name, (pre, post, res) in jax.device_get(
            jax.jit(maps)(datas, state["pool"][0])).items():
        n = res.shape[0]
        mean = res.mean(-1)
        out[name] = {
            "pre_sd": [float(pre.std(-1).min()), float(pre.std(-1).max())],
            "post_sd": [float(post.std(-1).min()),
                        float(post.std(-1).max())],
            "res_sd": [float(res.std(-1).min()), float(res.std(-1).max())],
            "res_mean_from_identity": float(np.abs(mean - np.eye(n)).max()),
            "res_mean_from_uniform": float(np.abs(mean - 1.0 / n).max())}
    return out


def _compare(state: Dict[str, Any], controls=()) -> Dict[str, Any]:
    """One pool batch at the timed sizes through the measured step from
    zero Adam state, against ``reference/xing4`` on the same tables and the
    calibrated biases: ``lm_train._compare``'s procedure (the reference
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

    # the draw's spread is for the record: the controls' run takes it
    spread = _map_spread(state) if controls else None
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
            with (CONTROLS[how]() if how else ref.maps_control(None)):
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
        map_spread=spread, reference_s=want["run_s"],
        reference_compile_s=want["compile_s"], measured_step_s=t_step,
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
