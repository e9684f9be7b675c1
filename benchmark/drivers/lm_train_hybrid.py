"""The fourth language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/nemotron_h`` (ONE mixer a block: Mamba-2
state-space mixers over a chunked scan, relu2 experts chosen by a sigmoid
under a selection bias beside a wider shared one, one grouped-query
attention without positions) on ``models/mla_moe``'s decoder path, tables,
step and ``Trainer``. The load, the bias calibration, the window and the
check after it are ``drivers/lm_train``'s, used as they are; what is this
file's own is the model's configuration, the two-matrix count of the
experts' operations and the comparison (another reference, limits and
controls of its own). ``benchmark/LM_HYBRID.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice, the forward pass once: every program compiled);
calibration of the routers' selection biases by forward-only passes over
the pool; Adam's state back to zero and the comparison with
``reference/nemotron_h`` on one pool batch through the measured step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import ssm_shapes
from benchmark.drivers import lm_train
from benchmark.reference import nemotron_h as ref

# The limits of the comparison, set as ``lm_train``'s are and for its
# reasons (bfloat16 operands against a float32 reference; a token whose
# sixth and seventh scores lie within the activations' rounding goes to
# another expert than in the reference and takes its part of that expert's
# and the router's gradient with it). Each lies between two readings on the
# chip (benchmark/LM_HYBRID.md; PERF.md section 6, PR 47): the largest the
# program showed over its seeds, and the smallest the CONTROLS showed: the
# reference computed as a faulty step would, in the measured step's place
# (``benchmark/lm_hybrid_control.py``), each of which has to come out as
# not agreeing: the scan's state and running sums kept in bfloat16, and the
# scan without the state one chunk hands the next. Program's largest over
# 28 seeds / controls' smallest over 3 and 6 / limit: plain norm 0.0568 /
# 0.097 / 0.08 and worst element 0.0798 / 0.126 / 0.11; experts 0.1113 /
# 0.228 / 0.16 and 0.1234 / 0.147 / 0.18 (the one limit NOT between its
# readings: 0.147 would leave the program 1.19 of room, and the control is
# held by every other class); router 0.2074 / 0.340 / 0.27 and 0.2121 /
# 0.326 / 0.30; counts 566 of 98,304 / 836 / 2^-7 (768); the loss 5.3e-5 /
# 0 to 1.8e-4 / ``lm_train_window``'s 2e-4 (the precision hardly moves a
# mean over 16,383 positions). LM_HYBRID.md has the table with its reasons.
TOL_LOSS = 2e-4
TOL_NORM = {"plain": 0.08, "experts": 0.16, "router": 0.27}
TOL_ELEM = {"plain": 0.11, "experts": 0.18, "router": 0.30}
TOL_COUNT = 2.0 ** -7
TOL_MOVE = lm_train.TOL_MOVE
ROUTER_MARGIN = 0          # ref.MARGINS[0] = 1e-4
# what stands in the measured step's place: ``reference.scan_control``'s
# faults
CONTROLS = ("sums_bfloat16", "no_carry")


def _model_config(cell):
    from multiverso_tpu.models import nemotron_h

    c = cell.config
    return nemotron_h.NemotronHConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        pattern=ref.layer_kinds(c),
        ssm_heads=int(c["mamba_num_heads"]),
        ssm_head_dim=int(c["mamba_head_dim"]),
        ssm_groups=int(c["n_groups"]), ssm_state=int(c["ssm_state_size"]),
        conv_kernel=int(c["conv_kernel"]), chunk=int(c["chunk_size"]),
        time_step_min=float(c["time_step_min"]),
        time_step_max=float(c["time_step_max"]),
        time_step_floor=float(c["time_step_floor"]),
        a_init=tuple(float(a) for a in c["a_init_range"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        moe_ffn=int(c["moe_intermediate_size"]),
        shared_ffn=int(c["moe_shared_expert_intermediate_size"]),
        n_experts=int(c["published"]["n_routed_experts"]),
        experts_held=int(c["n_routed_experts"]),
        expert_offset=int(c["expert_offset"]),
        top_k=int(c["num_experts_per_tok"]),
        routed_scale=float(c["routed_scaling_factor"]),
        bias_speed=float(c["bias_update_speed"]),
        eps=float(c["layer_norm_epsilon"]))


def setup(cell, controls=()) -> Dict[str, Any]:
    """``lm_train.setup``'s order under this model's configuration and
    comparison. ``controls``: names of :data:`CONTROLS`, see
    :func:`_compare` (``lm_hybrid_control.py`` gives them)."""
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


class _Blocks:
    """``cfg`` as ``lm_train.window`` reads it: that driver counts a
    step's attention kernels under ``MLAMoEConfig``'s three fields, which
    this model's configuration does not carry; here the blocks that have an
    attention core are the ``*`` blocks alone."""
    n_dense_layers = n_mtp = 0

    def __init__(self, cfg):
        self._cfg = cfg
        self.n_moe_layers = sum(layer.attn == "full"
                                for layer in cfg.layers())

    def __getattr__(self, name):
        return getattr(self._cfg, name)


def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """``lm_train.window``; the experts' operations counted for TWO
    matrices an expert (``ssm_shapes``: that file's count is for three)."""
    cfg = state["cfg"]
    run = lm_train.window(dict(state, cfg=_Blocks(cfg)), seconds)
    run["expert_flops"] = ssm_shapes.expert_products_flops(
        run["facts"]["held_rows"], cfg.dim, cfg.moe_ffn)
    return run


# The kernels a trace may lack of those a window ran: a host that stands
# still leaves the device the step it runs and the one queued ahead, so two
# steps' cores of four kernels each. A kernel the program no longer names
# under the scope is missed once a STEP, 25 times or more in a window, and
# is still told.
KERNELS_A_TRACE_MAY_LOSE = 8


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """``lm_train.check``. Where the trace lacks a stretch of the device's
    line (a host stopped for a second drains no trace buffer; the driver's
    first traced run of this cell, seed 2115252635, read 20.14 s busy of 26
    steps' 20.63 and a gap of 1.23 s: PERF.md section 6) its flash kernels
    are fewer than the steps ran, and ``layers/attn`` would leave the
    share out. The stretch is missing from ``busy_s`` too, so the kernels
    seen over the busy time seen is still the share: up to
    :data:`KERNELS_A_TRACE_MAY_LOSE` short, the count expected is the
    count seen, and the detail says both."""
    verdict = lm_train.check(state, run)
    seen = int(run["attention_s"].get("kernels", 0))
    expected = int(run["attention_kernels"])
    verdict["detail"]["attention_kernels"] = {"seen": seen,
                                              "expected": expected}
    if 0 < expected - seen <= KERNELS_A_TRACE_MAY_LOSE:
        run["attention_kernels"] = seen
    return verdict


# ---------------------------------------------------------------------- #
# the comparison with the reference: lm_train's procedure, under this
# model's reference, limits and controls (a file the benchmark has is not
# edited, and reference and limits are that file's module constants)
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


def _compare(state: Dict[str, Any], controls=()) -> Dict[str, Any]:
    """One pool batch at the timed sizes through the measured step from
    zero Adam state, against ``reference/nemotron_h`` on the same tables
    and the calibrated biases: ``lm_train._compare``'s procedure (the
    reference first, on the live tables' values, with Adam's moments set
    aside; then the moments back as zeros placed as they were, the measured
    step, and each table's stored gradient ``m / (1 - beta1)`` compared on
    the device).

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
            with ref.scan_control(how):
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
