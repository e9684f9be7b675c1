"""A language model trained through Adam tables, closed loop, one
trainer: ``multiverso_tpu/models/mla_moe.Trainer`` steps one donated
jitted program a batch (loss, float32 gradients, every table's
``functional_add``, the routers' bias rule) and reads the loss and the
routing counts back. Work is main-loss tokens: sequences x (positions - 1)
a step. ``benchmark/LM.md`` has the whole of it.

Set-up, in order: tables from the seed; the batch pool on the device;
warm-up (the step twice, the forward pass once: every program compiled);
calibration of the routers' selection biases by forward-only passes over
the pool; Adam's state back to zero and the comparison with
``reference/mla_moe`` on one pool batch through the measured step.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import gen, lm_shapes
from benchmark.layers import attn as attn_layer
from benchmark.reference import mla_moe as ref

# Matrix products take bfloat16 operands and sum in float32 (the
# configuration's ``assumed.compute_precision``); the reference is float32
# at the highest precision. A gradient lies behind up to six blocks of
# such products, forward, recomputed and backward. And where a token's
# fourth and fifth scores lie within the activations' rounding, it goes
# to another expert than in the reference (about 1% of a layer's
# assignments do: 64 scores lie close), and takes its whole contribution
# to that expert's and the router's gradient with it. Hence three classes
# of table: the routers, the held experts' matrices, and the rest. Held
# against the reference, on the chip and under --cpu-tiny alike (the
# program rounds to bfloat16 there too). Each limit lies between two
# readings (benchmark/LM.md; PERF.md section 6, PR 33): the largest the
# program showed over its seeds on the chip, and what the CONTROL shows: the
# reference computed as a step in float8_e4m3 would (``CONTROL``,
# ``reference.rounded_operands``: every product's operands rounded,
# forward and backward, each scaled as a whole so that no gradient rounds
# to nothing) and put in the measured step's place in this comparison
# (``benchmark/lm_control.py``), which has to come out as not agreeing.
# Program's largest over 46 seeds / control's smallest over 2 (worst table
# of the class, as the limit is applied) / limit:
#   TOL_LOSS   |loss - ref| / max(|ref|, 1): 1.95e-4 / 3.4e-4 / 6e-4. The
#              precision hardly moves it (the control's second seed read
#              6.4e-4), so the limit is three times the largest seen.
#   TOL_NORM   ||g - g_ref|| / ||g_ref||, every table, by its class:
#              plain 0.068 / 0.358 / 0.125; experts 0.175 / 0.521 / 0.35;
#              router 0.285 / 0.625 / 0.42.
#   TOL_ELEM   max |g - g_ref| / max |g_ref|, every table: a single wild
#              value, which the norm would not show. Plain 0.059 / 0.340 /
#              0.125; experts 0.185 / 0.614 / 0.35; router 0.361 / 0.661 /
#              0.5 (the tightest: 1.39 over the one seed that read 0.361,
#              the next largest 0.26).
#   TOL_COUNT  per expert layer, sum over the 64 experts of |c - c_ref|
#              over the layer's tokens x 4 assignments: 686 of 65,536 =
#              0.0105 / 6,000 = 0.092 / 2^-5 (2,048). Exact agreement is
#              not to be had (top-k is discontinuous and the inputs differ
#              by bfloat16's rounding); the identities hold exactly: every
#              layer's counts sum to tokens x 4, nothing overflowed.
#   TOL_MOVE   the value's move against NumPy's Adam on the gradient the
#              step stored, on eight rows spread over every table:
#              |new - want| <= 2^-22 |old| + TOL_MOVE * lr, and ``v`` to
#              1e-5. float32 tables keep it (seen 0.25 of the limit);
#              bfloat16 tables (half an ulp of a 0.05 weight is 1e-4, a
#              step 3.65e-7) cannot. The control has no tables to move.
#   router     the program's router alone (``moe.sigmoid_route``) on a
#              seeded float32 input of the timed size against the
#              reference's: it is float32 at the highest precision, so at
#              most the tokens whose choice hangs by under 1e-4 may differ
#              (seen 0 of an allowed 160 to 340).
TOL_LOSS = 6e-4
TOL_NORM = {"plain": 2.0 ** -3, "experts": 0.35, "router": 0.42}
TOL_ELEM = {"plain": 2.0 ** -3, "experts": 0.35, "router": 0.5}
TOL_COUNT = 2.0 ** -5
TOL_MOVE = 2.0 ** -12
ROUTER_MARGIN = 0          # ref.MARGINS[0] = 1e-4
CONTROL = jnp.float8_e4m3fn
MOVE_ROWS = 8


def table_class(name: str) -> str:
    kind = name.split(".")[-1]
    return ("router" if kind == "router"
            else "experts" if kind in ("eg", "eu", "ed") else "plain")


def lm_batches(vocab: int, sequences: int, positions: int, n_batches: int,
               zipf_a: float, doc_tokens, eod: int, seed: int) -> np.ndarray:
    """``[n_batches, sequences, positions]`` int32: packed documents, each
    ``doc_tokens[0] .. doc_tokens[1] - 1`` tokens from a bounded
    Zipf(zipf_a) over ids 1..vocab-1 (sent through a fixed multiplicative
    hash, so that frequent ids are not the table's first rows) and ended
    by ``eod``. Every sequence is full: the seed draws ids and document
    lengths, never a shape."""
    rng = np.random.default_rng([int(seed), 0x6C6D7472])      # "lmtr"
    n = n_batches * sequences * positions
    rank = np.minimum(np.searchsorted(gen.bounded_zipf_cdf(vocab - 1, zipf_a),
                                      rng.random(n)), vocab - 2)
    ids = (1 + (rank.astype(np.int64) * 40503 + 977) % (vocab - 1)).astype(
        np.int32)
    lengths = rng.integers(doc_tokens[0], doc_tokens[1],
                           size=n // doc_tokens[0] + 1)
    ends = np.cumsum(lengths + 1) - 1
    ids[ends[ends < n]] = eod
    return ids.reshape(n_batches, sequences, positions)


def _model_config(cell):
    from multiverso_tpu.models import mla_moe

    c = cell.config
    return mla_moe.MLAMoEConfig(
        vocab=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        q_lora_rank=int(c["q_lora_rank"]), kv_lora_rank=int(c["kv_lora_rank"]),
        qk_nope_dim=int(c["qk_nope_head_dim"]),
        qk_rope_dim=int(c["qk_rope_head_dim"]),
        v_head_dim=int(c["v_head_dim"]), rope_theta=float(c["rope_theta"]),
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
        eps=float(c["rms_norm_eps"]))


def setup(cell, control=None) -> Dict[str, Any]:
    """``control``: see :func:`_compare` (``lm_control.py`` gives it)."""
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
            scales={"router": float(c["router_init_scale"])})
    with cell.timed("batches"):
        pool = jax.block_until_ready(jnp.asarray(lm_batches(
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
        state["calibration"] = _calibrate(state)
    with cell.timed("reference_check"):
        state["verdict"] = _compare(state, control)
    return state


# ---------------------------------------------------------------------- #
# the routers' balance before the window
# ---------------------------------------------------------------------- #
def _layer_readings(counts: np.ndarray, cfg) -> Dict[str, np.ndarray]:
    """Per expert layer, from counts [layers, E (+1)] of one batch or
    summed over several: the busiest of all E experts over the mean, and
    the held experts' share of the routed rows in percent."""
    c = np.asarray(counts)[:, :cfg.n_experts].astype(np.float64)
    lo = cfg.expert_offset
    return {"max_over_mean": c.max(1) / c.mean(1),
            "held_share": 100.0 * c[:, lo:lo + cfg.experts_held].sum(1)
            / c.sum(1)}


def _calibrate(state: Dict[str, Any]) -> Dict[str, Any]:
    """Forward-only passes over the pool, the bias rule after each at a
    speed that starts at ``start_speed`` and is multiplied by ``shrink``
    every ``passes_per_speed`` passes down to the published one; ends
    when, at the published speed, the loads of one whole turn of the pool
    (every batch the window will feed, summed) have every layer's busiest
    expert within ``load_max_over_mean`` of the mean and every layer's
    held share within ``held_share_within`` of an even share. Weights are
    not touched."""
    from multiverso_tpu.parallel import moe

    cfg, tr, trainer = state["cfg"], state["cell"].traffic, state["trainer"]
    cal, pool = tr["calibration"], state["pool"]
    turn, even = int(pool.shape[0]), 100.0 * cfg.experts_held / cfg.n_experts
    speed, bias = float(cal["start_speed"]), trainer.bias
    history: List[np.ndarray] = []
    at_published = 0
    ok = False
    for k in range(int(cal["max_passes"])):
        _, counts = state["forward"](trainer.states, bias, pool[k % turn])
        counts = np.asarray(counts)
        bias = moe.bias_update(bias, counts[:, :cfg.n_experts], speed)
        history.append(counts)
        at_published = at_published + 1 if speed <= cfg.bias_speed else 0
        if at_published >= turn:
            last = _layer_readings(np.sum(history[-turn:], axis=0), cfg)
            ok = (float(last["max_over_mean"].max())
                  <= float(cal["load_max_over_mean"])
                  and float(np.abs(last["held_share"] - even).max())
                  <= float(cal["held_share_within"]))
            if ok:
                break
        if (k + 1) % int(cal["passes_per_speed"]) == 0:
            speed = max(speed * float(cal["shrink"]), cfg.bias_speed)
    trainer.bias = bias
    last = _layer_readings(np.sum(history[-turn:], axis=0), cfg)
    return {"passes": len(history), "balanced": bool(ok),
            "first_max_over_mean": float(
                _layer_readings(history[0], cfg)["max_over_mean"].max()),
            "last_turn_max_over_mean": [float(x)
                                        for x in last["max_over_mean"]],
            "last_turn_held_share": [float(x) for x in last["held_share"]],
            "last_turn_worst_batch": max(float(_layer_readings(
                h, cfg)["max_over_mean"].max()) for h in history[-turn:])}


# ---------------------------------------------------------------------- #
# the window
# ---------------------------------------------------------------------- #
def window(state: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    from multiverso_tpu.models import mla_moe

    trainer, pool, cfg = state["trainer"], state["pool"], state["cfg"]
    whole, losses, counts = [], [], []

    def took(done, last):
        if done is not None:
            losses.append(done[0])
            counts.append(done[1])
            whole.append((time.perf_counter() - last) * 1e3)

    # one step ahead: the next program is queued before the last one's
    # loss is read back, so that a stop of the host (110 ms, about one in
    # 20 s on some machines: PERF.md) costs the device nothing
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
    steps = [mla_moe.routing_counts(c, cfg) for c in counts]
    loads = _layer_readings(np.sum(counts, axis=0), cfg)
    held_rows = sum(s["held_rows"] for s in steps)
    facts = {"steps": i, "tokens_a_step": sequences * positions,
             "loss_first": losses[0], "loss_last": losses[-1],
             "routed_rows": sum(s["routed_rows"] for s in steps),
             "held_rows": held_rows,
             "overflow_rows": sum(s["overflow_rows"] for s in steps),
             # a step's worst layer, the worst step; and the window's
             # loads as a whole, layer by layer
             "load_max_over_mean_worst_step": max(
                 s["load_max_over_mean"] for s in steps),
             "load_max_over_mean": [float(x)
                                    for x in loads["max_over_mean"]],
             "held_share": [float(x) for x in loads["held_share"]],
             "calibration": state["calibration"]}
    f = cfg.moe_ffn
    return {"work": i * sequences * (positions - 1), "elapsed_s": now - t0,
            "attempted": i,
            "failed": int(sum(1 for x in losses if not np.isfinite(x))),
            "losses": losses, "spans_ms": {"step": whole}, "facts": facts,
            # the grouped products' stacked operands and results: what
            # ``moe.expert_device_share.lm`` asks the reduction for
            "table_shapes": [(cfg.experts_held, cfg.dim, f),
                             (cfg.experts_held, f, cfg.dim)],
            "expert_flops": lm_shapes.expert_products_flops(
                held_rows, cfg.dim, f),
            # a block's attention core is four kernels: forward, forward
            # again in the backward pass, dQ, dK with dV
            "attention_kernels": 4 * i * (
                cfg.n_dense_layers + cfg.n_moe_layers + cfg.n_mtp)}


def check(state: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Any]:
    """The comparison made in set-up, and after the window: every loss
    finite, the last under the first, no row over the held experts'
    buffer; the states adopted back into their tables.

    ``run.py`` calls this between stopping the trace and reducing it, and
    deletes the trace before a reader runs; the reduction keeps ten
    operations and a step has 24 attention kernels. So on a traced run
    ``layers/attn`` sums them from the trace here, and ``run`` carries the
    sum to its reader: the one thing this writes into ``run``."""
    state["trainer"].adopt()
    detail = dict(state["verdict"])
    losses = run["losses"]
    detail["losses_finite"] = bool(np.all(np.isfinite(losses)))
    # the last step's loss against the same batch's a turn of the pool
    # earlier; a window shorter than a turn has no such pair
    turn = int(state["pool"].shape[0])
    detail["loss_fell"] = bool(len(losses) <= turn
                               or losses[-1] < losses[-1 - turn])
    detail["overflow_rows"] = int(run["facts"]["overflow_rows"])
    run["attention_s"] = attn_layer.kernel_seconds(state["cell"].name)
    return {"correct": bool(detail["step_agrees"] and detail["losses_finite"]
                            and detail["loss_fell"]
                            and detail["overflow_rows"] == 0),
            "detail": detail}


# ---------------------------------------------------------------------- #
# the comparison with the reference
# ---------------------------------------------------------------------- #
COMPARED_ELEMENTS = 4 << 20


def _stride(shape) -> int:
    """Every table's gradient is compared; of a table of more than 4M
    values, every k-th row (k the least that leaves 4M or fewer): the
    reference's gradients leave the device before the measured step runs,
    and 2.8 GB of them took a minute of set-up."""
    return max(1, -(-int(np.prod(shape)) // COMPARED_ELEMENTS))


def _move_rows(rows: int) -> np.ndarray:
    """``MOVE_ROWS`` rows spread over the whole of a table (every held
    expert's part of a stacked one)."""
    return np.unique(np.linspace(0, rows - 1, MOVE_ROWS).astype(np.int64))


@jax.jit
def _errors(got, want):
    return (jnp.linalg.norm((got - want).ravel()),
            jnp.linalg.norm(want.ravel()),
            jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want)))


def _held_to(want: Dict[str, Any], loss: float, counts: np.ndarray,
             grad_of, cfg, tokens_n: int) -> Dict[str, Any]:
    """A step's loss, routing counts [layers, E + 1] and gradients
    (``grad_of(name)``: the compared rows of that table's) against the
    reference's ``want``, each over its limit: whatever stands in the
    measured step's place goes through here."""
    worst = {"norm": (0.0, ""), "elem": (0.0, "")}
    by_kind: Dict[str, List[float]] = {}       # raw errors, for the record
    for n, g in want["grads"].items():
        e_norm, g_norm, e_max, g_max = (float(x)
                                        for x in _errors(grad_of(n), g))
        seen = by_kind.setdefault(n.split(".")[-1], [0.0, 0.0])
        seen[0] = max(seen[0], e_norm / (g_norm + 1e-30))
        seen[1] = max(seen[1], e_max / (g_max + 1e-30))
        cls = table_class(n)
        worst["norm"] = max(worst["norm"], (
            e_norm / (TOL_NORM[cls] * g_norm + 1e-30), n))
        worst["elem"] = max(worst["elem"], (
            e_max / (TOL_ELEM[cls] * g_max + 1e-30), n))
    by_class: Dict[str, List[float]] = {}
    for kind, seen in by_kind.items():
        c = by_class.setdefault(table_class("." + kind), [0.0, 0.0])
        c[0], c[1] = max(c[0], seen[0]), max(c[1], seen[1])
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
    zero Adam state, against ``reference/mla_moe`` on the same tables.

    The reference runs first, on the live tables' values and biases (the
    program's ``make_tables`` and the calibration made them: the two
    sides must start from the same model), with Adam's moments set aside
    (they are due to be zero for this step anyway, and the reference's
    gradients need their room); its gradients go to the host, the moments
    come back as zeros, the measured step runs, and each table's stored
    gradient (``m / (1 - beta1)``) is compared on the device with the
    reference's.

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
    b1, b2, eps = (float(cell.config[k]) for k in
                   ("adam_beta1", "adam_beta2", "adam_eps"))

    # Adam's moments out of the way; what comes back for the measured
    # step is zeros placed exactly as these were, so that the step's
    # program is the one the window runs and not a second compilation
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
                    ::_stride(shapes[n])] for n, g in grads.items()}

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
        lambda u, w, b: moe.sigmoid_route(u, w, b, mla_moe.held(cfg, tokens_n)))(
            route_in, router, bias[0])
    counts_alone_ref, ties_alone = jax.device_get(jax.jit(
        lambda u, w, b: ref.route_alone(u, w, b, c))(
            route_in, router, bias[0]))
    router_flips = int(np.abs(np.asarray(counts_alone)
                              - counts_alone_ref).sum())
    router_allowed = 2 * int(ties_alone[ROUTER_MARGIN])

    rows_of = {n: _move_rows(int(t.shape[0])) for n, t in tables.items()}
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
        return m[:int(tables[n].shape[0]):_stride(shapes[n])] / (1.0 - b1)

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
