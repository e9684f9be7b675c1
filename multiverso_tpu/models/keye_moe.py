"""A decoder of grouped-query attention over the keys a learned indexer
chooses for every query, the indexer trained beside it by a term of its
own in the loss, and routed experts alone in every layer under a softmax
route, trained through Adam tables: the sixth model on
``models/mla_moe.py``'s one decoder path (Keye-VL-2.0-30B-A3B's language
model: ``Qwen3MoeConfig``'s keys and an ``sa_config``).

This file is the model's configuration, its indexer, its selection and the
indexer's loss term. The projections, the q/k norms, the positions and
``W_o`` are ``gqa_moe``'s own lines (``heads_of``, ``out_of``: what
``mellum2`` and ``trinity`` run round their cores); the block, the
products, the expert layer's call, the softmax route and its balance term,
the chunked loss, the tables, the step and the ``Trainer`` are
``mla_moe``'s; the core is ``ops/attention_kernels.flash_attention`` with
one more operand, the selection. The equations, for a block with input
``x`` [B, S, D] and ``u = RMSNorm(x)``, ``u' = stop_gradient(u)``:

* ``h = x + Attn(u)``, ``y = h + Experts(RMSNorm(h))``.
* q, k, v as ``gqa_moe``'s with the q/k norms on; rotary positions at
  ``rope_theta``. The published ``mrope_section`` deals the frequencies
  over three position ids a token (``mla_moe.rotary(positions=,
  sections=)``); a text token's three ids are equal, which is plain rotary,
  and the trainer feeds text.
* the indexer (:func:`index_operands`, :func:`index_scores`): ``qI = u'
  W_qI`` (``index_heads`` heads of ``index_dim``), ``kI = LayerNorm(u'
  W_kI)`` (ONE head, gain and bias), plain rotary over all of both, ``w =
  u' W_w * index_heads^-0.5 * index_dim^-0.5``; ``I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])`` for ``s <= t``.
* the selection (:func:`select`): ``S_t`` = the ``min(index_topk, t + 1)``
  positions ``s <= t`` with the largest ``I[t, s]``, a tie to the lower
  ``s``: exactly ``jax.lax.top_k``'s set. Handed to the core as int8 [B,
  S, S], shared by the heads.
* core: softmax over ``S_t`` of ``q . k / sqrt(head_dim)``, times v.
* the indexer's term (:func:`index_loss`): with ``pbar[t, s]`` the mean
  over the query heads of the core's probabilities (held constant), ``LI =
  mean_t sum_{s in S_t} pbar (log pbar - log softmax_{S_t}(I))``. ``u'``
  and ``pbar`` are constants to it, so it moves the five indexer tensors
  alone; a selection has no derivative, so the cross-entropy moves none of
  them. Loss = cross-entropy + ``balance_coef`` x the routes' balance terms
  + ``index_coef`` x the layers' ``LI``.

Scores, selection and term are made ``index_chunk`` query rows at a time
(``lax.map`` / ``lax.scan``), so that no [S, S] float32 array outlives a
chunk: at 16,384 positions a chunk of 512 rows is 32 MB of scores, and the
32 heads' probabilities of the term 1 GB. The rematerialised block keeps
two things by name (:data:`KEPT_NAMES`): the SELECTION, int8 [B, S, S] (it
carries no gradient, so nothing of how it was made is read again: the
block made again runs neither the selection's scores nor its counting
passes, and the forward kernel, the kernels of the backward pass and the
term all read ONE selection, the forward's; :func:`selection_remade`
still counts the rows that a selection made again would choose
otherwise), and the term's gradients to the indexer's operands, which the
forward makes beside the term.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from multiverso_tpu.models import gqa_moe, mla_moe
from multiverso_tpu.models.mla_moe import Layer
from multiverso_tpu.ops import index_kernels
from multiverso_tpu.ops.attention_kernels import causal_pairs, flash_attention

_DEAD = -1e30
# what a rematerialised block keeps of a sparse layer
# (``mla_moe.kept_names``): the term's gradients to the indexer's operands
# and the selection
KEPT_NAMES = KEEP_GRADS, KEEP_SELECTION = (
    "mv.lm.attn.target.grads", "mv.lm.attn.select.chosen")


class KeyeMoEConfig(NamedTuple):
    vocab: int = 512                 # token ids held here (a slice)
    dim: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    layer_kinds: Tuple[str, ...] = ("sparse", "sparse")
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (1, 1, 2)   # adds up to head_dim / 2
    moe_ffn: int = 48
    n_experts: int = 16              # the router's outputs
    experts_held: int = 4
    expert_offset: int = 0
    top_k: int = 4
    balance_coef: float = 1e-3       # on the routers' load-balance terms
    eps: float = 1e-6
    # the indexer and its selection
    index_heads: int = 2
    index_dim: int = 4
    index_topk: int = 8              # keys a query keeps
    index_chunk: int = 16            # query rows a chunk of scores
    index_coef: float = 1.0          # on the indexer's terms
    attn: Optional[str] = None       # as MLAMoEConfig's
    expert_kernel: Optional[str] = None
    attn_block: int = 512
    loss_chunk: int = 4096
    compute_dtype: Any = jnp.bfloat16

    def layers(self) -> Tuple[Layer, ...]:
        return tuple(Layer(f"L{i}", kind, "experts")
                     for i, kind in enumerate(self.layer_kinds))

    def attn_shapes(self, kind: str) -> Dict[str, Tuple[int, ...]]:
        d, hi, di = self.dim, self.index_heads, self.index_dim
        return dict(gqa_moe.gqa_shapes(self), wq_i=(d, hi * di),
                    wk_i=(d, di), k_i_norm=(di,), k_i_bias=(di,),
                    ww_i=(d, hi))

    def attend(self, u, p, kind: str):
        return sparse_gqa(u, p, self)

    def index_grid(self, s: int) -> Dict[str, int]:
        """The indexer's and the selection's static counts over ``s``
        positions, as ``lm.step`` spans carry them: the indexer's sizes;
        of ONE head of ONE sequence the positions a query selects, those
        it sees causally and those the kernels compute (the triangle in
        whole tiles: a selection prunes no pair yet); the key tiles one
        of the term's two kernels visits a layer a sequence, a chunk's up
        to its diagonal, and the tiles of the uncut rectangle (equal where
        XLA makes the term: it computes every key); a layer's selection
        in bytes a sequence and what the blocks keep of the terms; and the
        operations a token of the matrix products a forward pass needs on
        this chip: the indexer's (its
        three products and its scores over the ``(s + 1) / 2`` keys a
        query sees on average), the selected core's two products, the
        term's one (the query heads' scores again) and the whole step's
        (those, the four projections, a router, the held experts at their
        even share, and the head)."""
        d, hd, h, hkv = self.dim, self.head_dim, self.n_heads, self.n_kv_heads
        hi, di, layers = self.index_heads, self.index_dim, len(self.layers())
        k = min(self.index_topk, s)
        selected = k * (k + 1) // 2 + (s - k) * k
        walked = causal_pairs(s, *mla_moe.attn_blocks(self, s))
        tiles = index_kernels.walk_of(s, _chunk_rows(self, s))
        kernels = mla_moe.attn_core(self) == "flash"
        index = 2 * d * (hi * di + di + hi) + hi * di * (s + 1)
        core, target = 4 * hd * h * selected // s, 2 * hd * h * selected // s
        rest = (2 * d * hd * 2 * (h + hkv) + 2 * d * self.n_experts
                + 6 * d * self.moe_ffn * self.top_k * self.experts_held
                // self.n_experts)
        return {"index_heads": hi, "index_dim": di,
                "index_topk": self.index_topk,
                "index_chunk": min(self.index_chunk, s),
                "attn_positions_selected": selected,
                "attn_positions_causal": walked["needed"],
                "attn_positions_computed": walked["computed"],
                "index_tiles_walked": (tiles.walked() if kernels
                                       else tiles.whole()),
                "index_tiles_whole": tiles.whole(),
                "select_bytes": s * s,
                # what the rematerialised blocks keep of the terms: their
                # float32 gradients to qI, kI and w, a sequence
                "target_kept_bytes": layers * 4 * s * (hi * di + di + hi),
                "index_flops_token": layers * index,
                "core_flops_token": layers * core,
                "target_flops_token": layers * target,
                "step_flops_token": 2 * d * self.vocab + layers * (
                    index + core + target + rest)}

    # ``gqa_moe``'s switches, as this model has them
    qk_norm = True
    attn_gate = False
    rope_kinds = ("sparse",)
    window = yarn = None
    post_norms = False               # ``mla_moe.block``'s
    embed_scale = 1.0
    route = "softmax"                # parallel/moe.HeldExperts.route
    routed_scale = 1.0               # the gates sum to 1
    expert_form = "gated_silu"       # parallel/moe.HeldExperts.form
    kept_names = KEPT_NAMES          # ``mla_moe.kept_names``

    def kept_bytes(self, b: int, s: int) -> int:
        """What :data:`KEPT_NAMES` keeps a step of ``b`` sequences of ``s``
        positions (``mla_moe.kept_grid``): ``index_grid``'s
        ``target_kept_bytes`` and a layer's ``select_bytes``, every
        layer."""
        grid = self.index_grid(s)
        return b * (grid["target_kept_bytes"]
                    + len(self.layers()) * grid["select_bytes"])

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def kv_group(self) -> int:       # query heads a key-value head
        return self.n_heads // self.n_kv_heads


# ---------------------------------------------------------------------- #
# the indexer
# ---------------------------------------------------------------------- #
def index_operands(u, p, cfg):
    """The indexer's three products on ``u`` [B, S, D]: (``qI`` [B, S,
    index_heads, index_dim] and ``kI`` [B, S, index_dim], both after
    rotary positions, ``kI`` after its LayerNorm; the heads' weights ``w``
    [B, S, index_heads], scaled), float32. Products with operands in the
    compute dtype and float32 sums."""
    b, s, _ = u.shape
    hi, di = cfg.index_heads, cfg.index_dim
    mm = functools.partial(mla_moe.matmul, dtype=cfg.compute_dtype,
                           out_dtype=jnp.float32)
    with jax.named_scope("mv.lm.attn.index"):
        qi = mm(u, p["wq_i"], False).reshape(b, s, hi, di)
        ki = mm(u, p["wk_i"], False)
        mean = jnp.mean(ki, -1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mean), -1, keepdims=True)
        ki = ((ki - mean) * jax.lax.rsqrt(var + cfg.eps) * p["k_i_norm"]
              + p["k_i_bias"])
        qi = mla_moe.rotary(qi, cfg.rope_theta)
        ki = mla_moe.rotary(ki[:, :, None, :], cfg.rope_theta)[:, :, 0]
        w = mm(u, p["ww_i"], False) * (hi ** -0.5 * di ** -0.5)
    return qi, ki, w


def _scores(qi, ki, w, dtype):
    """``I`` for the query rows given: qi [B, R, Hi, Di], w [B, R, Hi], ki
    [B, S, Di] -> [B, R, S] float32, causal or not: the heads' dots with
    operands in ``dtype``, relu, weights and the sum over the heads in
    float32."""
    with jax.named_scope("mv.lm.attn.index"):
        dots = jnp.einsum("brhd,bsd->bhrs", qi.astype(dtype),
                          ki.astype(dtype),
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots)
                       * w.transpose(0, 2, 1)[..., None], axis=1)


def _chunks(x, rows: int, axis: int = 1):
    """``x`` cut along ``axis`` into chunks of ``rows``, the chunks'
    number first."""
    n = x.shape[axis] // rows
    cut = x.reshape(x.shape[:axis] + (n, rows) + x.shape[axis + 1:])
    return jnp.moveaxis(cut, axis, 0)


def _chunk_rows(cfg, s: int) -> int:
    rows = min(cfg.index_chunk, s)
    if s % rows:
        raise ValueError(f"{s} positions do not divide into chunks of {rows}")
    return rows


def index_scores(u, p, cfg):
    """``I`` [B, S, S] float32 whole, made ``index_chunk`` rows at a time
    (what a test or a smoke reads; the layer itself never holds it)."""
    qi, ki, w = index_operands(u, p, cfg)
    rows = _chunk_rows(cfg, u.shape[1])
    out = jax.lax.map(
        lambda c: _scores(c[0], ki, c[1], cfg.compute_dtype),
        (_chunks(qi, rows), _chunks(w, rows)))
    return jnp.moveaxis(out, 0, 1).reshape(u.shape[0], u.shape[1], -1)


# ---------------------------------------------------------------------- #
# the selection
# ---------------------------------------------------------------------- #
def _ordered(x):
    """float32 -> uint32 with the same order (-0.0 as 0.0)."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, 0.0, x).astype(jnp.float32), jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def select(scores, cfg, first=0):
    """The selection of the query rows ``first .. first + R - 1``: scores
    [B, R, S] float32 -> int8 [B, R, S], 1 at the ``min(index_topk, t +
    1)`` positions ``s <= t`` with the largest scores, a tie to the lower
    ``s``: ``jax.lax.top_k``'s set exactly, found without a sort. A row's
    threshold, its k-th largest causal score, is the largest number that
    at least k of its causal scores reach: 32 counting passes over the
    scores' order-preserving integer keys, one bit of the threshold a
    pass. Ties AT the threshold are cut by a running count, in a branch
    that runs only where some row has more of them than it may take."""
    with jax.named_scope("mv.lm.attn.select"):
        r, s = scores.shape[1:]
        t = first + jnp.arange(r, dtype=jnp.int32)
        causal = jnp.arange(s, dtype=jnp.int32)[None, :] <= t[:, None]
        want = jnp.minimum(cfg.index_topk, t + 1)               # [R]
        # a key of 0 is below every score's (that would be a NaN's)
        keys = jnp.where(causal, _ordered(scores), jnp.uint32(0))

        def bit(n, threshold):
            trial = threshold | (jnp.uint32(1 << 31) >> n.astype(jnp.uint32))
            reach = jnp.sum(keys >= trial[..., None], -1, dtype=jnp.int32)
            return jnp.where(reach >= want, trial, threshold)

        threshold = jax.lax.fori_loop(
            0, 32, bit, jnp.zeros(scores.shape[:2], jnp.uint32))[..., None]
        above, at = keys > threshold, (keys == threshold) & causal
        room = want - jnp.sum(above, -1, dtype=jnp.int32)       # ties to take
        exact = jnp.all(jnp.sum(at, -1, dtype=jnp.int32) == room)
        chosen = jax.lax.cond(
            exact, lambda: above | at,
            lambda: above | (at & (jnp.cumsum(at, -1, dtype=jnp.int32)
                                   <= room[..., None])))
        return chosen.astype(jnp.int8)


def selection(qi, ki, w, cfg):
    """int8 [B, S, S] from the indexer's operands, ``index_chunk`` rows at
    a time: a chunk's scores live only as long as its selection is made."""
    b, s = ki.shape[:2]
    rows = _chunk_rows(cfg, s)

    def chunk(c):
        n, qi_c, w_c = c
        return select(_scores(qi_c, ki, w_c, cfg.compute_dtype), cfg,
                      n * rows)

    out = jax.lax.map(chunk, (jnp.arange(s // rows, dtype=jnp.int32),
                              _chunks(qi, rows), _chunks(w, rows)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s)


# ---------------------------------------------------------------------- #
# the indexer's term in the loss
# ---------------------------------------------------------------------- #
def _kl_chunks(qi, ki, w, q, k, chosen, cfg, lse=None):
    """The scan under :func:`index_loss`, ``index_chunk`` query rows at a
    time: the query heads' scores of the chunk over every key, their
    probabilities over the selected keys (a softmax, or with the core's
    own ``lse`` [B, H, S] ``exp(score - lse)`` in one pass) and the mean
    over the heads (``pbar``); the chunk's ``I`` again and its log-softmax
    over the same keys; the rows' KL; and, while both exist, what the
    term's gradient is made of: ``dI = (softmax(I) - pbar) / (B S)`` on
    the selected keys, taken back through the chunk's scores to ``qI`` and
    ``w`` (the chunk's rows) and to ``kI`` (summed in a carry). Returns
    (the term, (dqI, dkI, dw)).

    Everything from ``I`` on is two kernels' where the core is
    (``mla_moe.attn_core``: ``ops/index_kernels.term_chunk``, which walks
    the key tiles up to the chunk's diagonal and hands back the rows' KL
    and the three gradients, no [rows, S] array between them), and XLA's
    whole arrays elsewhere."""
    b, h, s, hd = q.shape
    hkv, rows, dt = k.shape[1], _chunk_rows(cfg, s), cfg.compute_dtype
    kernels = mla_moe.attn_core(cfg) == "flash"

    def body(carry, c):
        total, dki = carry
        n, qi_c, w_c, q_c, chosen_c, lse_c = c
        live = chosen_c != 0                                    # [B, R, S]
        dots = jnp.einsum(
            "bkgrd,bksd->bkgrs", q_c.reshape(b, hkv, h // hkv, rows, hd), k,
            preferred_element_type=jnp.float32) / hd ** 0.5
        if lse_c is None:
            prob = jax.nn.softmax(
                jnp.where(live[:, None, None], dots, _DEAD), -1)
        else:
            prob = jnp.exp(dots - lse_c.reshape(
                b, hkv, h // hkv, rows, 1))
        pbar = jnp.where(live, jnp.mean(prob, (1, 2)), 0.0)
        if kernels:
            with jax.named_scope("mv.lm.attn.index"):
                kl, dqi_c, dki, dw_c = index_kernels.term_chunk(
                    n, qi_c.astype(dt), ki.astype(dt), w_c, chosen_c, pbar,
                    dki)
            total = total + jnp.sum(kl) / (b * s)
            return (total, dki), (dqi_c, dw_c)
        index, back = jax.vjp(
            lambda qi_c, ki, w_c: _scores(qi_c, ki, w_c, dt), qi_c, ki, w_c)
        logq = jax.nn.log_softmax(jnp.where(live, index, _DEAD), -1)
        total = total + jnp.sum(
            jax.scipy.special.xlogy(pbar, pbar)
            - pbar * jnp.where(live, logq, 0.0)) / (b * s)
        d_index = jnp.where(live, jnp.exp(logq) - pbar, 0.0) / (b * s)
        dqi_c, dki_c, dw_c = back(d_index)
        return (total, dki + dki_c), (dqi_c, dw_c)

    xs = (jnp.arange(s // rows, dtype=jnp.int32), _chunks(qi, rows),
          _chunks(w, rows), _chunks(q, rows, 2), _chunks(chosen, rows),
          None if lse is None else _chunks(lse, rows, 2))
    (total, dki), out = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros(ki.shape, jnp.float32)),
        xs)
    whole = lambda x: jnp.moveaxis(x, 0, 1).reshape((b, s) + x.shape[3:])
    return total, (whole(out[0]), dki, whole(out[1]))


def index_loss(qi, ki, w, q, k, chosen, cfg, lse=None):
    """The indexer's term of one layer: ``mean_t sum_{s in S_t} pbar[t, s]
    (log pbar[t, s] - log softmax_{S_t}(I)[t, s])``, ``pbar`` the mean over
    the query heads of the core's probabilities (softmax over ``S_t`` of
    ``q . k / sqrt(head_dim)``; q [B, H, S, hd] and k [B, Hkv, S, hd] as
    the core takes them; ``lse``: the core's own log-sum-exp of those
    rows, where it hands one out), held constant. Its gradient reaches the
    indexer's operands alone.

    Term and gradients are made ONCE, together, from constants (a chunk's
    probabilities exist once, as ``mla_moe._chunked_ce``'s logits do), the
    gradients are named (:data:`KEEP_GRADS`: a rematerialised block keeps
    them, a float32 for every element of qI, kI and w, and so makes the
    query heads' scores no second time), and the term is handed on as
    itself plus, for each
    operand ``x`` with gradient ``g``, ``sum(g * (x - stop_gradient(x)))``:
    nothing in value, ``g`` in derivative."""
    still = jax.lax.stop_gradient
    with jax.named_scope("mv.lm.attn.target"):
        term, grads = _kl_chunks(
            still(qi), still(ki), still(w), still(q), still(k), chosen, cfg,
            None if lse is None else still(lse))
        for x, g in zip((qi, ki, w), grads):
            g = checkpoint_name(still(g), KEEP_GRADS)
            term = term + jnp.sum(g * (x - still(x)))
        return term


# ---------------------------------------------------------------------- #
# the attention
# ---------------------------------------------------------------------- #
def sparse_gqa(u, p, cfg):
    """Grouped-query attention over the selected keys on the normed input
    ``u`` [B, S, D] -> ([B, S, D] float32, the indexer's term). The
    indexer reads ``stop_gradient(u)``. The selection is named
    (:data:`KEEP_SELECTION`): a rematerialised block keeps it, and its
    backward pass reads the forward's."""
    s = u.shape[1]
    with jax.named_scope("mv.lm.attn"):
        q, k, v = gqa_moe.heads_of(u, p, cfg, "sparse")
        qi, ki, w = index_operands(jax.lax.stop_gradient(u), p, cfg)
        chosen = checkpoint_name(selection(qi, ki, w, cfg), KEEP_SELECTION)
        with jax.named_scope("mv.lm.attn.sparse"):
            if mla_moe.attn_core(cfg) == "flash":
                o, lse = flash_attention(q, k, v, True,
                                         *mla_moe.attn_blocks(cfg, s),
                                         select=chosen, with_lse=True)
            else:
                o, lse = mla_moe._xla_attention(q, k, v, None, chosen), None
        term = index_loss(qi, ki, w, q, k, chosen, cfg, lse)
        return gqa_moe.out_of(o, u, p, cfg), term


@jax.custom_vjp
def _witness(x, seen, slot):
    """``x``; the cotangent of ``slot`` is ``seen`` as the pass that made
    the residuals saw it."""
    return x


_witness.defvjp(lambda x, seen, slot: (x, seen),
                lambda seen, g: (g, jnp.zeros_like(seen), seen))


def selection_remade(u, p, cfg):
    """How many query rows' selections differ between a forward pass and
    the same forward remade under ``jax.checkpoint`` in ONE program, as a
    rematerialised block remakes it: int32 [B]. A row is told by two sums
    over its selected positions (``s // 4096`` and ``s % 4096``: exact in
    float32 up to 2,048 keys of 16,384)."""
    s = u.shape[1]
    at = jnp.arange(s, dtype=jnp.int32)

    def seen(u):
        chosen = selection(*index_operands(u, p, cfg), cfg)
        return jnp.stack([jnp.sum(chosen * (at // 4096), -1),
                          jnp.sum(chosen * (at % 4096), -1)],
                         -1).astype(jnp.float32)

    def run(u, slot):
        first = seen(u)
        return jnp.sum(_witness(jnp.zeros(()), first, slot)), first

    slot = jnp.zeros(u.shape[:2] + (2,), jnp.float32)
    (_, first), again = jax.value_and_grad(
        jax.checkpoint(run), argnums=1, has_aux=True)(u, slot)
    return jnp.sum(jnp.any(first != again, -1), -1).astype(jnp.int32)


def layer_selections(params, tokens, cfg):
    """Every layer's selection for ``tokens`` [B, S], int8 [layers, B, S,
    S], from a plain forward pass over the parameters by name: what a
    comparison hands its reference, so that the two attend over the same
    keys."""
    x = mla_moe._embed(params, tokens, cfg)
    chosen = []
    for layer in cfg.layers():
        p = mla_moe._sub(params, layer.name)
        u = mla_moe.rms_norm(x, p["attn_norm"], cfg.eps)
        chosen.append(selection(*index_operands(u, p, cfg), cfg))
        x, _ = mla_moe._run_block(x, p, layer, None, cfg, remat=False)
    return jnp.stack(chosen)
