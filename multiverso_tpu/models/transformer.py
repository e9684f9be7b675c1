"""Decoder-only transformer LM with context-parallel (long-context) training.

The reference framework predates transformers (SURVEY §5: long-context
absent), but long context is first-class here: this model family trains with
**ring attention** or **Ulysses all-to-all** sequence parallelism
(parallel/ring.py) over a ``(dp, sp)`` mesh — batch data-parallel on ``dp``,
sequence context-parallel on ``sp`` — so sequence length scales with the
number of chips. Everything is a pure function designed for one jitted SPMD
step: params replicated (psum'd grads on dp = the BSP merge the reference's
SyncServer provided, ref src/server.cpp:68-222), activations sharded
``P(dp, sp)``, attention collectives riding ICI.

TPU notes: matmuls are einsum-batched for the MXU; ``cfg.dtype=bfloat16``
keeps activations in bf16 while the loss/softmax runs in f32; no
data-dependent Python control flow — the layer stack is a ``lax.scan`` over
stacked per-layer params so XLA compiles ONE layer body regardless of depth.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.parallel import ring


class TransformerConfig(NamedTuple):
    vocab_size: int = 256
    dim: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_seq: int = 512
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    attn: str = "ring"   # "ring" | "zigzag" | "ulysses" | "local" | "flash"
    seq_axis: Optional[str] = None   # mesh axis for sequence parallelism
    batch_axis: Optional[str] = None  # mesh axis for data parallelism
    tp_axis: Optional[str] = None    # mesh axis for tensor parallelism
    # rematerialize each layer in backward (jax.checkpoint on the scanned
    # layer body): stores only the L layer-boundary activations and
    # recomputes one layer's internals at a time — trades ~1/3 more FLOPs
    # for the dominant per-layer activation memory; the HBM lever for deep
    # stacks / long sequences
    remat: bool = False
    # interleaved pipeline schedule: virtual chunks per pp device (see
    # parallel/pipeline.pipeline_apply_interleaved); 1 = plain GPipe
    pp_chunks: int = 1
    # expert-parallel MoE MLPs (parallel/moe.py): 0 = dense MLP
    moe_experts: int = 0
    moe_axis: str = "ep"             # mesh axis the experts shard over
    moe_top_k: int = 1
    moe_capacity_factor: float = 2.0
    moe_aux_coef: float = 0.01


def init_params(cfg: TransformerConfig, seed: int = 0) -> Dict[str, Any]:
    """Stacked-per-layer parameter pytree (leading dim = layer, for scan)."""
    rng = np.random.default_rng(seed)
    d, h, L = cfg.dim, cfg.num_heads, cfg.num_layers
    m = cfg.mlp_ratio * d

    def norm(*shape, scale):
        return jnp.asarray(rng.normal(0, scale, shape), cfg.dtype)

    s = 1.0 / np.sqrt(d)
    layers = {
        "wqkv": norm(L, d, 3 * d, scale=s),
        "wo": norm(L, d, d, scale=s / np.sqrt(2 * L)),
        "ln1": jnp.ones((L, d), cfg.dtype),
        "ln2": jnp.ones((L, d), cfg.dtype),
    }
    if cfg.moe_experts:
        e = cfg.moe_experts
        layers["moe_w1"] = norm(L, e, d, m, scale=s)
        layers["moe_w2"] = norm(L, e, m, d,
                                scale=np.sqrt(1.0 / m) / np.sqrt(2 * L))
        layers["moe_router"] = norm(L, d, e, scale=s)
    else:
        layers["w1"] = norm(L, d, m, scale=s)
        layers["w2"] = norm(L, m, d,
                            scale=np.sqrt(1.0 / m) / np.sqrt(2 * L))
    return {
        "embed": norm(cfg.vocab_size, d, scale=0.02),
        "pos": norm(cfg.max_seq, d, scale=0.02),
        "layers": layers,
        "ln_f": jnp.ones((d,), cfg.dtype),
    }


def _rmsnorm(x, g):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6).astype(x.dtype)) * g


def _attention(cfg: TransformerConfig, q, k, v):
    if cfg.attn == "local":
        # global-level attention; with tp_axis set GSPMD shards the
        # (embarrassingly parallel) head dim itself
        return ring.reference_attention(q, k, v, causal=True)
    if cfg.attn == "flash":
        # fused Pallas kernel (ops/attention_kernels.py); the sequence stays
        # whole per chip — use attn='ring' to shard S. With dp/tp axes set
        # the kernel is shard_mapped so each chip runs it on its own
        # batch/head slice (a bare pallas_call has no GSPMD partitioning
        # rule, so jit alone would replicate the global batch per chip).
        if cfg.seq_axis is not None:
            raise ValueError("attn='flash' is the single-chip fused kernel; "
                             "use attn='ring' for sequence parallelism")
        from multiverso_tpu.ops.attention_kernels import flash_attention
        # block size: biggest divisor of S up to 512 — measured on the
        # 472M LM bench, 512x512 blocks cut the whole-model step ~25-45%
        # vs 128x128 (fewer grid sweeps re-streaming K/V through VMEM)
        blk = next((bsz for bsz in (512, 256, 128)
                    if q.shape[2] % bsz == 0), 128)
        if cfg.batch_axis is None and cfg.tp_axis is None:
            return flash_attention(q, k, v, True, blk, blk)
        from jax.sharding import PartitionSpec as P

        from multiverso_tpu.zoo import Zoo
        spec = P(cfg.batch_axis, cfg.tp_axis, None, None)
        return jax.shard_map(
            lambda q, k, v: flash_attention(q, k, v, True, blk, blk),
            mesh=Zoo.get().mesh(), in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False)(q, k, v)
    if cfg.attn == "ring":
        return ring.ring_attention(q, k, v, axis_name=cfg.seq_axis,
                                   causal=True, batch_axis=cfg.batch_axis,
                                   head_axis=cfg.tp_axis)
    if cfg.attn == "zigzag":
        # balanced causal ring; activations are in zigzag sequence order
        # end to end (shard_batch permutes tokens, forward permutes pos)
        return ring.zigzag_ring_attention(
            q, k, v, axis_name=cfg.seq_axis, batch_axis=cfg.batch_axis,
            head_axis=cfg.tp_axis)
    if cfg.tp_axis is not None:
        raise ValueError("ulysses attention reshards heads itself; combine "
                         "tp_axis with attn='ring' or 'local' instead")
    return ring.ulysses_attention(q, k, v, axis_name=cfg.seq_axis,
                                  causal=True, batch_axis=cfg.batch_axis)


def shard_params_moe(params: Dict[str, Any], cfg: TransformerConfig,
                     mesh=None) -> Dict[str, Any]:
    """Place params with expert weights sharded over ``cfg.moe_axis`` (the
    [L, E, ...] stacks split on E) and everything else replicated."""
    from jax.sharding import PartitionSpec as P

    from multiverso_tpu.parallel import tp as tp_lib
    if not cfg.moe_experts:
        raise ValueError("shard_params_moe needs cfg.moe_experts > 0")
    ax = cfg.moe_axis
    rules = {
        "embed": P(), "pos": P(),
        "layers": {
            "wqkv": P(), "wo": P(), "ln1": P(), "ln2": P(),
            "moe_w1": P(None, ax, None, None),
            "moe_w2": P(None, ax, None, None),
            "moe_router": P(),
        },
        "ln_f": P(),
    }
    return tp_lib.shard_params(params, rules, mesh)


def shard_params_fsdp(params: Dict[str, Any], cfg: TransformerConfig,
                      mesh=None, axis: str = "fsdp") -> Dict[str, Any]:
    """Place params FSDP-sharded over ``axis`` (see
    parallel/tp.transformer_fsdp_rules): each chip stores 1/n of every
    large tensor; combine with ``batch_axis=axis`` on the config so the
    same chips compute data-parallel. Works for dense and MoE param trees
    (the signature matches shard_params_tp/shard_params_moe)."""
    from multiverso_tpu.parallel import tp as tp_lib
    return tp_lib.shard_params(
        params, tp_lib.transformer_fsdp_rules(axis,
                                              moe=bool(cfg.moe_experts)),
        mesh)


def shard_params_tp(params: Dict[str, Any], cfg: TransformerConfig,
                    mesh=None) -> Dict[str, Any]:
    """Place params Megatron-sharded over ``cfg.tp_axis`` (see parallel/tp)."""
    from multiverso_tpu.parallel import tp as tp_lib
    if cfg.tp_axis is None:
        raise ValueError("shard_params_tp needs cfg.tp_axis set; with no "
                         "tensor-parallel axis it would silently replicate "
                         "every parameter")
    return tp_lib.shard_params(
        params, tp_lib.transformer_tp_rules(cfg.tp_axis), mesh)


def _make_layer_fn(cfg: TransformerConfig, tp_hint, heads_spec, hidden_spec,
                   mcfg):
    """One transformer block as a scan body ``(x, aux_sum), p -> ...``.

    Shared by :func:`forward_with_aux` (scan over the whole stack) and
    :func:`make_pp_train_step` (scan over one pipeline stage's slice of the
    stack). Shapes are taken from the activation so the same body serves
    full batches and pipeline microbatches.
    """
    h, d = cfg.num_heads, cfg.dim
    hd = d // h
    if cfg.moe_experts:
        from multiverso_tpu.parallel import moe as moe_lib

    def layer(carry, p):
        x, aux_sum = carry
        b, s = x.shape[0], x.shape[1]
        y = _rmsnorm(x, p["ln1"])
        qkv = jnp.einsum("bsd,de->bse", y, p["wqkv"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        # [B, S, D] -> [B, H, S, hd]; tp shards the head dim
        split = lambda t: tp_hint(
            t.reshape(b, s, h, hd).transpose(0, 2, 1, 3), heads_spec)
        o = _attention(cfg, split(q), split(k), split(v))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + jnp.einsum("bsd,de->bse", o, p["wo"])
        y = _rmsnorm(x, p["ln2"])
        if cfg.moe_experts:
            mlp, aux, _ = moe_lib.moe_layer(
                y, {"w1": p["moe_w1"], "w2": p["moe_w2"],
                    "router": p["moe_router"]},
                mcfg, batch_axis=cfg.batch_axis)
            return (x + mlp, aux_sum + aux), None
        # tp shards the MLP hidden dim (column-parallel w1, row-parallel w2)
        y = tp_hint(jnp.einsum("bsd,dm->bsm", y, p["w1"]), hidden_spec)
        y = jax.nn.gelu(y)
        return (x + jnp.einsum("bsm,md->bsd", y, p["w2"]), aux_sum), None

    return layer


def forward(params: Dict[str, Any], tokens: jax.Array,
            cfg: TransformerConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] (MoE aux loss discarded; training
    uses :func:`loss_fn`, which keeps it)."""
    return forward_with_aux(params, tokens, cfg)[0]


def forward_with_aux(params: Dict[str, Any], tokens: jax.Array,
                     cfg: TransformerConfig):
    """tokens [B, S] -> (logits [B, S, V], moe aux-loss scalar). Written at
    the global-logical level; the attention call shard_maps over the
    sequence axis and MoE MLPs all_to_all tokens over ``moe_axis``."""
    s = tokens.shape[1]
    d = cfg.dim

    if cfg.moe_experts:
        if cfg.seq_axis is not None or cfg.tp_axis is not None:
            raise ValueError(
                "MoE MLPs shard tokens over moe_axis; combine with "
                "batch_axis only (seq_axis/tp_axis are not supported "
                "together with moe_experts yet)")
        from multiverso_tpu.parallel import moe as moe_lib
        mcfg = moe_lib.MoEConfig(
            num_experts=cfg.moe_experts, dim=d, hidden=cfg.mlp_ratio * d,
            capacity_factor=cfg.moe_capacity_factor,
            axis=cfg.moe_axis, top_k=cfg.moe_top_k)

    if cfg.tp_axis is not None or cfg.batch_axis is not None:
        # Constrain activations whenever ANY mesh axis is in play — not
        # just tp. Without the batch-axis pin, the scan-over-layers
        # backward lets GSPMD invent hybrid layouts for the saved
        # attention residuals and fall back to "involuntary full
        # rematerialization" (replicate-then-reshard) on the dp/fsdp
        # mesh — a silent cross-chip perf tax on every layer.
        from jax.sharding import PartitionSpec as P

        from multiverso_tpu.parallel import tp as tp_lib
        heads_spec = P(cfg.batch_axis, cfg.tp_axis, cfg.seq_axis, None)
        hidden_spec = P(cfg.batch_axis, cfg.seq_axis, cfg.tp_axis)
        tp_hint = lambda t, spec: tp_lib.constrain(t, spec)
    else:
        tp_hint = lambda t, spec: t
        heads_spec = hidden_spec = None

    if cfg.attn == "zigzag":
        # tokens arrive zigzag-permuted (shard_batch); position embeddings
        # must follow the same permutation so each token keeps its true
        # global position
        from multiverso_tpu.zoo import Zoo as _Zoo
        zmesh = _Zoo.get().mesh()
        zax = cfg.seq_axis or _Zoo.get().shard_axis()
        zperm = ring.zigzag_shard_ids(s, zmesh.shape[zax])
        pos = params["pos"][zperm]
    else:
        pos = params["pos"][:s]
    x = params["embed"][tokens] + pos[None]

    layer = _make_layer_fn(cfg, tp_hint, heads_spec, hidden_spec,
                           mcfg if cfg.moe_experts else None)

    if cfg.remat:
        # prevent_cse=False: safe (and recommended) under lax.scan, avoids
        # optimization barriers that would inhibit in-layer fusion
        layer = jax.checkpoint(layer, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(layer, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    return _lm_head(x, params["ln_f"], params["embed"]), aux


def _lm_head(x, ln_f, embed):
    """Final norm + tied-embedding projection: [B, S, D] -> [B, S, V]."""
    return jnp.einsum("bsd,vd->bsv", _rmsnorm(x, ln_f), embed)


def _nll(logits, targets, mask=None):
    """Mean next-token cross-entropy in f32; ``mask`` weights positions.

    Written as logsumexp - target_logit rather than log_softmax + gather:
    the casts fuse into the reductions so the [B, S, V] f32 log-prob
    tensor (256 MB at the 472M bench config) is never materialized —
    measured ~1 ms/step off the 472M LM train step, loss equal to f32
    association order. The max shift is a constant offset of both terms,
    so it carries no gradient (stop_gradient skips its backward)."""
    lg32 = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lg32, -1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lg32 - m), -1)) + m[..., 0]
    tl = jnp.take_along_axis(lg32, targets[..., None], -1)[..., 0]
    nll = lse - tl
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def loss_fn(params, tokens, targets, cfg: TransformerConfig,
            mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross-entropy (f32) plus ``moe_aux_coef`` times the
    MoE load-balance loss when MoE layers are enabled. ``targets`` is
    tokens shifted by one on the host, so sequence shards never need a halo
    exchange; ``mask`` zeroes padding/terminal positions and is given in
    the ORIGINAL sequence order — with ``attn="zigzag"`` it is permuted
    here to match the zigzag-ordered nll."""
    if mask is not None and cfg.attn == "zigzag":
        from multiverso_tpu.zoo import Zoo as _Zoo
        ax = cfg.seq_axis or _Zoo.get().shard_axis()
        perm = ring.zigzag_shard_ids(mask.shape[1],
                                     _Zoo.get().mesh().shape[ax])
        mask = mask[:, perm]
    logits, aux = forward_with_aux(params, tokens, cfg)
    nll = _nll(logits, targets, mask)
    if cfg.moe_experts:
        nll = nll + cfg.moe_aux_coef * aux
    return nll


def make_train_step(cfg: TransformerConfig, learning_rate: float = 1e-2):
    """Plain-SGD jittable step (params, tokens, targets) -> (params, loss).

    For the parameter-server training mode, keep params in a table instead:
    compute ``grads`` with ``jax.grad(loss_fn)`` and push ``-lr * grads``
    through ``sharedvar.SharedPytree.sync`` (the delta-sync ASGD surface) or
    ``Table.functional_add`` inside your own step. For stateful optimizers
    use :func:`make_optax_train_step`.

    Jit with ``donate_argnums=(0,)`` when your loop rebinds ``params``
    every step: the update then writes the weight buffers in place
    (measured ~0.6 ms/step on the 472M bench config) — but the ORIGINAL
    params object is consumed, so leave donation off if you keep it.
    """

    def step(params, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets,
                                                  cfg)
        params = jax.tree.map(
            lambda p, g: p - jnp.asarray(learning_rate, p.dtype) * g,
            params, grads)
        return params, loss

    return step


def make_optax_train_step(cfg: TransformerConfig, optimizer):
    """Jittable step for any optax GradientTransformation:
    ``(params, opt_state, tokens, targets) -> (params, opt_state, loss)``.
    Initialize with ``optimizer.init(params)`` — under FSDP/TP the
    optimizer state inherits each param's sharding (ZeRO for free)."""
    import optax

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets,
                                                  cfg)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def _qkv_head_perm(d: int, h: int) -> np.ndarray:
    """Column permutation taking wqkv's [q_all | k_all | v_all] layout to
    head-grouped [(q,k,v) of head 0 | (q,k,v) of head 1 | ...].

    Needed for tensor parallelism inside pipeline stages: sharding the
    3d output dim contiguously must hand each tp member whole heads (the
    Megatron interleaved-qkv trick)."""
    hd = d // h
    return np.asarray([c * d + g * hd + i
                       for g in range(h) for c in range(3)
                       for i in range(hd)], dtype=np.int64)


def stack_pp_params(params: Dict[str, Any], cfg: TransformerConfig,
                    n_stages: int, tp: Optional[bool] = None,
                    pp_chunks: Optional[int] = None) -> Dict[str, Any]:
    """Regroup the [L, ...] layer stack as [n_stages, L/n_stages, ...].

    The pipeline places stage s's slice on device s of the ``pp`` axis
    (parallel/pipeline.py contract: leading dim = n_stages); each stage
    scans its local L/n_stages layers per tick. When the config has a
    ``tp_axis`` (default ``tp=None`` reads it from ``cfg``, so the same
    config drives stacking, sharding and the step consistently) the wqkv
    columns are permuted head-grouped (see :func:`_qkv_head_perm`) so a
    contiguous tp shard owns whole heads. ``pp_chunks > 1`` produces the
    [n_stages, pp_chunks, per, ...] layout of the interleaved schedule
    (pipeline.pipeline_apply_interleaved).
    """
    if tp is None:
        tp = cfg.tp_axis is not None
    if pp_chunks is None:
        pp_chunks = cfg.pp_chunks
    L = cfg.num_layers
    groups = n_stages * pp_chunks
    if L % groups:
        raise ValueError(f"num_layers={L} not divisible by "
                         f"n_stages*pp_chunks={groups}")
    per = L // groups
    layers = dict(params["layers"])
    if tp:
        layers["wqkv"] = layers["wqkv"][
            ..., _qkv_head_perm(cfg.dim, cfg.num_heads)]
    out = {k: v for k, v in params.items() if k != "layers"}
    if pp_chunks > 1:
        # interleaved layout: global group g -> (device g % S, chunk g // S)
        out["stages"] = jax.tree.map(
            lambda p: p.reshape(pp_chunks, n_stages, per, *p.shape[1:])
                       .swapaxes(0, 1), layers)
    else:
        out["stages"] = jax.tree.map(
            lambda p: p.reshape(n_stages, per, *p.shape[1:]), layers)
    return out


def unstack_pp_params(stacked: Dict[str, Any],
                      cfg: Optional[TransformerConfig] = None,
                      tp: Optional[bool] = None,
                      pp_chunks: Optional[int] = None) -> Dict[str, Any]:
    """Inverse of :func:`stack_pp_params` (for eval/decode/checkpoint
    interop with the plain [L, ...] layout). Pass the same ``cfg`` (and
    ``pp_chunks``) used at stack time so the head-grouped qkv layout and
    the interleaved chunk layout are undone (``tp`` defaults from
    ``cfg.tp_axis`` exactly like :func:`stack_pp_params`)."""
    if tp is None:
        tp = cfg is not None and cfg.tp_axis is not None
    if pp_chunks is None:
        pp_chunks = cfg.pp_chunks if cfg is not None else 1
    out = {k: v for k, v in stacked.items() if k != "stages"}
    if pp_chunks > 1:
        layers = jax.tree.map(
            lambda p: np.asarray(p).swapaxes(0, 1).reshape(
                p.shape[0] * p.shape[1] * p.shape[2], *p.shape[3:]),
            stacked["stages"])
    else:
        layers = jax.tree.map(
            lambda p: np.asarray(p).reshape(p.shape[0] * p.shape[1],
                                            *p.shape[2:]),
            stacked["stages"])
    if tp:
        if cfg is None:
            raise ValueError("unstack_pp_params(tp=True) needs cfg to "
                             "invert the head-grouped qkv layout")
        perm = _qkv_head_perm(cfg.dim, cfg.num_heads)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.size)
        layers["wqkv"] = layers["wqkv"][..., inv]
    out["layers"] = layers
    return out


def _pp_stage_specs(cfg: TransformerConfig, axis: str,
                    chunked: bool = False):
    """PartitionSpecs for the stages subtree under pp x tp: weights split
    over ``cfg.tp_axis`` on the Megatron dims (qkv/w1 output-sharded,
    wo/w2 input-sharded), norms pp-only. ``chunked``: leaves carry the
    interleaved schedule's extra [n_chunks] dim after the stage dim."""
    from jax.sharding import PartitionSpec as P
    t = cfg.tp_axis
    c = (None,) if chunked else ()
    return {
        "wqkv": P(axis, *c, None, None, t),
        "wo": P(axis, *c, None, t, None),
        "ln1": P(axis), "ln2": P(axis),
        "w1": P(axis, *c, None, None, t),
        "w2": P(axis, *c, None, t, None),
    }


def shard_params_pp(stacked: Dict[str, Any], mesh=None,
                    axis: str = "pp",
                    cfg: Optional[TransformerConfig] = None
                    ) -> Dict[str, Any]:
    """Place a :func:`stack_pp_params` tree: stages split over ``axis``
    (one stage's layers per device, via pipeline.shard_stages),
    embeddings/final-norm replicated. Pass ``cfg`` with ``tp_axis`` set to
    additionally shard each stage's weights tensor-parallel
    (:func:`_pp_stage_specs`)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from multiverso_tpu.parallel import pipeline as pp_lib
    from multiverso_tpu.zoo import Zoo
    mesh = mesh or Zoo.get().mesh()
    out = {k: jax.tree.map(
        lambda p: jax.device_put(p, NamedSharding(mesh, P())), v)
        for k, v in stacked.items() if k != "stages"}
    if cfg is not None and cfg.tp_axis is not None:
        # derive the chunked layout from the actual leaf rank (a too-short
        # spec against a [S, V, ...] leaf would silently shard the wrong
        # dim over tp; rank is the ground truth, not cfg.pp_chunks)
        chunked = stacked["stages"]["wqkv"].ndim == 5
        specs = _pp_stage_specs(cfg, axis, chunked=chunked)
        out["stages"] = {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in stacked["stages"].items()}
    else:
        out["stages"] = pp_lib.shard_stages(stacked["stages"], axis=axis,
                                            mesh=mesh)
    return out


def _make_tp_layer_fn(cfg: TransformerConfig, tp_axis: str, n_tp: int):
    """Transformer block with EXPLICIT Megatron tensor parallelism, for use
    inside an enclosing shard_map (the pipeline body): weights arrive as
    tp-local shards (head-grouped qkv — whole heads per member; w1
    column-, wo/w2 row-sharded) and each sublayer ends in ONE
    ``lax.psum`` over ``tp_axis`` — the column->row pairing of
    parallel/tp.py spelled out at the collective level because GSPMD hints
    cannot cross a manual shard_map boundary."""
    h, d = cfg.num_heads, cfg.dim
    hd = d // h
    h_loc = h // n_tp

    def layer(carry, p):
        x, aux_sum = carry
        b, s = x.shape[0], x.shape[1]
        y = _rmsnorm(x, p["ln1"])
        qkv = jnp.einsum("bsd,de->bse", y, p["wqkv"])  # [b,s,3d/t] by head
        qkv = qkv.reshape(b, s, h_loc, 3, hd).transpose(0, 2, 3, 1, 4)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        o = _attention(cfg, q, k, v)                   # local heads
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h_loc * hd)
        x = x + jax.lax.psum(
            jnp.einsum("bsd,de->bse", o, p["wo"]), tp_axis)
        y = _rmsnorm(x, p["ln2"])
        y = jax.nn.gelu(jnp.einsum("bsd,dm->bsm", y, p["w1"]))
        x = x + jax.lax.psum(
            jnp.einsum("bsm,md->bsd", y, p["w2"]), tp_axis)
        return (x, aux_sum), None

    return layer


def make_pp_loss_fn(cfg: TransformerConfig, n_micro: int, axis: str = "pp",
                    mesh=None, pp_chunks: Optional[int] = None):
    """Pipelined LM loss ``loss(stacked, tokens, targets, mask=None)``
    (``mask`` weights positions like :func:`loss_fn`) over the
    ``axis`` mesh dimension (GPipe microbatch ring, parallel/pipeline.py).

    The reference's "pipeline" is communication/compute double-buffering
    (SURVEY §2.10 — `async_buffer.h`, ps_model.cpp GetPipelineTable); layer
    pipelining is the strategy the PS design could not express. Here the
    stack runs through parallel/pipeline.py's single-scan microbatch ring
    and ``jax.grad`` differentiates through the ppermute ring, which
    reverses the schedule automatically: forward fills stage s at tick t,
    backward drains it in the transposed order — the GPipe fill/drain
    schedule without a hand-written backward pass.

    Composition: combine with ``cfg.batch_axis`` on a ``(dp, pp)`` mesh for
    data-parallel pipelines; set ``cfg.tp_axis`` on a ``(dp, pp, tp)`` mesh
    to additionally run Megatron tensor parallelism INSIDE each stage
    (explicit psum layer, :func:`_make_tp_layer_fn`; stack with ``tp=True``
    and shard with ``cfg=`` so qkv is head-grouped); ``cfg.remat=True``
    recomputes each layer in backward (the standard GPipe memory trade).
    Params must be :func:`stack_pp_params` + :func:`shard_params_pp`.
    """
    from multiverso_tpu.parallel import pipeline as pp_lib
    from multiverso_tpu.zoo import Zoo
    mesh = mesh or Zoo.get().mesh()
    if pp_chunks is None:
        pp_chunks = cfg.pp_chunks
    if cfg.moe_experts or cfg.seq_axis is not None:
        raise ValueError("the pp step pipelines the dense stack; sp/moe "
                         "combinations are separate strategies (see "
                         "seq_axis / moe_experts)")
    if cfg.attn not in ("local", "flash"):
        raise ValueError("pipeline stages attend within a microbatch that "
                         "is fully local to the stage; use attn='local' "
                         "(or 'flash' for the fused per-chip kernel)")
    n_stages = mesh.shape[axis]
    if cfg.num_layers % (n_stages * pp_chunks):
        raise ValueError(f"num_layers={cfg.num_layers} not divisible by "
                         f"pp={n_stages} x pp_chunks={pp_chunks}")
    if pp_chunks > 1 and n_micro != n_stages:
        raise ValueError(f"the interleaved schedule runs a fixed "
                         f"n_micro == pp ({n_stages}); got "
                         f"n_micro={n_micro}")
    # inside the pipeline body activations are stage-local, so the layer is
    # built without global sharding hints (flash lowers to the direct
    # kernel call rather than its own shard_map)
    pcfg = cfg._replace(batch_axis=None, tp_axis=None, seq_axis=None)
    param_specs = None
    if cfg.tp_axis is not None:
        n_tp = mesh.shape[cfg.tp_axis]
        if cfg.num_heads % n_tp or (cfg.mlp_ratio * cfg.dim) % n_tp:
            raise ValueError(
                f"num_heads={cfg.num_heads} and mlp hidden "
                f"{cfg.mlp_ratio * cfg.dim} must both be divisible by "
                f"tp={n_tp}")
        layer = _make_tp_layer_fn(pcfg, cfg.tp_axis, n_tp)
        param_specs = _pp_stage_specs(cfg, axis, chunked=pp_chunks > 1)
    else:
        layer = _make_layer_fn(pcfg, lambda t, spec: t, None, None, None)
    if cfg.remat:
        layer = jax.checkpoint(layer, prevent_cse=False)

    def stage_fn(p, x):
        (x, _), _ = jax.lax.scan(layer, (x, jnp.zeros((), jnp.float32)), p)
        return x

    def loss(stacked, tokens, targets, mask=None):
        s = tokens.shape[1]
        x = stacked["embed"][tokens] + stacked["pos"][:s][None]
        if pp_chunks > 1:
            x = pp_lib.pipeline_apply_interleaved(
                stage_fn, stacked["stages"], x, axis=axis, mesh=mesh,
                batch_axis=cfg.batch_axis, param_specs=param_specs)
        else:
            x = pp_lib.pipeline_apply(stage_fn, stacked["stages"], x,
                                      n_micro, axis=axis, mesh=mesh,
                                      batch_axis=cfg.batch_axis,
                                      param_specs=param_specs)
        return _nll(_lm_head(x, stacked["ln_f"], stacked["embed"]),
                    targets, mask)

    return loss


def make_pp_train_step(cfg: TransformerConfig, n_micro: int,
                       learning_rate: float = 1e-2, axis: str = "pp",
                       mesh=None, pp_chunks: Optional[int] = None):
    """Plain-SGD pipeline-parallel LM train step (see
    :func:`make_pp_loss_fn` for the pipelining semantics).
    Returns ``step(stacked, tokens, targets, mask=None) ->
    (stacked, loss)``; ``mask`` weights positions like :func:`loss_fn`."""
    loss = make_pp_loss_fn(cfg, n_micro, axis, mesh, pp_chunks)

    def step(stacked, tokens, targets, mask=None):
        loss_v, grads = jax.value_and_grad(loss)(stacked, tokens, targets,
                                                 mask)
        stacked = jax.tree.map(
            lambda p, g: p - jnp.asarray(learning_rate, p.dtype) * g,
            stacked, grads)
        return stacked, loss_v

    return step


def make_pp_optax_train_step(cfg: TransformerConfig, n_micro: int,
                             optimizer, axis: str = "pp", mesh=None,
                             pp_chunks: Optional[int] = None):
    """Pipelined step for any optax GradientTransformation:
    ``(stacked, opt_state, tokens, targets, mask=None) ->
    (stacked, opt_state, loss)``.
    Initialize with ``optimizer.init(stacked)`` — optimizer moments inherit
    each stage's placement, so Adam state for stage s lives only on device
    s of the ``pp`` axis (the reference pays per-shard updater state the
    same way, ref adagrad_updater.h:19)."""
    import optax

    loss = make_pp_loss_fn(cfg, n_micro, axis, mesh, pp_chunks)

    def step(stacked, opt_state, tokens, targets, mask=None):
        loss_v, grads = jax.value_and_grad(loss)(stacked, tokens, targets,
                                                 mask)
        updates, opt_state = optimizer.update(grads, opt_state, stacked)
        return optax.apply_updates(stacked, updates), opt_state, loss_v

    return step


def _is_q(x):
    from multiverso_tpu.ops.quantization import QuantizedTensor
    return isinstance(x, QuantizedTensor)


def _emb_rows(e, idx):
    """Embedding-row lookup without materializing the full table."""
    if _is_q(e):
        want = (e.q.shape[0],) + (1,) * (e.q.ndim - 1)
        if e.scale.shape != want:
            # out-of-bounds gathers clamp silently, so a wrong scale
            # layout would corrupt decoding without any error
            raise ValueError(
                f"embedding QuantizedTensor needs per-row scales "
                f"{want}, got {e.scale.shape}; quantize embeddings "
                "with keep_axes=(0,) (quantize_lm_params does)")
        return e.q[idx].astype(jnp.float32) * e.scale[idx]
    return e[idx]


def _tied_logits(x, e):
    """[.., D] @ tied embedding -> [.., V] f32 logits. For int8 embeddings
    the int8 operand feeds the dot directly (the convert fuses) and the
    per-row scale lands on the small logits output — the [V, D] f32 table
    is never materialized."""
    if _is_q(e):
        logits = jnp.einsum("bd,vd->bv", x, e.q.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return logits * e.scale[:, 0][None]
    return jnp.einsum("bd,vd->bv", x, e,
                      preferred_element_type=jnp.float32)


def _moe_exact(y2d, pl, cfg: TransformerConfig, chunk: int = 64):
    """Exact top-k MoE for [T, D] tokens, position-chunked so the per-token
    expert-weight gather stays O(chunk * K * D * M) instead of
    O(T * K * D * M) (a long prompt would otherwise materialize a private
    copy of its experts' weights per position)."""
    from multiverso_tpu.parallel.moe import top_k_gates
    t, d = y2d.shape
    c = min(t, chunk)
    pad = (-t) % c
    if pad:
        y2d = jnp.concatenate(
            [y2d, jnp.zeros((pad, d), y2d.dtype)])

    def one_chunk(yc):
        probs = jax.nn.softmax(
            (yc @ pl["moe_router"]).astype(jnp.float32), -1)
        gates, topi = top_k_gates(probs, cfg.moe_top_k)
        w1_sel = pl["moe_w1"][topi]                  # [C, K, D, M]
        w2_sel = pl["moe_w2"][topi]
        hmid = jax.nn.gelu(jnp.einsum("td,tkdm->tkm", yc, w1_sel))
        out = jnp.einsum("tkm,tkmd->tkd", hmid, w2_sel)
        return (out * gates[..., None].astype(out.dtype)).sum(1)

    mlp = jax.lax.map(one_chunk, y2d.reshape(-1, c, d)).reshape(-1, d)
    return mlp[:t]


def _decode_step(params, caches, tok, t, cfg: TransformerConfig):
    """One token through all layers, reading/updating the KV cache.
    caches: dict of [L, B, H, max_seq, hd]; tok [B]; t scalar position.
    Returns (caches, logits [B, V] f32). Accepts int8 quantized trees
    (weights dequantize one layer at a time)."""
    from multiverso_tpu.ops.quantization import maybe_dequantize

    b = tok.shape[0]
    h, d = cfg.num_heads, cfg.dim
    hd = d // h
    neg_inf = jnp.asarray(-1e30, jnp.float32)
    x = (_emb_rows(params["embed"], tok)
         + _emb_rows(params["pos"], t)).astype(cfg.dtype)    # [B, D]

    def layer(carry, inputs):
        x, = carry
        pl, ck, cv = inputs
        pl = jax.tree.map(lambda l: maybe_dequantize(l, cfg.dtype),
                          pl, is_leaf=_is_q)
        y = _rmsnorm(x, pl["ln1"])
        qkv = y @ pl["wqkv"]                             # [B, 3D]
        q, kk, vv = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, h, hd)
        kk = kk.reshape(b, h, hd)
        vv = vv.reshape(b, h, hd)
        ck = jax.lax.dynamic_update_slice_in_dim(
            ck, kk[:, :, None], t, axis=2)               # [B,H,max,hd]
        cv = jax.lax.dynamic_update_slice_in_dim(
            cv, vv[:, :, None], t, axis=2)
        # f32 score/output accumulation, matching reference_attention's
        # preferred_element_type so bf16 greedy decode agrees with
        # forward()
        s = jnp.einsum("bhd,bhkd->bhk", q, ck,
                       preferred_element_type=jnp.float32)
        s = s / (hd ** 0.5)
        live = jnp.arange(cfg.max_seq)[None, None] <= t
        s = jnp.where(live, s, neg_inf)
        pattn = jax.nn.softmax(s, -1).astype(cv.dtype)
        o = jnp.einsum("bhk,bhkd->bhd", pattn, cv).reshape(b, d)
        x = x + o @ pl["wo"]
        y = _rmsnorm(x, pl["ln2"])
        if cfg.moe_experts:
            # exact top-k routing: each token gathers only its chosen
            # experts' weights (no capacity/dropping at decode time)
            return (x + _moe_exact(y, pl, cfg),), (ck, cv)
        y = jax.nn.gelu(y @ pl["w1"])
        return (x + y @ pl["w2"],), (ck, cv)

    (x,), (ck, cv) = jax.lax.scan(
        layer, (x,), (params["layers"], caches["k"], caches["v"]))
    x = _rmsnorm(x, params["ln_f"])
    return {"k": ck, "v": cv}, _tied_logits(x, params["embed"])


def _prefill(params, prompt, cfg: TransformerConfig, total: int,
             batched: bool = True):
    """Validate a decode request, build the KV caches from the prompt, and
    return (caches, next-token logits).

    ``batched=True`` (default) runs ONE causal pass over all prompt
    positions — the whole prompt hits the MXU as [B, P] matmuls instead
    of P sequential single-token layer scans; ``batched=False`` keeps the
    token-by-token path (the decode step itself, so the two must agree —
    tested)."""
    b, p = prompt.shape
    if p < 1:
        raise ValueError("prompt must contain at least one token (an "
                         "empty prompt would decode from placeholder "
                         "logits)")
    if total <= p:
        raise ValueError("max_new_tokens must be >= 1")
    if total > cfg.max_seq:
        raise ValueError(f"prompt + new tokens = {total} exceeds "
                         f"max_seq={cfg.max_seq}")
    if cfg.moe_experts and not 1 <= cfg.moe_top_k <= cfg.moe_experts:
        raise ValueError(f"top_k={cfg.moe_top_k} out of range for "
                         f"{cfg.moe_experts} experts")
    h, d = cfg.num_heads, cfg.dim
    caches = {
        "k": jnp.zeros((cfg.num_layers, b, h, cfg.max_seq, d // h),
                       cfg.dtype),
        "v": jnp.zeros((cfg.num_layers, b, h, cfg.max_seq, d // h),
                       cfg.dtype),
    }
    if batched:
        ks, vs, logits = _prefill_pass(params, prompt, cfg)
        caches = {
            "k": caches["k"].at[:, :, :, :p].set(ks),
            "v": caches["v"].at[:, :, :, :p].set(vs),
        }
        return caches, logits

    def prefill(carry, i):
        caches, last = carry
        caches, logits = _decode_step(params, caches, prompt[:, i], i, cfg)
        return (caches, logits), None

    (caches, logits), _ = jax.lax.scan(
        prefill, (caches, jnp.zeros((b, cfg.vocab_size), jnp.float32)),
        jnp.arange(p))
    return caches, logits


def _prefill_pass(params, prompt, cfg: TransformerConfig):
    """One causal pass over the prompt, capturing per-layer K/V.
    Returns (ks [L,B,H,P,hd], vs [L,B,H,P,hd], last-position logits
    [B, V] f32). Mirrors _decode_step's math (incl. quantized trees and
    exact MoE routing) batched over positions."""
    from multiverso_tpu.ops.quantization import maybe_dequantize

    b, p = prompt.shape
    h, d = cfg.num_heads, cfg.dim
    hd = d // h
    neg_inf = jnp.asarray(-1e30, jnp.float32)
    x = (_emb_rows(params["embed"], prompt)
         + _emb_rows(params["pos"], jnp.arange(p))[None]
         ).astype(cfg.dtype)                                 # [B, P, D]
    causal = jnp.tril(jnp.ones((p, p), bool))

    def layer(carry, pl):
        x, = carry
        pl = jax.tree.map(lambda l: maybe_dequantize(l, cfg.dtype),
                          pl, is_leaf=_is_q)
        y = _rmsnorm(x, pl["ln1"])
        qkv = jnp.einsum("bpd,de->bpe", y, pl["wqkv"])
        q, kk, vv = jnp.split(qkv, 3, axis=-1)
        split = lambda t: t.reshape(b, p, h, hd).transpose(0, 2, 1, 3)
        q, kk, vv = split(q), split(kk), split(vv)           # [B,H,P,hd]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                       preferred_element_type=jnp.float32) / (hd ** 0.5)
        s = jnp.where(causal[None, None], s, neg_inf)
        pattn = jax.nn.softmax(s, -1).astype(vv.dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", pattn, vv)
        o = o.transpose(0, 2, 1, 3).reshape(b, p, d)
        x = x + jnp.einsum("bpd,de->bpe", o, pl["wo"])
        y = _rmsnorm(x, pl["ln2"])
        if cfg.moe_experts:
            mlp = _moe_exact(y.reshape(b * p, d), pl, cfg)
            return (x + mlp.reshape(b, p, d),), (kk, vv)
        y = jax.nn.gelu(jnp.einsum("bpd,dm->bpm", y, pl["w1"]))
        return (x + jnp.einsum("bpm,md->bpd", y, pl["w2"]),), (kk, vv)

    (x,), (ks, vs) = jax.lax.scan(layer, (x,), params["layers"])
    xl = _rmsnorm(x[:, -1], params["ln_f"])                  # [B, D]
    return ks, vs, _tied_logits(xl, params["embed"])


def generate(params: Dict[str, Any], prompt: jax.Array,
             cfg: TransformerConfig, max_new_tokens: int,
             temperature: float = 0.0,
             key: Optional[jax.Array] = None,
             top_p: float = 1.0,
             eos_id: Optional[int] = None) -> jax.Array:
    """Autoregressive decode with a static KV cache: one ``lax.scan`` over
    decode steps, each step one fused single-token pass (no recompute of
    the prefix). Greedy at ``temperature=0.0``, else samples with ``key``;
    ``top_p < 1.0`` restricts sampling to the nucleus (smallest probability
    mass >= top_p); with ``eos_id`` set, a sequence that emits it keeps
    emitting it (shapes stay static — trim on the host).

    prompt: [B, P] int32 -> returns [B, P + max_new_tokens]. Decoding is
    inherently sequential so there is no sequence axis here (dense and MoE
    configs; attn is ignored); run it data-parallel by sharding B. MoE
    layers decode with exact top-k routing — each token gathers only its
    chosen experts' weights.

    ``params`` may be an int8 weight-only tree from
    ``ops.quantization.quantize_lm_params`` — weights stay int8 in HBM and
    are dequantized one layer at a time inside the decode scan.
    """
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if eos_id is not None and not 0 <= eos_id < cfg.vocab_size:
        raise ValueError(f"eos_id={eos_id} outside vocab of "
                         f"{cfg.vocab_size} (the latch could never fire)")
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    b, p = prompt.shape
    caches, logits = _prefill(params, prompt, cfg, p + max_new_tokens)
    neg_inf = jnp.asarray(-1e30, jnp.float32)

    def pick(logits, k):
        if temperature <= 0.0:
            return jnp.argmax(logits, -1).astype(prompt.dtype)
        logits = logits / temperature
        if top_p < 1.0:
            # nucleus filter: drop tokens outside the smallest set whose
            # probability mass reaches top_p (the top token always stays)
            sorted_logits = jnp.sort(logits, -1)[:, ::-1]
            csum = jnp.cumsum(jax.nn.softmax(sorted_logits, -1), -1)
            cutoff_idx = jnp.sum(csum < top_p, -1)  # first idx reaching p
            cutoff = jnp.take_along_axis(sorted_logits,
                                         cutoff_idx[:, None], -1)
            logits = jnp.where(logits >= cutoff, logits, neg_inf)
        return jax.random.categorical(k, logits).astype(prompt.dtype)

    def finish(tok, done):
        """Latch eos: once a row emits it, it keeps emitting it."""
        if eos_id is None:
            return tok, done
        tok = jnp.where(done, jnp.asarray(eos_id, tok.dtype), tok)
        return tok, done | (tok == eos_id)

    def decode(carry, i):
        caches, logits, k, done = carry
        k, sub = jax.random.split(k)
        tok, done = finish(pick(logits, sub), done)
        caches, logits = _decode_step(params, caches, tok, p + i, cfg)
        return (caches, logits, k, done), tok

    # scan max_new_tokens - 1 steps; the final token needs only the last
    # logits, not another forward pass
    k0 = key if key is not None else jax.random.key(0)
    done0 = jnp.zeros((b,), bool)
    (_, logits, kf, done), new = jax.lax.scan(
        decode, (caches, logits, k0, done0), jnp.arange(max_new_tokens - 1))
    _, sub = jax.random.split(kf)
    last, _ = finish(pick(logits, sub), done)
    new = (jnp.concatenate([new.T, last[:, None]], axis=1)
           if max_new_tokens > 1 else last[:, None])
    return jnp.concatenate([prompt, new], axis=1)


def generate_beam(params: Dict[str, Any], prompt: jax.Array,
                  cfg: TransformerConfig, max_new_tokens: int,
                  num_beams: int = 4, return_score: bool = False):
    """Beam-search decode: keep the ``num_beams`` highest-logprob
    continuations per sequence, return the best [B, P + max_new_tokens]
    (with its total continuation log-prob [B] when ``return_score``).

    Built on the same KV-cache machinery as :func:`generate` by running
    the batch expanded to B*W rows; each step reorders the caches along
    the beam dim (one gather) after the top-k over (beam, token) pairs.
    ``num_beams=1`` reduces exactly to greedy decoding. Note beam search
    maximizes over the searched set — the greedy path itself can be
    pruned, so the result is not pointwise >= greedy in log-prob.
    """
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    b, p = prompt.shape
    w = num_beams
    v = cfg.vocab_size

    # prefill once per sequence, then fan the caches out to the W beams
    # (each batch row's beams start identical); scores start [0, -inf, ...]
    # so the first expansion step picks W distinct tokens from beam 0
    caches, logits = _prefill(params, prompt, cfg, p + max_new_tokens)
    caches = jax.tree.map(lambda c: jnp.repeat(c, w, axis=1), caches)
    logits = jnp.repeat(logits, w, axis=0)                   # [B*W, V]
    scores = jnp.tile(jnp.asarray([0.0] + [-1e30] * (w - 1), jnp.float32),
                      (b, 1))                                # [B, W]

    def step(carry, i):
        caches, logits, scores, toks = carry
        logp = jax.nn.log_softmax(logits, -1).reshape(b, w, v)
        cand = scores[..., None] + logp                      # [B, W, V]
        scores, flat = jax.lax.top_k(cand.reshape(b, w * v), w)
        origin = flat // v                                   # [B, W]
        tok = (flat % v).astype(prompt.dtype)
        # reorder beam state to follow the surviving beams
        gather = (jnp.arange(b)[:, None] * w + origin).reshape(-1)
        caches = jax.tree.map(lambda c: c[:, gather], caches)
        toks = toks[jnp.arange(b)[:, None], origin]          # [B, W, T]
        toks = toks.at[:, :, i].set(tok)
        caches, logits = _decode_step(params, caches, tok.reshape(-1),
                                      p + i, cfg)
        return (caches, logits, scores, toks), None

    toks0 = jnp.zeros((b, w, max_new_tokens), prompt.dtype)
    (caches, logits, scores, toks), _ = jax.lax.scan(
        step, (caches, logits, scores, toks0),
        jnp.arange(max_new_tokens - 1))
    # final token from the last logits, no further forward pass
    logp = jax.nn.log_softmax(logits, -1).reshape(b, w, v)
    cand = scores[..., None] + logp
    scores, flat = jax.lax.top_k(cand.reshape(b, w * v), w)
    origin, tok = flat // v, (flat % v).astype(prompt.dtype)
    toks = toks[jnp.arange(b)[:, None], origin]
    toks = toks.at[:, :, max_new_tokens - 1].set(tok)
    best = jnp.argmax(scores, -1)                            # [B]
    new = toks[jnp.arange(b), best]                          # [B, T]
    out = jnp.concatenate([prompt, new], axis=1)
    if return_score:
        return out, scores[jnp.arange(b), best]
    return out


def shard_batch(tokens: np.ndarray, cfg: TransformerConfig,
                mesh=None) -> jax.Array:
    """device_put a [B, S] token batch sharded P(batch_axis, seq_axis).
    With ``attn="zigzag"`` the sequence is permuted into zigzag order first
    (apply to tokens AND targets; logits/losses come back in the same
    order, which leaves any position-mean loss unchanged)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from multiverso_tpu.zoo import Zoo
    zoo_mesh = Zoo.get().mesh()
    mesh = mesh or zoo_mesh
    tokens = jnp.asarray(tokens)
    if cfg.attn == "zigzag":
        ax = cfg.seq_axis or Zoo.get().shard_axis()
        if mesh.shape[ax] != zoo_mesh.shape[ax]:
            # forward_with_aux derives the zigzag layout from the Zoo mesh;
            # permuting with a different shard count would silently corrupt
            # the causal masking
            raise ValueError(
                f"mesh axis {ax!r} has {mesh.shape[ax]} shards but the "
                f"active Zoo mesh has {zoo_mesh.shape[ax]}; zigzag layout "
                "must be computed against the mesh the model runs on")
        perm = ring.zigzag_shard_ids(tokens.shape[1], mesh.shape[ax])
        tokens = tokens[:, perm]
    spec = P(cfg.batch_axis, cfg.seq_axis)
    return jax.device_put(tokens, NamedSharding(mesh, spec))
