"""DLRM-style recommender: sharded embedding tables + dot-interaction MLP.

The workload class the parameter-server design exists for (ref: the
LogisticRegression app's sparse-FTRL CTR path, Applications/
LogisticRegression/src/util/sparse_table.h, and WordEmbedding's claim of
21M-vocab embedding tables, Applications/WordEmbedding/README.md "Why") —
modernized: categorical fields hit row-sharded embedding tables
(`MatrixTable`), the dense side is a small MLP, and second-order feature
interactions are pairwise dots (the DLRM architecture).

TPU-first training shape: ONE jitted step — gather embedding rows, forward
+ backward, scatter the row gradients into a dense table delta
(duplicate-accumulating, like the word2vec fused path), then apply the
table's server-side updater via ``functional_add``. Gradient aggregation
followed by one updater application per step = the BSP parameter-server
semantics with zero wire hops. All tables stay row-sharded over the mesh;
XLA inserts the collectives.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.updaters import AddOption


class DLRMConfig(NamedTuple):
    vocab_sizes: Tuple[int, ...] = (100, 100, 100)  # rows per categorical field
    embed_dim: int = 16
    dense_dim: int = 8                  # continuous-feature width
    bottom_mlp: Tuple[int, ...] = (32, 16)  # last entry must equal embed_dim
    top_mlp: Tuple[int, ...] = (32, 1)      # last entry must be 1 (logit)
    dtype: Any = jnp.float32


def field_offsets(cfg: DLRMConfig) -> np.ndarray:
    """Row offset of each field inside the single concatenated table (the
    standard multi-table-in-one-table layout, so ONE sharded MatrixTable
    serves every field)."""
    return np.concatenate([[0], np.cumsum(cfg.vocab_sizes)[:-1]]).astype(
        np.int32)


def total_rows(cfg: DLRMConfig) -> int:
    return int(sum(cfg.vocab_sizes))


def _mlp_shapes(cfg: DLRMConfig):
    f = len(cfg.vocab_sizes)
    n_inter = (f + 1) * f // 2          # upper-triangle pairwise dots
    bottom, top = [], []
    d_in = cfg.dense_dim
    for d_out in cfg.bottom_mlp:
        bottom.append((d_in, d_out))
        d_in = d_out
    if cfg.bottom_mlp[-1] != cfg.embed_dim:
        raise ValueError(f"bottom_mlp must end at embed_dim="
                         f"{cfg.embed_dim}, got {cfg.bottom_mlp}")
    d_in = cfg.embed_dim + n_inter
    for d_out in cfg.top_mlp:
        top.append((d_in, d_out))
        d_in = d_out
    if cfg.top_mlp[-1] != 1:
        raise ValueError(f"top_mlp must end at 1 (logit), got {cfg.top_mlp}")
    return bottom, top


def init_mlp_params(cfg: DLRMConfig, seed: int = 0) -> Dict[str, Any]:
    rng = np.random.default_rng(seed)
    bottom, top = _mlp_shapes(cfg)

    def glorot(shape):
        s = np.sqrt(2.0 / (shape[0] + shape[1]))
        return jnp.asarray(rng.normal(0, s, shape), cfg.dtype)

    return {
        "bottom_w": [glorot(s) for s in bottom],
        "bottom_b": [jnp.zeros((s[1],), cfg.dtype) for s in bottom],
        "top_w": [glorot(s) for s in top],
        "top_b": [jnp.zeros((s[1],), cfg.dtype) for s in top],
    }


def flatten_mlp(params: Dict[str, Any]) -> Tuple[np.ndarray, Any]:
    """[flat f32 vector, treedef] — the MLP side lives in ONE ArrayTable
    (the ref bindings' flatten-the-net-into-one-table convention,
    ref theano_ext/lasagne_ext/param_manager.py:9-64)."""
    leaves, treedef = jax.tree.flatten(params)
    flat = np.concatenate([np.asarray(l).reshape(-1) for l in leaves])
    meta = (treedef, [l.shape for l in leaves],
            [int(np.prod(l.shape)) for l in leaves])
    return flat.astype(np.float32), meta


def unflatten_mlp(flat: jax.Array, meta) -> Dict[str, Any]:
    treedef, shapes, sizes = meta
    leaves, off = [], 0
    for shape, size in zip(shapes, sizes):
        leaves.append(flat[off: off + size].reshape(shape))
        off += size
    return jax.tree.unflatten(treedef, leaves)


def _mlp(x, ws, bs, final_linear=True):
    for i, (w, b) in enumerate(zip(ws, bs)):
        x = x @ w + b
        if not (final_linear and i == len(ws) - 1):
            x = jax.nn.relu(x)
    return x


def forward(mlp: Dict[str, Any], emb_rows: jax.Array, dense: jax.Array,
            cfg: DLRMConfig) -> jax.Array:
    """emb_rows [B, F, D], dense [B, dense_dim] -> logits [B].

    DLRM dot interaction: the bottom-MLP output joins the F embeddings,
    all (F+1 choose 2) pairwise dots concat with the bottom output feed
    the top MLP.
    """
    f = len(cfg.vocab_sizes)
    x = _mlp(dense, mlp["bottom_w"], mlp["bottom_b"], final_linear=False)
    z = jnp.concatenate([x[:, None, :], emb_rows], axis=1)   # [B, F+1, D]
    dots = jnp.einsum("bfd,bgd->bfg", z, z)                  # [B, F+1, F+1]
    iu, ju = np.triu_indices(f + 1, k=1)
    inter = dots[:, iu, ju]                                  # [B, (F+1)F/2]
    top_in = jnp.concatenate([x, inter], axis=-1)
    return _mlp(top_in, mlp["top_w"], mlp["top_b"])[:, 0]


def loss_fn(mlp: Dict[str, Any], emb_rows: jax.Array, dense: jax.Array,
            labels: jax.Array, cfg: DLRMConfig) -> jax.Array:
    """Mean binary cross-entropy on the click logit (f32)."""
    logits = forward(mlp, emb_rows, dense, cfg).astype(jnp.float32)
    y = labels.astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def make_train_step(cfg: DLRMConfig, emb_table, mlp_table, mlp_meta,
                    emb_opt: Optional[AddOption] = None,
                    mlp_opt: Optional[AddOption] = None):
    """One jitted PS step over the sharded tables.

    ``step(emb_state, mlp_state, cat_ids [B, F], dense, labels) ->
    (emb_state, mlp_state, loss)`` — gather rows, grad, scatter row grads
    into a dense delta (duplicate ids accumulate), apply each table's
    server-side updater via ``functional_add``. Donate both states when
    jitting to recycle the table buffers:
    ``jax.jit(step, donate_argnums=(0, 1))``.
    """
    offsets = jnp.asarray(field_offsets(cfg))
    n_mlp = int(mlp_table.shape[0])
    emb_opt = emb_opt or AddOption(learning_rate=0.05, rho=0.1)
    mlp_opt = mlp_opt or AddOption(learning_rate=0.05, rho=0.1)
    # The MLP params sliced out of the mesh-sharded ArrayTable state must
    # be pinned REPLICATED: on a multi-device mesh the SPMD partitioner
    # otherwise propagates the state's row-sharding through the slice
    # into the tiny parameter tensors and miscompiles the fused
    # fwd+bwd+two-updates graph — wrong LOSS, wrong deltas (first seen
    # when the 8-virtual-device conftest mesh became real; both updates
    # must be live outputs to trigger it). Replicated is also simply the
    # correct layout for a few-KB parameter vector every device reads.
    try:
        from jax.sharding import NamedSharding, PartitionSpec
        from multiverso_tpu.zoo import Zoo
        _replicated = NamedSharding(Zoo.get().mesh(), PartitionSpec())
    except Exception:   # noqa: BLE001 — no Zoo/mesh: single-device use
        _replicated = None

    def step(emb_state, mlp_state, cat_ids, dense, labels):
        # mv.dlrm.*: the names the step's regions carry in a device trace
        # (metadata only); the updater rule inside functional_add is
        # mv.rowapply.rule
        b, f = cat_ids.shape
        with jax.named_scope("mv.dlrm.gather"):
            ids = (cat_ids + offsets[None, :]).reshape(-1)    # [B*F] global
            rows = jnp.take(emb_state["data"], ids, axis=0)
            rows = rows.reshape(b, f, cfg.embed_dim)
        with jax.named_scope("mv.dlrm.mlp"):
            flat_params = mlp_state["data"][:n_mlp]
            if _replicated is not None:
                flat_params = jax.lax.with_sharding_constraint(
                    flat_params, _replicated)
            mlp = unflatten_mlp(flat_params, mlp_meta)
            loss, (g_mlp, g_rows) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(mlp, rows, dense, labels, cfg)
        # PS push: duplicate-accumulating scatter of row grads into a dense
        # table-shaped delta, then ONE updater application (grad aggregation
        # before update = BSP server semantics)
        with jax.named_scope("mv.dlrm.delta"):
            emb_delta = jnp.zeros_like(emb_state["data"]).at[ids].add(
                g_rows.reshape(b * f, cfg.embed_dim))
        emb_state = emb_table.functional_add(emb_state, emb_delta, emb_opt)
        flat_g = jnp.concatenate(
            [g.reshape(-1) for g in jax.tree.leaves(g_mlp)])
        mlp_state = mlp_table.functional_add(
            mlp_state, mlp_table.pad_delta(flat_g), mlp_opt)
        return emb_state, mlp_state, loss

    return step


def synthetic_ctr(cfg: DLRMConfig, n: int, seed: int = 0):
    """Click data with planted structure: certain (field-0, field-1) row
    pairs interact positively — learnable only through the embedding
    tables + dot interaction."""
    rng = np.random.default_rng(seed)
    f = len(cfg.vocab_sizes)
    cat = np.stack([rng.integers(0, v, n) for v in cfg.vocab_sizes],
                   axis=1).astype(np.int32)
    dense = rng.normal(size=(n, cfg.dense_dim)).astype(np.float32)
    w = rng.normal(size=cfg.dense_dim)
    affinity = rng.normal(0, 1.5, (cfg.vocab_sizes[0], cfg.vocab_sizes[1]))
    logits = dense @ w + affinity[cat[:, 0], cat[:, 1] % cfg.vocab_sizes[1]]
    labels = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(
        np.float32)
    return cat, dense, labels
