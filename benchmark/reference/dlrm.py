"""DLRM (Naumov et al. 2019), plainly: float32 ``jax.numpy`` at
``highest`` matmul precision. Bottom MLP with ReLU after every layer,
pairwise dot interaction over the bottom output and the F embeddings
(strict upper triangle, row-major), concatenated with the bottom output
into a top MLP whose last layer is linear; mean binary cross-entropy on
the logit. Gradients by autodiff; duplicate rows accumulated and AdaGrad
applied in NumPy (``rules``). Independent of ``multiverso_tpu``.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import rules


def _dense(x, ws, bs, last_linear: bool):
    for i, (w, b) in enumerate(zip(ws, bs)):
        x = x @ w + b
        if not (last_linear and i == len(ws) - 1):
            x = jnp.maximum(x, 0.0)
    return x


def logits(mlp: Dict[str, Sequence], emb: jax.Array, dense: jax.Array):
    """``emb [B, F, D]``, ``dense [B, dense_dim]`` -> logits ``[B]``."""
    x = _dense(dense, mlp["bottom_w"], mlp["bottom_b"], last_linear=False)
    z = jnp.concatenate([x[:, None, :], emb], axis=1)          # [B, F+1, D]
    dots = jnp.matmul(z, jnp.swapaxes(z, 1, 2))                # [B, F+1, F+1]
    i, j = np.triu_indices(z.shape[1], k=1)      # i < j, row-major
    top_in = jnp.concatenate([x, dots[:, i, j]], axis=1)
    return _dense(top_in, mlp["top_w"], mlp["top_b"], last_linear=True)[:, 0]


def bce(mlp, emb, dense, labels):
    z = logits(mlp, emb, dense)
    return jnp.mean(jnp.maximum(z, 0.0) - z * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(z))))


def grads(mlp: Dict[str, List[np.ndarray]], rows: np.ndarray,
          dense: np.ndarray, labels: np.ndarray
          ) -> Tuple[float, Dict[str, List[np.ndarray]], np.ndarray]:
    """Loss, MLP gradients and per-slot embedding gradients ``[B, F, D]``
    of one batch whose gathered embedding rows are ``rows``."""
    with jax.default_matmul_precision("highest"):
        mlp_j = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), mlp)
        fn = jax.jit(jax.value_and_grad(bce, argnums=(0, 1)))
        loss, (g_mlp, g_rows) = fn(
            mlp_j, jnp.asarray(rows, jnp.float32),
            jnp.asarray(dense, jnp.float32), jnp.asarray(labels, jnp.float32))
    return (float(loss), jax.tree.map(np.asarray, g_mlp),
            np.asarray(g_rows))


def row_gradients(ids: np.ndarray, g_rows: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Unique touched table rows and their accumulated gradients
    (``ids [B, F]`` global row ids, ``g_rows [B, F, D]``)."""
    return rules.dedupe(ids.reshape(-1),
                        g_rows.reshape(-1, g_rows.shape[-1]))
