"""Weight-only int8 quantization for inference.

Complements the wire-compression filter (utils/filters.py — the
reference's SparseFilter, ref
include/multiverso/util/quantization_util.h) with *storage* quantization:
params are held as int8 + per-channel f32 scales — 4x smaller in HBM, the
win for HBM-bandwidth-bound decoding — and dequantized on use (the
matmuls themselves still run in the model dtype; a true int8-MXU dot is a
possible future step).

Symmetric scheme: ``scale = max|w| / 127`` per kept channel and
``w ≈ q.astype(f32) * scale``; error is bounded by scale/2 per element.
:class:`QuantizedTensor` is a plain two-array pytree, so stacked
``[L, ...]`` quantized layers slice transparently under ``lax.scan`` —
``models/transformer.generate`` accepts trees produced by
:func:`quantize_lm_params` and dequantizes one layer at a time.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp


class QuantizedTensor(NamedTuple):
    q: jax.Array          # int8, same shape as the original
    scale: jax.Array      # f32, original shape with reduced dims = 1


def quantize(w: jax.Array, keep_axes: Sequence[int] = (-1,)
             ) -> QuantizedTensor:
    """Symmetric int8 quantization with one scale per index of the
    ``keep_axes`` dims (all other dims share a scale)."""
    keep = {a % w.ndim for a in keep_axes}
    reduce_dims = tuple(d for d in range(w.ndim) if d not in keep)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_dims,
                   keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return QuantizedTensor(q.astype(jnp.int8), scale)


def dequantize(t: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    return (t.q.astype(jnp.float32) * t.scale).astype(dtype)


def maybe_dequantize(leaf: Any, dtype=jnp.float32) -> Any:
    return dequantize(leaf, dtype) if isinstance(leaf, QuantizedTensor) \
        else leaf


def quantize_lm_params(params: Any) -> Any:
    """Quantize a models/transformer param tree for decoding: embeddings
    per-row, stacked layer matrices per (layer, out-channel); the tiny
    norm vectors stay exact. The result drops into
    ``transformer.generate`` directly."""
    out = dict(params)
    out["embed"] = quantize(params["embed"], keep_axes=(0,))
    out["pos"] = quantize(params["pos"], keep_axes=(0,))
    layers = dict(params["layers"])
    for k in ("wqkv", "wo", "w1", "w2"):
        if k in layers:
            layers[k] = quantize(layers[k], keep_axes=(0, -1))
    out["layers"] = layers
    return out
