"""Wire compression filters (numpy REFERENCE implementations).

TPU-native equivalent of the reference filter layer
(ref: include/multiverso/util/quantization_util.h:37-154 — ``SparseFilter``
rewrites a blob as (index, value) pairs when >50% of entries fall under a
clip threshold; ``OneBitsFilter`` (:160-161) was declared and never
implemented). On TPU the intra-pod wire is ICI managed by XLA, so these
filters matter on the *host/DCN* seams: compressing deltas before
cross-process aggregation or before a slow host<->device transfer.

``OneBitsFilter`` is actually implemented here — 1-bit sign quantization with
per-block scale and error-feedback residual (the 1-bit SGD recipe the
reference planned): finishing what the reference left as a stub.
``TopKFilter`` adds the sparse top-magnitude encode (QSGD-style
sparsification) with the same error-feedback contract.

These numpy implementations are the SOURCE OF TRUTH the jitted device
kernels in ``ops/wire_codec.py`` are property-tested against, bit-for-bit
on bits and scales. That parity is engineered: per-block sums use the
explicit pairwise fold in :func:`_fold_sum` (the identical f32 addition
sequence the device kernel performs — a naive ``.sum(1)`` would differ in
the last ulp from XLA's reduction order), masking uses ``where`` (never
multiply, which XLA could fuse into an FMA), and the scale division is a
single f32/f32 divide. Change one side only in lockstep with the other.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np


# Codec property: SUB-NORMAL inputs are flushed to zero before encoding.
# XLA's CPU/TPU arithmetic flushes denormals (FTZ) the moment the residual
# add runs, so the device kernel cannot see them; the numpy side flushes
# EXPLICITLY at the same point so bits/scales/residuals stay bit-identical.
# Denormal gradient entries (< ~1.18e-38) are far below any useful signal.
_TINY = np.float32(np.finfo(np.float32).tiny)


def canon_f32(x: np.ndarray) -> np.ndarray:
    """Flush sub-normals to zero (mirrors ``wire_codec.canon_f32``)."""
    return np.where(np.abs(x) < _TINY, np.float32(0), x)


def _fold_sum(x: np.ndarray) -> np.ndarray:
    """Pairwise-fold sum over axis 1 (width must be a power of two):
    mirrors ``wire_codec.fold_sum`` addition-for-addition."""
    while x.shape[1] > 1:
        x = x[:, 0::2] + x[:, 1::2]
    return x[:, 0]


def _pow2_pad(width: int) -> int:
    return 1 << max(width - 1, 0).bit_length() if width > 1 else 1


def _block_scales(blocks: np.ndarray, n: Optional[int] = None
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(pos mask, pos_scale, neg_scale) for (nb, block) f32 blocks —
    mean of positives / mean magnitude of non-positives per block.
    ``n`` (logical element count): the block-padding tail beyond it is
    EXCLUDED from the negative-side mean — pad zeros are not data, and
    counting them dilutes the last block's neg scale toward 0 (for a
    small payload in a big block that dilution destabilizes error
    feedback: negatives decode near-zero forever)."""
    nb, block = blocks.shape
    pos = blocks > 0
    neg = ~pos
    if n is not None and n < nb * block:
        valid = (np.arange(nb * block) < n).reshape(nb, block)
        neg = neg & valid
    m = _pow2_pad(block)

    def _mean(vals: np.ndarray, mask: np.ndarray) -> np.ndarray:
        picked = np.where(mask, vals, np.float32(0))
        if m != block:
            picked = np.pad(picked, ((0, 0), (0, m - block)))
        s = _fold_sum(picked)
        cnt = np.maximum(mask.sum(1), 1).astype(np.float32)
        return np.where(mask.any(1), s / cnt, np.float32(0))

    return pos, _mean(blocks, pos), _mean(-blocks, neg)


def onebit_encode_np(flat: np.ndarray, block: int = 1024
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Stateless 1-bit encode of a flat f32 array -> (bits, scales) —
    the payload half of :class:`OneBitsFilter` without the residual, and
    the numpy reference of ``wire_codec.onebit_encode``. Used where the
    stream has no owner to carry error feedback (the PS wire's
    :func:`~multiverso_tpu.ps.wire.encode_payload`) and as the shared
    core of the filter above."""
    if block % 8:
        raise ValueError(f"block must be a multiple of 8, got {block}")
    flat = canon_f32(np.asarray(flat, np.float32).reshape(-1))
    n = flat.size
    nb = (n + block - 1) // block
    padded = np.zeros(nb * block, np.float32)
    padded[:n] = flat
    pos, pos_scale, neg_scale = _block_scales(padded.reshape(nb, block),
                                              n=n)
    return np.packbits(pos, axis=None), np.stack([pos_scale, neg_scale],
                                                 axis=1)


def onebit_decode_np(bits: np.ndarray, scales: np.ndarray, n: int,
                     block: int = 1024) -> np.ndarray:
    """Inverse of :func:`onebit_encode_np` (f32[n] out)."""
    nb = (n + block - 1) // block
    pos = np.unpackbits(np.asarray(bits), count=nb * block
                        ).astype(bool).reshape(nb, block)
    scales = np.asarray(scales)
    out = np.where(pos, scales[:, 0][:, None], -scales[:, 1][:, None])
    return out.reshape(-1)[:n].astype(np.float32)


def default_topk(n: int) -> int:
    """Default top-k support: ~3% of entries, at least one (MUST stay in
    sync with ``wire_codec.default_topk`` — the two codecs are parallel
    implementations of the same wire)."""
    return max(n // 32, 1)


def topk_encode_np(flat: np.ndarray, k: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Stateless top-k encode of a flat f32 array -> (idx i32, vals f32)
    — the payload half of :class:`TopKFilter` without the residual (same
    selection rule: stable descending |x|, ties to the lower index, like
    ``jax.lax.top_k``). Used where the stream has no owner to carry
    error feedback (row-batch adds on the PS wire: the row set changes
    between batches, so a positional residual has no stable meaning)."""
    flat = canon_f32(np.asarray(flat, np.float32).reshape(-1))
    k = min(default_topk(flat.size) if k is None else k, flat.size)
    idx = np.argsort(-np.abs(flat), kind="stable")[:k].astype(np.int32)
    return idx, flat[idx]


def topk_decode_np(idx: np.ndarray, vals: np.ndarray, n: int) -> np.ndarray:
    """Inverse of :func:`topk_encode_np` (zeros off-support)."""
    out = np.zeros(n, np.float32)
    out[np.asarray(idx)] = np.asarray(vals, np.float32)
    return out


class SparseFilter:
    """(index, value) sparse encoding under a clip threshold
    (ref quantization_util.h SparseFilter: FilterIn/FilterOut)."""

    def __init__(self, clip: float = 0.0):
        self.clip = clip

    def filter_in(self, data: np.ndarray) -> Tuple[Dict, np.ndarray]:
        """Returns (header, payload). Sparse iff >50% of entries are clipped
        (the reference's worthwhile-to-compress rule)."""
        flat = np.asarray(data, dtype=np.float32).reshape(-1)
        keep = np.abs(flat) > self.clip
        nnz = int(keep.sum())
        if nnz * 2 < flat.size:
            idx = np.nonzero(keep)[0].astype(np.int32)
            vals = flat[keep]
            payload = np.concatenate([idx.view(np.float32), vals])
            return ({"sparse": True, "size": flat.size, "nnz": nnz},
                    payload)
        return {"sparse": False, "size": flat.size}, flat

    def filter_out(self, header: Dict, payload: np.ndarray) -> np.ndarray:
        if not header["sparse"]:
            return payload.copy()
        nnz = header["nnz"]
        idx = payload[:nnz].view(np.int32)
        vals = payload[nnz:]
        out = np.zeros(header["size"], dtype=np.float32)
        out[idx] = vals
        return out


class OneBitsFilter:
    """1-bit quantization with error feedback (declared but empty in the
    reference, quantization_util.h:160-161 — implemented here).

    Encode: per-block mean magnitude of positives/negatives + sign bitmap.
    The quantization error is kept as a residual and added to the next
    payload, so the compressed stream is unbiased over time (1-bit SGD)."""

    def __init__(self, block: int = 1024):
        if block % 8:
            raise ValueError(f"block must be a multiple of 8, got {block}")
        self.block = block
        self._residual: Optional[np.ndarray] = None

    def filter_in(self, data: np.ndarray) -> Tuple[Dict, np.ndarray, np.ndarray]:
        flat = np.asarray(data, dtype=np.float32).reshape(-1)
        if self._residual is None or self._residual.size != flat.size:
            self._residual = np.zeros_like(flat)
        flat = canon_f32(flat + self._residual)
        n = flat.size
        bits, scales = onebit_encode_np(flat, self.block)
        self._residual = flat - onebit_decode_np(bits, scales, n, self.block)
        return {"size": n, "block": self.block}, bits, scales

    def filter_out(self, header: Dict, bits: np.ndarray,
                   scales: np.ndarray) -> np.ndarray:
        return onebit_decode_np(bits, scales, header["size"],
                                header["block"])

    def compression_ratio(self, n: int) -> float:
        """bytes(original float32) / bytes(bits + scales)."""
        nb = (n + self.block - 1) // self.block
        return (4.0 * n) / (n / 8.0 + 8.0 * nb)


class TopKFilter:
    """Sparse top-magnitude encode with error feedback: the k largest-|x|
    entries travel exactly as (i32 index, f32 value) pairs; everything
    else accumulates in the residual for later payloads (QSGD-style
    sparsification — the ``wire_codec.topk_encode`` numpy reference).

    Ties break toward the lower index (stable descending sort), matching
    ``jax.lax.top_k``."""

    def __init__(self, k: int):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.k = k
        self._residual: Optional[np.ndarray] = None

    def filter_in(self, data: np.ndarray
                  ) -> Tuple[Dict, np.ndarray, np.ndarray]:
        flat = np.asarray(data, dtype=np.float32).reshape(-1)
        if self._residual is None or self._residual.size != flat.size:
            self._residual = np.zeros_like(flat)
        flat = canon_f32(flat + self._residual)
        k = min(self.k, flat.size)
        idx = np.argsort(-np.abs(flat), kind="stable")[:k].astype(np.int32)
        vals = flat[idx]
        self._residual = flat.copy()
        self._residual[idx] = np.float32(0)
        return {"size": flat.size, "k": k}, idx, vals

    def filter_out(self, header: Dict, idx: np.ndarray,
                   vals: np.ndarray) -> np.ndarray:
        out = np.zeros(header["size"], np.float32)
        out[idx] = vals
        return out

    def compression_ratio(self, n: int) -> float:
        """bytes(original float32) / bytes(idx + vals)."""
        return (4.0 * n) / (8.0 * min(self.k, n))
