"""Wire compression filter.

TPU-native equivalent of the reference filter layer
(ref: include/multiverso/util/quantization_util.h:37-154 — ``SparseFilter``
rewrites a blob as (index, value) pairs when >50% of entries fall under a
clip threshold; the 1-bit filter declared beside it (:160-161) was never
implemented there, and is not here). On TPU the intra-pod wire is ICI
managed by XLA, so the filter matters on the *host/DCN* seams: compressing
deltas before cross-process aggregation.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


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
