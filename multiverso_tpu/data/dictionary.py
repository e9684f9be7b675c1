"""Vocabulary dictionary + Huffman encoding for hierarchical softmax.

TPU-native equivalent of the reference WordEmbedding vocab machinery
(ref: Applications/WordEmbedding/src/dictionary.cpp — word->id map with
min_count pruning; src/huffman_encoder.cpp — Huffman tree over word counts
producing per-word (codes, points) paths). The host-side logic is the same
job; the output here is *padded numpy arrays* (codes/points/lengths) ready to
ship to the device once, because the TPU consumes fixed-shape tensors, not
per-word C structs.
"""

from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np


class Dictionary:
    """Word <-> id with count-based pruning (ref dictionary.cpp).

    Training needs the counts alone (subsampling, the negative sampler,
    the table's row count): a vocabulary may be given as counts without
    a word list (:meth:`from_counts` with ``words=None``), and then
    ``words`` (the ids' decimal names) and ``word2id`` are made when
    something first asks for them, as ``word2id`` is for every
    dictionary. A web-scale vocabulary (12M words) trains without 12M
    strings and a dict over them on the host."""

    def __init__(self, min_count: int = 5):
        self.min_count = min_count
        self.counts: np.ndarray = np.zeros(0, dtype=np.int64)
        self._words: Optional[List[str]] = []
        self._word2id: Optional[Dict[str, int]] = None

    @property
    def words(self) -> List[str]:
        if self._words is None:
            self._words = [str(i) for i in range(self.counts.size)]
        return self._words

    @words.setter
    def words(self, words: List[str]) -> None:
        self._words, self._word2id = words, None

    @property
    def word2id(self) -> Dict[str, int]:
        if self._word2id is None:
            self._word2id = {w: i for i, w in enumerate(self.words)}
        return self._word2id

    @classmethod
    def from_counts(cls, words: Optional[List[str]], counts: np.ndarray,
                    min_count: int = 5) -> "Dictionary":
        """Adopt a pre-counted vocabulary (e.g. from the native corpus
        loader), which is already pruned and count-desc sorted. ``words``
        may be ``None``: ids are then all there is, until a word is
        asked for."""
        d = cls(min_count)
        d.counts = np.asarray(counts, dtype=np.int64)
        d._words = None if words is None else list(words)
        return d

    @classmethod
    def build(cls, tokens: Iterable[str], min_count: int = 5,
              max_vocab: Optional[int] = None) -> "Dictionary":
        d = cls(min_count)
        counter = collections.Counter(tokens)
        items = [(w, c) for w, c in counter.items() if c >= min_count]
        items.sort(key=lambda wc: (-wc[1], wc[0]))
        if max_vocab is not None:
            items = items[:max_vocab]
        d.words = [w for w, _ in items]
        d.counts = np.array([c for _, c in items], dtype=np.int64)
        return d

    def __len__(self) -> int:
        return (int(self.counts.size) if self._words is None
                else len(self._words))

    def encode(self, tokens: Iterable[str]) -> np.ndarray:
        """Token stream -> id stream, dropping OOV (ref reader behavior)."""
        w2i = self.word2id
        return np.fromiter((w2i[t] for t in tokens if t in w2i),
                           dtype=np.int64)

    def subsample(self, ids: np.ndarray, t: float = 1e-4,
                  seed: int = 0) -> np.ndarray:
        """Frequent-word subsampling (ref reader.cpp sample_value): keep word w
        with prob (sqrt(f/t)+1)*t/f where f is w's corpus frequency."""
        total = self.counts.sum()
        freq = self.counts / max(total, 1)
        keep = np.minimum(1.0, (np.sqrt(freq / t) + 1) * t / np.maximum(freq, 1e-12))
        rng = np.random.default_rng(seed)
        return ids[rng.random(ids.size) < keep[ids]]

    def unigram_table(self, power: float = 0.75) -> np.ndarray:
        """Negative-sampling distribution (counts^0.75, normalized)."""
        p = self.counts.astype(np.float64) ** power
        return (p / p.sum()).astype(np.float32)


def build_huffman(counts: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Huffman tree over word counts (ref huffman_encoder.cpp:BuildTree).

    Returns (codes, points, lengths):
    * codes  [V, L] int32 in {0,1}, the left/right decisions, padded with 0
    * points [V, L] int32, inner-node ids (< V-1), padded with V-2 safe ids
      (masked out by lengths)
    * lengths [V] int32, true path length per word

    L = max path length. Inner nodes are numbered 0..V-2 (the output table for
    HS has V-1 rows).
    """
    vocab = int(counts.size)
    if vocab < 2:
        raise ValueError("huffman needs >= 2 words")
    # Standard two-queue O(V log V) build via heap for clarity.
    import heapq
    heap = [(int(c), i) for i, c in enumerate(counts)]
    heapq.heapify(heap)
    parent = np.zeros(2 * vocab - 1, dtype=np.int64)
    binary = np.zeros(2 * vocab - 1, dtype=np.int8)
    next_id = vocab
    while len(heap) > 1:
        c1, i1 = heapq.heappop(heap)
        c2, i2 = heapq.heappop(heap)
        parent[i1] = next_id
        parent[i2] = next_id
        binary[i2] = 1
        heapq.heappush(heap, (c1 + c2, next_id))
        next_id += 1
    root = next_id - 1

    codes_list, points_list = [], []
    max_len = 0
    for w in range(vocab):
        code, point = [], []
        node = w
        while node != root:
            code.append(int(binary[node]))
            node = int(parent[node])
            point.append(node - vocab)  # inner-node id in [0, V-2]
        code.reverse()
        point.reverse()
        codes_list.append(code)
        points_list.append(point)
        max_len = max(max_len, len(code))

    codes = np.zeros((vocab, max_len), dtype=np.int32)
    points = np.full((vocab, max_len), max(vocab - 2, 0), dtype=np.int32)
    lengths = np.zeros(vocab, dtype=np.int32)
    for w in range(vocab):
        l = len(codes_list[w])
        lengths[w] = l
        codes[w, :l] = codes_list[w]
        points[w, :l] = points_list[w]
    return codes, points, lengths
