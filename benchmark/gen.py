"""Seeded inputs: a Zipf token-id corpus and Criteo-shaped CTR batches.

Everything here is NumPy on the host and a function of the seed alone, so
the same ``--seed`` gives the same inputs on every machine. No strings
are made: the corpus is ids and the vocabulary is counts.

The corpus has the program's ``synthetic_corpus`` structure
(``apps/word_embedding.py``) without the strings: tokens come in topic
runs of ``run_lo`` to ``run_hi - 1`` positions, and inside a run a token
is ``topic + (zipf(offset_zipf_a) % band)``, so a window holds a few ids
many times over. Unlike ``synthetic_corpus``, whose topics are uniform
over the vocabulary, a run's topic is drawn from a bounded Zipf law
(``p_i ~ (i+1)^-topic_zipf_a``), as words in text are: there are
frequent words, so the program's frequent-word subsampling, its
unigram^0.75 negative sampler and duplicate rows in one batch's scatter
all have work to do. ``topic_zipf_a`` = 0 is the uniform law. The law is
a dict of those five numbers (a configuration's ``corpus_law``).

Ids are ranks: id 0 is the most frequent token, as in a pre-counted
vocabulary file, which is sorted by count.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np
from scipy.special import zeta

Law = Tuple[float, float, int, int, int]


def _law(law: Dict[str, Any]) -> Law:
    return (float(law["topic_zipf_a"]), float(law["offset_zipf_a"]),
            int(law["band"]), int(law["run_lo"]), int(law["run_hi"]))


def _zipf_mod_pmf(a: float, m: int) -> np.ndarray:
    """P(X % m == r) for X ~ Zipf(a), r = 0..m-1, exactly: the residue
    class r holds r, r+m, r+2m, ... so its mass is m^-a * hurwitz(a, r/m)
    over zeta(a); r = 0 holds m, 2m, ... which is m^-a * zeta(a)."""
    r = np.arange(1, m, dtype=np.float64)
    p = np.empty(m, np.float64)
    p[0] = float(m) ** -a
    p[1:] = float(m) ** -a * zeta(a, r / m) / zeta(a)
    return p / p.sum()


def _topic_pmf(vocab: int, a: float, band: int) -> np.ndarray:
    w = np.arange(1, max(vocab - band, 1) + 1, dtype=np.float64) ** -a
    return w / w.sum()


@functools.lru_cache(maxsize=4)
def _ranked(vocab: int, law: Law) -> Tuple[np.ndarray, np.ndarray]:
    """(pmf by rank, rank of each raw id): the law of one corpus token
    over raw ids (the topic's law convolved with the offset's, the
    overhang folded onto the last id as ``corpus_ids`` clips it), sorted
    by falling probability."""
    a_topic, a_off, band = law[:3]
    p = np.convolve(_topic_pmf(vocab, a_topic, band),
                    _zipf_mod_pmf(a_off, band))
    p = np.concatenate([p, np.zeros(max(vocab - p.size, 0))])
    p[vocab - 1] += p[vocab:].sum()
    p = p[:vocab] / p[:vocab].sum()
    order = np.argsort(-p, kind="stable")
    rank_of = np.empty(vocab, np.int32)
    rank_of[order] = np.arange(vocab, dtype=np.int32)
    return p[order], rank_of


def token_pmf(vocab: int, law: Dict[str, Any]) -> np.ndarray:
    """The law of one corpus token over ids 0..vocab-1 (falling)."""
    return _ranked(vocab, _law(law))[0]


def vocab_counts(vocab: int, corpus_words: int, min_count: int,
                 law: Dict[str, Any]) -> np.ndarray:
    """Pre-counted vocabulary: expected counts of ``corpus_words`` tokens
    under :func:`token_pmf`, floored at ``min_count`` (a pre-counted
    vocabulary file is already pruned). Independent of the seed, so every
    run trains the same dictionary."""
    c = np.round(token_pmf(vocab, law) * float(corpus_words)).astype(np.int64)
    return np.maximum(c, int(min_count))


def corpus_ids(num_tokens: int, vocab: int, seed: int,
               law: Dict[str, Any]) -> np.ndarray:
    """``num_tokens`` raw token ids (int32) in topic runs, from ``seed``."""
    a_topic, a_off, band, run_lo, run_hi = key = _law(law)
    rng = np.random.default_rng([int(seed), 0x636F7270])   # "corp"
    # 6% more runs than the mean length needs always cover the text (a
    # short draw would only lengthen the last run, never cut the corpus)
    n_runs = int(num_tokens / ((run_lo + run_hi - 1) / 2) * 1.06) + 64
    runs = rng.integers(run_lo, run_hi, size=n_runs)
    if int(runs.sum()) < num_tokens:
        runs[-1] += num_tokens - int(runs.sum())
    cdf = np.cumsum(_topic_pmf(vocab, a_topic, band))
    topics = np.minimum(np.searchsorted(cdf, rng.random(n_runs)),
                        cdf.size - 1).astype(np.int32)
    # offsets by table lookup (the word2vec.c sampler's design): 65,536
    # slots filled in proportion to the offset's law, one uniform 16-bit
    # draw per token; four times faster than a binary search per token
    slots = np.round(np.cumsum(_zipf_mod_pmf(a_off, band)) * 65536)
    table = np.repeat(np.arange(band, dtype=np.int32),
                      np.diff(np.concatenate([[0], slots])).astype(np.int64))
    offsets = table[rng.integers(0, 65536, size=num_tokens, dtype=np.uint16)]
    out = np.repeat(topics, runs)[:num_tokens]
    out += offsets
    np.minimum(out, np.int32(vocab - 1), out=out)
    return _ranked(vocab, key)[1][out]


def bounded_zipf_cdf(card: int, a: float) -> np.ndarray:
    """CDF of p_i ~ (i+1)^-a over i = 0..card-1."""
    w = np.arange(1, card + 1, dtype=np.float64) ** -a
    return np.cumsum(w / w.sum())


def ctr_batches(cards, dense_dim: int, batch: int, n_batches: int,
                zipf_a: float, seed: int):
    """``n_batches`` CTR batches: ``cat [n, batch, F]`` int32 ids, each
    field drawn from a bounded Zipf(zipf_a) over its cardinality and then
    sent through a fixed permutation-free hash so that hot ids are not
    the field's first rows; ``dense [n, batch, dense_dim]`` f32 normal;
    ``labels [n, batch]`` f32 from a planted logistic model over the dense
    features and a hash of the first two fields."""
    rng = np.random.default_rng([int(seed), 0x63747262])   # "ctrb"
    n = n_batches * batch
    cat = np.empty((n, len(cards)), np.int32)
    for f, card in enumerate(cards):
        rank = np.searchsorted(bounded_zipf_cdf(card, zipf_a), rng.random(n))
        rank = np.minimum(rank, card - 1).astype(np.int64)
        # spread the popularity ranks over the field's rows
        cat[:, f] = (rank * 2654435761 + 12345 * (f + 1)) % card
    dense = rng.normal(size=(n, dense_dim)).astype(np.float32)
    w = rng.normal(size=dense_dim)
    affinity = 1.5 * np.sin(cat[:, 0] * 12.9898 + cat[:, 1] * 78.233)
    logits = dense @ w + affinity
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return (cat.reshape(n_batches, batch, len(cards)),
            dense.reshape(n_batches, batch, dense_dim),
            labels.reshape(n_batches, batch))
