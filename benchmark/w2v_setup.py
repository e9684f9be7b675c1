"""What the two word2vec drivers share: the program's ``WordEmbedding``
built over a pre-counted vocabulary, its input table drawn on the device
from the seed, the seeded corpus cut into equal chunks, and the
comparison of one batch with the plain reference.

The program's own knobs (batch size, negative pool, block size, its
internal seed) come from the traffic file; the model's sizes from the
configuration. ``program_seed`` is fixed per traffic on purpose: the
program draws its dynamic windows from it, so it fixes the pair count of
a chunk and with it the shapes of the compiled programs, for every
``--seed``. The corpus and the weights come from ``--seed``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import gen, weights
from benchmark.reference import w2v_sgns

# bf16 keeps 8 significant bits. A step that computes in bf16 rounds both
# operands of every product and its outputs (about 2^-8 each, relative),
# then sums hundreds of them: 2^-5 of the largest reference delta leaves
# two bits for the accumulation. A float32 step at full precision (the CPU
# of benchmark/tests) is held to 1e-4: a frequent word's row sums hundreds
# of float32 terms of one batch, in another order than the reference
# (seen: 1.1e-5 of the largest delta). A step that computed in anything narrower than bf16 (fp8, int8:
# 2^-3 or worse per rounding) fails the first; the drivers print the error
# they saw, as a share of the tolerance.
TOL_BF16 = 2.0 ** -5
TOL_F32 = 1e-4


def build(cell) -> Dict[str, Any]:
    """The program's WordEmbedding at the configuration's sizes."""
    from multiverso_tpu.apps.word_embedding import WEConfig, WordEmbedding
    from multiverso_tpu.data.dictionary import Dictionary

    cfg, tr = cell.config, cell.traffic
    vocab = int(cfg["vocab_size"])
    law = cfg["corpus_law"]   # counts and stream come from one law
    with cell.timed("vocab_counts"):
        counts = gen.vocab_counts(vocab, int(cfg["vocab_corpus_words"]),
                                  int(cfg["min_count"]), law)
    with cell.timed("dictionary_strings"):
        # WordEmbedding takes a Dictionary, which is a list of words and a
        # dict over them: the ids' decimal names stand in for the words
        dictionary = Dictionary.from_counts(
            [str(i) for i in range(vocab)], counts, int(cfg["min_count"]))
    we_cfg = WEConfig(
        size=cfg["vector_size"], window=cfg["window"],
        negative=cfg["negative"], alpha=cfg["alpha"], sample=cfg["sample"],
        min_count=cfg["min_count"], epoch=1, seed=tr["program_seed"],
        **tr["program"])
    with cell.timed("tables_host_init"):
        # the program draws embed_in on the host and copies it over
        we = WordEmbedding(we_cfg, dictionary)
        jax.block_until_ready((we.table_in.raw(), we.table_out.raw()))
    with cell.timed("weights_from_seed"):
        # the program's own law for embed_in (ref communicator.cpp:20)
        weights.seed_table(we.table_in, cell.seed, 0.5 / we_cfg.size)
    return {"we": we, "cfg": we_cfg, "dictionary": dictionary,
            "counts": counts, "law": law}


def keep_share(counts: np.ndarray, sample: float) -> float:
    """Expected share of raw tokens that frequent-word subsampling keeps
    (the word2vec rule the program applies), under the corpus's own law."""
    if sample <= 0:
        return 1.0
    f = counts / counts.sum()
    keep = np.minimum(1.0, (np.sqrt(f / sample) + 1) * sample / f)
    return float((f * keep).sum())


def chunks(cell, built: Dict[str, Any], words_per_chunk: int,
           n_chunks: int) -> List[np.ndarray]:
    """``n_chunks`` equal chunks of the training stream: raw ids from the
    seed, then the program's own stream policy (``prepare_ids``:
    frequent-word subsampling), then cut. Equal lengths and a fixed
    program seed give every chunk the same pair count, so one compiled
    program serves them all."""
    from multiverso_tpu.apps.word_embedding import prepare_ids

    need = words_per_chunk * n_chunks
    share = keep_share(built["counts"], built["cfg"].sample)
    vocab = len(built["dictionary"])
    parts, have, draw = [], 0, 0
    while have < need:
        raw = gen.corpus_ids(int((need - have) / share * 1.02) + 10_000,
                             vocab, cell.seed + draw, built["law"])
        kept = prepare_ids(built["dictionary"], raw, built["cfg"])
        parts.append(np.asarray(kept, np.int32))
        have += kept.size
        draw += 1
    stream = np.concatenate(parts)[:need]
    return [stream[i * words_per_chunk:(i + 1) * words_per_chunk]
            for i in range(n_chunks)]


def seeded_batch(stream: np.ndarray, vocab: int, batch: int, negatives,
                 seed: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One batch for the comparison: adjacent tokens of the stream as
    (center, context) pairs, negatives uniform over the vocabulary.
    ``negatives`` is a shape: ``(K',)`` shared pool or ``(batch, K)``."""
    rng = np.random.default_rng([int(seed), 0x63686B])      # "chk"
    pos = rng.integers(0, stream.size - 1, size=batch)
    return (stream[pos].astype(np.int32), stream[pos + 1].astype(np.int32),
            rng.integers(0, vocab, size=negatives).astype(np.int32))


def host_tables(we) -> Tuple[np.ndarray, np.ndarray]:
    """Both tables whole on the host (2 x 2.16 GB at 1.8M rows, a second
    or two each way): where the device has no room for copies (the fused
    epoch peaks at 14.2 of 16.9 GB)."""
    return np.asarray(we.table_in.raw()), np.asarray(we.table_out.raw())


def device_tables(we) -> Tuple[jax.Array, jax.Array]:
    """Copies of both tables on the device, where there is room."""
    return jnp.copy(we.table_in.raw()), jnp.copy(we.table_out.raw())


# The comparisons below take a table as a NumPy array on the host or as a
# jax array on the device: indexing, != and arithmetic read the same.

def _rows(table, ids: np.ndarray) -> np.ndarray:
    return np.asarray(table[ids])


def moved_rows(old, new) -> np.ndarray:
    return np.flatnonzero(np.asarray((old != new).any(axis=1)))


def compare_batch(old, new, centers, contexts, negatives, loss: float,
                  lr: float, neg_weight: float, tol: float) -> Dict[str, Any]:
    """Hold what one batch did to the tables (``old`` -> ``new``, each an
    (embed_in, embed_out) pair) to the plain reference on the same pairs
    and negatives: the deltas of the touched rows, the loss, that the
    touched rows moved and that no other row of either table did."""
    in_ids = np.unique(centers)
    out_ids = np.unique(np.concatenate([contexts, negatives.reshape(-1)]))
    was = (_rows(old[0], in_ids), _rows(old[1], out_ids))
    ref_loss, ref = w2v_sgns.step(
        was[0], was[1], np.searchsorted(in_ids, centers),
        np.searchsorted(out_ids, contexts),
        np.searchsorted(out_ids, negatives), lr, neg_weight)
    detail: Dict[str, Any] = {"tolerance": tol, "touched_in": int(in_ids.size),
                              "touched_out": int(out_ids.size)}
    ok = True
    for k, side, ids in ((0, "in", in_ids), (1, "out", out_ids)):
        want = np.zeros_like(was[k])
        want[ref[side + "_ids"]] = ref[side + "_delta"]
        got = _rows(new[k], ids) - was[k]
        scale = float(np.max(np.abs(want)))
        err = float(np.max(np.abs(got - want)))
        moved = moved_rows(old[k], new[k])
        detail[f"{side}_err_over_tol"] = err / (tol * scale) if scale else 0.0
        detail[f"{side}_moved_share"] = float(np.isin(ids, moved).mean())
        detail[f"{side}_others_unchanged"] = bool(np.isin(moved, ids).all())
        ok &= bool(np.all(np.isfinite(got))) and scale > 0 and err <= tol * scale
        ok &= detail[f"{side}_others_unchanged"]
        # early on a centre whose contexts and negatives are all still
        # zero rows has a zero delta (1.2% of a batch's centres, seen on
        # the chip); the deltas above are what is held, this is a floor
        ok &= detail[f"{side}_moved_share"] >= 0.9
    detail["loss"], detail["loss_ref"] = float(loss), ref_loss
    ok &= abs(float(loss) - ref_loss) <= tol * max(abs(ref_loss), 1.0)
    detail["step_agrees"] = bool(ok)
    return detail


def tables_finite(we) -> bool:
    return bool(jnp.isfinite(we.table_in.raw()).all()
                & jnp.isfinite(we.table_out.raw()).all())


# On the Zipfian stream the frequent words come back in every batch, so
# the loss falls from the first call on: 3.89 to 1.36 over 24 fused calls,
# 4.03 to 2.19 over 40 blocks (PR 24 sweep on the chip). A window has to
# show a fall of at least LOSS_FALL, or training went wrong (on this
# stream the program diverges above the traffic file's batch size: the
# loss then rises, or is NaN).
LOSS_FALL = 0.1


def loss_falls(first: float, last: float) -> bool:
    return bool(np.isfinite(last) and last <= float(first) * (1.0 - LOSS_FALL))


# A block through train_ps_blocks against reference/w2v_sgns.train_pairs on
# the same words: each side draws its own windows and negatives, so what
# can be held is size, not rows: the Frobenius norm of what the block
# added to each table, the program's over the reference's. Seen on the
# chip over four seeds: 0.966 to 0.983 (embed_in), 1.001 to 1.019
# (embed_out); on the CPU at the tiny size 0.95 to 0.99. A block that
# drops or doubles a sixth of its updates, or applies them at another
# rate, falls outside.
NORM_RATIO = (0.85, 1.15)


def compare_block(old, new, ids: np.ndarray, built: Dict[str, Any],
                  slots: np.ndarray, seed: int) -> Dict[str, Any]:
    """Hold what one block of words did to the tables (``old`` -> ``new``)
    to the plain reference training the same words from the same rows
    (``reference/w2v_sgns``: ``block_inputs``, ``train_pairs``): the rows
    of the block's words moved in both tables, no other row of embed_in
    did (embed_out also takes the negatives), and each table's delta has
    the reference's size. ``slots`` is the program's negative-sampling
    table (word ids, one per slot): the reference draws from the same law."""
    cfg = built["cfg"]
    words = np.unique(ids)
    centers, contexts, negs = w2v_sgns.block_inputs(
        ids, slots, cfg.window, cfg.negative, seed)
    rows = (np.unique(centers),
            np.unique(np.concatenate([contexts, negs.reshape(-1)])))
    was = [_rows(old[0], rows[0]), _rows(old[1], rows[1])]
    sim = w2v_sgns.train_pairs(
        was[0], was[1], np.searchsorted(rows[0], centers),
        np.searchsorted(rows[1], contexts), np.searchsorted(rows[1], negs),
        cfg.batch_size, cfg.alpha)
    detail: Dict[str, Any] = {"block_words": int(ids.size)}
    ok = True
    for k, side in ((0, "in"), (1, "out")):
        moved = moved_rows(old[k], new[k])
        ratio = (float(((new[k] - old[k]) ** 2).sum()) ** 0.5
                 / max(float(np.linalg.norm(sim[k] - was[k])), 1e-30))
        detail[f"block_{side}_norm_ratio"] = ratio
        detail[f"block_{side}_moved_share"] = float(np.isin(words, moved).mean())
        ok &= NORM_RATIO[0] <= ratio <= NORM_RATIO[1]
        ok &= detail[f"block_{side}_moved_share"] >= 0.95
        if side == "in":
            detail["block_in_others_unchanged"] = bool(
                np.isin(moved, words).all())
    detail["block_agrees"] = bool(ok and detail["block_in_others_unchanged"])
    return detail
