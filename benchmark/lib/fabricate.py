"""Data made from the seed, in the layouts the trainers' own dataset
classes read, so that a run goes through the real data path.

``cifar_layout``: ``FedCIFAR10``'s *prepared* layout
(``data/fed_cifar.py``: ``client{c}.npy`` (n, 32, 32, 3) uint8 for each
of the 10 classes, ``test.npz``, ``stats.json``). Images are
class-conditional so the task is learnable: a per-class mean image plus
per-image noise, the construction of ``data/synthetic.py`` moved to
uint8. After the CIFAR normalisation (mean ~0.47, std ~0.25 of the
0..1 range, i.e. ~64 levels per unit) a pixel is about
``separation * N(0,1)_class + U(-0.87, 0.87)`` (noise std 0.5).
"""

from __future__ import annotations

import json
import os
import random
from typing import Dict

import numpy as np

_LEVELS_PER_UNIT = 64.0   # one normalised unit ~ 0.25 * 255 levels
_NOISE_HALF_WIDTH = 55    # uniform noise, std 55/sqrt(3) ~ 0.5 units


def cifar_layout(dataset_dir: str, seed: int, per_class: int = 5000,
                 num_classes: int = 10, num_val: int = 1000,
                 separation: float = 0.1) -> None:
    os.makedirs(dataset_dir, exist_ok=True)
    rng = np.random.default_rng([int(seed), 0xC1FA])
    means = np.rint(127.5 + _LEVELS_PER_UNIT * separation
                    * rng.standard_normal((num_classes, 32, 32, 3))
                    ).astype(np.int16)

    def draw(n, c):
        noise = rng.integers(-_NOISE_HALF_WIDTH, _NOISE_HALF_WIDTH + 1,
                             size=(n, 32, 32, 3), dtype=np.int16)
        return np.clip(noise + means[c], 0, 255).astype(np.uint8)

    for c in range(num_classes):
        np.save(os.path.join(dataset_dir, f"client{c}.npy"),
                draw(per_class, c))
    n_val = num_val // num_classes
    np.savez(os.path.join(dataset_dir, "test.npz"),
             x=np.concatenate([draw(n_val, c) for c in range(num_classes)]),
             y=np.repeat(np.arange(num_classes), n_val))
    with open(os.path.join(dataset_dir, "stats.json"), "w") as f:
        json.dump({"images_per_client": [per_class] * num_classes,
                   "num_val_images": n_val * num_classes}, f)


# --- PersonaChat-format corpus and GPT-2-layout vocabulary -----------------
# Copies of ``data/tokenizer.py`` ``fabricate_bpe_vocab`` (+ GPT-2's
# byte table) and ``data/fed_persona.py``
# ``generate_learnable_personachat``, taken so that the traffic a cell is
# measured on cannot change under a later PR (originals listed in
# PERF.md section 7 for deletion once nothing else calls them).

RAW_NAME = "personachat_self_original.json"


def _bytes_to_unicode() -> Dict[int, str]:
    """GPT-2's reversible byte<->unicode table."""
    bs = (list(range(ord("!"), ord("~") + 1))
          + list(range(ord("\xa1"), ord("\xac") + 1))
          + list(range(ord("\xae"), ord("\xff") + 1)))
    cs = bs[:]
    n = 0
    for b in range(256):
        if b not in bs:
            bs.append(b)
            cs.append(256 + n)
            n += 1
    return dict(zip(bs, [chr(c) for c in cs]))


def fabricate_bpe_vocab(save_dir: str, vocab_size: int = 50257,
                        num_words: int = 8000, seed: int = 0):
    """Write a full-size GPT-2-layout ``vocab.json``/``merges.txt``
    whose *geometry* matches the real GPT-2 vocabulary (default
    50257 entries — the reference fine-tunes this exact shape,
    gpt2_train.py:262-285) without needing the real files (zero-egress
    environment). Returns the list of ``num_words`` synthetic words,
    each of which encodes to exactly ONE token through
    :class:`GPT2BPETokenizer`, both bare and with a leading space.

    Construction: words are two consonant-vowel syllables
    ("bade", "kilu", ...). Merges are layered so greedy BPE resolves
    deterministically: char-pair -> syllable, syllable-pair -> word,
    "Ġ"+word -> spaced word. Ids are shuffled so the reachable tokens
    spread across the whole [0, vocab_size) range (embedding/softmax
    rows are exercised across the full table, not a dense prefix).
    Remaining ids are filler entries, unreachable by the merge rules —
    the real vocabulary likewise has ids rare text never produces.
    """
    rng = __import__("random").Random(seed)
    consonants = "bcdfghjklmnprstvwz"
    vowels = "aeiou"
    syllables = [c + v for c in consonants for v in vowels]  # 90
    if num_words > len(syllables) ** 2:
        raise ValueError("num_words exceeds 2-syllable combinations")
    pairs = [(a, b) for a in syllables for b in syllables]
    rng.shuffle(pairs)
    words = [a + b for a, b in pairs[:num_words]]

    byte_tokens = list(_bytes_to_unicode().values())  # 256
    tokens = list(byte_tokens) + list(syllables)
    merges = [(s[0], s[1]) for s in syllables]
    for a, b in pairs[:num_words]:
        merges.append((a, b))
        tokens.append(a + b)
    for w in words:
        merges.append(("Ġ", w))
        tokens.append("Ġ" + w)
    n_filler = vocab_size - len(tokens)
    if n_filler < 0:
        raise ValueError(f"vocab_size {vocab_size} < {len(tokens)} "
                         "constructed tokens")
    tokens.extend(f"<unused{i}>" for i in range(n_filler))

    ids = list(range(vocab_size))
    rng.shuffle(ids)
    encoder = {t: i for t, i in zip(tokens, ids)}

    os.makedirs(save_dir, exist_ok=True)
    with open(os.path.join(save_dir, "vocab.json"), "w") as f:
        json.dump(encoder, f)
    with open(os.path.join(save_dir, "merges.txt"), "w",
              encoding="utf-8") as f:
        f.write("#version: 0.2\n")
        f.write("\n".join(" ".join(m) for m in merges) + "\n")
    return words


def generate_learnable_personachat(path, word_list,
                                   num_personalities=1000,
                                   dialogs_per_personality=4,
                                   utterances_per_dialog=5,
                                   num_candidates=5,
                                   signature_size=24,
                                   num_val_dialogs=100,
                                   seed=0,
                                   val_from_train_sigs=False,
                                   distractor_disjoint=False):
    """Write a personachat-format archive with *learnable* structure,
    for convergence evidence where the real archive is unavailable
    (zero egress; reference fed_persona.py:23 downloads it from S3).

    Each personality draws a signature set of ``signature_size`` words
    from ``word_list``; its persona sentences, dialog turns, and gold
    replies all use only signature words, while distractor candidates
    are sentences from a *different* personality's signature. So:

    - the LM can cut NLL from ~ln(|word_list|) to ~ln(signature_size)
      by conditioning on the persona/history prefix;
    - the MC head is above chance iff it learns "the gold reply shares
      the prefix's vocabulary" — a relation, not a memorized string:
      validation dialogs use personalities (signature sets) never seen
      in training, so val PPL/accuracy measure the learned rule.

    ``val_from_train_sigs=True`` instead draws validation dialogs
    (fresh sentences) from the TRAINING personalities — the easier
    seen-persona tier: persona-vocabulary associations absorbed during
    training suffice, no cross-persona rule needed. Useful as a
    second evaluation split for a model trained on the default corpus
    (same word list + seed ⇒ identical train signatures).

    ``distractor_disjoint=True`` rejection-samples each distractor's
    source personality so its signature shares NO words with the gold
    signature (falls back to the least-overlapping candidate after 64
    tries). Without it, random signature collisions put gold-vocabulary
    words inside distractors, diluting the lexical-overlap signal the
    MC head must learn; with it the task's Bayes accuracy is 1.0 by a
    pure "candidate vocabulary ⊆ prefix vocabulary" rule. Off by
    default so pre-existing seeds regenerate byte-identically.

    Gold candidate is last (reference convention, fed_persona.py:305).
    """
    rng = random.Random(seed)

    def make_persona():
        return rng.sample(word_list, signature_size)

    def sentence(sig):
        return " ".join(rng.choice(sig)
                        for _ in range(rng.randint(4, 8)))

    def pick_distractor_sig(gold_set, all_sigs):
        if not distractor_disjoint:
            return rng.choice(all_sigs)
        best, best_overlap = None, None
        for _ in range(64):
            cand = rng.choice(all_sigs)
            overlap = len(gold_set.intersection(cand))
            if overlap == 0:
                return cand
            if best_overlap is None or overlap < best_overlap:
                best, best_overlap = cand, overlap
        return best

    def dialog(sig, all_sigs):
        gold_set = set(sig)
        utterances = []
        history = [sentence(sig)]
        for _ in range(utterances_per_dialog):
            cands = [sentence(pick_distractor_sig(gold_set, all_sigs))
                     for _ in range(num_candidates - 1)]
            cands.append(sentence(sig))  # gold last
            utterances.append({"history": list(history),
                               "candidates": cands})
            history.append(sentence(sig))
            history.append(sentence(sig))
        return utterances

    data = {"train": [], "valid": []}
    train_sigs = [make_persona() for _ in range(num_personalities)]
    for sig in train_sigs:
        personality = [sentence(sig) for _ in range(3)]
        others = [s for s in train_sigs if s is not sig] or [sig]
        for _ in range(dialogs_per_personality):
            data["train"].append({"personality": personality,
                                  "utterances": dialog(sig, others)})
    n_val_sigs = max(1, num_val_dialogs // 4)
    if val_from_train_sigs:
        val_sigs = [train_sigs[rng.randrange(len(train_sigs))]
                    for _ in range(n_val_sigs)]
    else:
        val_sigs = [make_persona() for _ in range(n_val_sigs)]
    for i in range(num_val_dialogs):
        sig = val_sigs[i % len(val_sigs)]
        others = [s for s in val_sigs if s is not sig] or [sig]
        data["valid"].append({
            "personality": [sentence(sig) for _ in range(3)],
            "utterances": dialog(sig, others)})
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, RAW_NAME), "w") as f:
        json.dump(data, f)


def persona_layout(dataset_dir: str, vocab_dir: str, seed: int,
                   num_personalities: int, dialogs_per_personality: int,
                   utterances_per_dialog: int, num_candidates: int,
                   num_val_dialogs: int = 8, vocab_size: int = 50257,
                   num_words: int = 8000) -> None:
    """A fabricated GPT-2-geometry vocabulary in ``vocab_dir`` and a
    learnable PersonaChat-format archive over its words in
    ``dataset_dir``, both from ``seed``."""
    words = fabricate_bpe_vocab(vocab_dir, vocab_size=vocab_size,
                                num_words=num_words, seed=seed)
    generate_learnable_personachat(
        dataset_dir, words, num_personalities=num_personalities,
        dialogs_per_personality=dialogs_per_personality,
        utterances_per_dialog=utterances_per_dialog,
        num_candidates=num_candidates, num_val_dialogs=num_val_dialogs,
        seed=seed)
