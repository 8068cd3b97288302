"""A small text corpus from the seed, in run.train's ``--data_dir`` format.

Copied from chip_smoke.py::make_corpus (PR 22): words drawn Zipf-like from a
short lexicon, long enough to fill every sequence. Uniform synthetic tokens
over a 50257 vocabulary give a loss that is flat inside batch noise, so a
broken update would not show; skewed unigrams give every step a gradient."""

from __future__ import annotations

import json
import os

import numpy as np


def make_corpus(data_dir: str, seed: int, *, n_words: int,
                words_per_side: int, n_train: int, n_valid: int = 4) -> str:
    rng = np.random.default_rng(seed)
    words = np.array([f"w{i}" for i in range(n_words)])
    p = 1.0 / np.arange(1, n_words + 1)
    p /= p.sum()
    os.makedirs(data_dir, exist_ok=True)
    for split, n in (("train", n_train), ("valid", n_valid)):
        draws = rng.choice(words, (n, 2, words_per_side), p=p)
        with open(os.path.join(data_dir, f"{split}.jsonl"), "w") as f:
            for row in draws:
                f.write(json.dumps({"src": " ".join(row[0]),
                                    "trg": " ".join(row[1])}) + "\n")
    return data_dir
