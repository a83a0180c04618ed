"""The one traffic generator: every mix is a data file under
``traffic/`` that this module reads.

Training (``"kind": "train"``): steps of ``batch`` rows of ``seq`` + 1
uniform random token ids, every row different, drawn from the seed.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """uint32 words from any whole number (negative and > 2**63 too)."""
    return np.random.SeedSequence(int(seed) % (1 << 128)).generate_state(n)


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(w) for w in seed_words(seed, 4)]
                                 + [stream])


def train_rows(spec: dict, seed: int, vocab: int):
    """Endless steps: each a (batch, seq + 1) int32 array of token ids."""
    g = rng(seed, 3)
    while True:
        yield g.integers(0, vocab, (spec["batch"], spec["seq"] + 1),
                         dtype=np.int32)
