"""A seeded corpus of uniform full-length token rows (the live-harvest stream).

Traffic parameters: ``token_rows`` (rows in the corpus; the stream wraps).
The row length is the configuration's ``seq_len`` and the ids are uniform
over its vocabulary, so every seed gives the same amount of work.
"""

from __future__ import annotations

import numpy as np


def make(traffic: dict, seq_len: int, vocab_size: int, seed: int) -> np.ndarray:
    rows = int(traffic["token_rows"])
    return np.random.default_rng(seed).integers(
        0, vocab_size, size=(rows, seq_len), dtype=np.int32)
