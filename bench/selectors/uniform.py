"""Which stored documents the queries are made from: each drawn uniformly
from the corpus by the seed. No mix parameters."""
from __future__ import annotations

import numpy as np


def pick(n_docs: int, n: int, rng, mix: dict) -> np.ndarray:
    return rng.integers(0, n_docs, n)
