"""Zero-mean unit-variance instances: every value independent."""

import numpy as np


def make(n: int, shape: tuple, seed: int) -> np.ndarray:
    return np.random.RandomState(seed % 2 ** 32).randn(n, *shape)
