"""Reference mixing matrix of the ring: self and both neighbours weigh
1/3, or 1/2 each for two nodes (exact averaging)."""
from __future__ import annotations

import numpy as np


def matrix(n: int) -> np.ndarray:
    if n == 2:
        return np.full((2, 2), 0.5)
    c = np.zeros((n, n))
    for i in range(n):
        for j in (i - 1, i, i + 1):
            c[i, j % n] = 1.0 / 3.0
    return c
