"""Every query the same size: ``{"fixed": n}``."""
import numpy as np


def sizes(n_rows, n, rng):
    return np.full(n, int(n_rows), np.int64)
