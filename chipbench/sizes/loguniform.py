"""Sizes log-uniform over ``[lo, hi]`` (``{"loguniform": [lo, hi]}``): the
midpoint quantiles of the law, shuffled."""
import numpy as np

from chipbench import loadgen


def sizes(bounds, n, rng):
    lo, hi = bounds
    return rng.permutation(np.rint(lo * (hi / lo) ** loadgen.quantiles(n)).astype(np.int64))
