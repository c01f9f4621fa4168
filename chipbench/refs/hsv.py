"""HSV color histogram in plain NumPy (copy of ``kernels/ref.py``'s).

RGB in [0, 255] -> H in [0, 180), S and V in [0, 255] (OpenCV scale); a
pixel counts for the first of the color ranges that holds it, else for
"other"; the histogram is the share of pixels per bucket. The reference
computes in float32 with IEEE arithmetic; the control rounds every
intermediate to bfloat16.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

COLOR_NAMES = ("red", "black", "gray", "yellow", "green", "blue", "purple",
               "pink", "white", "other")
# (lo_h, lo_s, lo_v, hi_h, hi_s, hi_v)
COLOR_RANGES = np.array([
    [0, 50, 70, 9, 255, 255],      # red
    [0, 0, 0, 180, 255, 45],       # black
    [0, 0, 46, 180, 50, 200],      # gray
    [20, 50, 70, 33, 255, 255],    # yellow
    [34, 50, 70, 85, 255, 255],    # green
    [86, 50, 70, 128, 255, 255],   # blue
    [129, 50, 70, 158, 255, 255],  # purple
    [159, 50, 70, 177, 255, 255],  # pink
    [0, 0, 201, 180, 49, 255],     # white
], np.float32)


def histograms(crops: np.ndarray, dtype=np.float32) -> np.ndarray:
    """(B, H, W, 3) -> (B, 10) pixel shares, computed in ``dtype``."""
    out = []
    for crop in crops:
        rgb = np.asarray(crop).astype(dtype)
        r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
        mx = np.maximum(np.maximum(r, g), b)
        mn = np.minimum(np.minimum(r, g), b)
        diff = mx - mn
        one, zero = dtype(1), dtype(0)
        safe = np.where(diff == 0, one, diff)
        h = np.where(mx == r, np.mod((g - b) / safe, dtype(6)),
                     np.where(mx == g, (b - r) / safe + dtype(2),
                              (r - g) / safe + dtype(4)))
        h = np.where(diff == 0, zero, h) * dtype(30)
        s = np.where(mx == 0, zero, diff / np.where(mx == 0, one, mx)) * dtype(255)
        v = mx.astype(np.float32)
        h, s = h.astype(np.float32), s.astype(np.float32)
        taken = np.zeros(h.shape, bool)
        counts = []
        for lo_h, lo_s, lo_v, hi_h, hi_s, hi_v in COLOR_RANGES:
            inside = ((h >= lo_h) & (h <= hi_h) & (s >= lo_s) & (s <= hi_s)
                      & (v >= lo_v) & (v <= hi_v))
            counts.append(np.count_nonzero(inside & ~taken))
            taken |= inside
        counts.append(taken.size - np.count_nonzero(taken))
        out.append(np.array(counts) / taken.size)
    return np.array(out)


def control_histograms(crops: np.ndarray) -> np.ndarray:
    return histograms(crops, ml_dtypes.bfloat16)
