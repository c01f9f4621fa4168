"""Dog crops for the UC1 lost-dog query, vectorised.

A copy of ``repro.data.video.SyntheticVideo`` and ``crop_to_canonical``:
96x128 frames of uniform noise in [60, 200), a dog planted as a solid
rectangle 24..55 pixels on a side at a uniform position, colored black
(10,10,10), gray (120,120,120), yellow (230,210,40) or white
(240,240,240); crops resized to a square by nearest neighbour. The one
change: a crop takes the dog's box widened by an eighth of its size on
each side (clipped to the frame), as a detector's box is, so that every
crop holds background pixels of all hues as well as the dog.
"""
from __future__ import annotations

import numpy as np

BREEDS = ("great dane", "labrador retriever", "poodle", "beagle")
COLORS = ("black", "gray", "yellow", "white")
COLOR_RGB = {
    "black": (10, 10, 10),
    "gray": (120, 120, 120),
    "yellow": (230, 210, 40),
    "white": (240, 240, 240),
}
BREED_PROBS = (0.25, 0.06, 0.39, 0.30)
COLOR_PROBS = (0.35, 0.06, 0.29, 0.30)
HEIGHT, WIDTH = 96, 128


def crop_to_canonical(crop: np.ndarray, size: int) -> np.ndarray:
    """Nearest-neighbour resize to a square (copy of the program's)."""
    h, w = crop.shape[:2]
    ys = (np.arange(size) * h // size).clip(0, h - 1)
    xs = (np.arange(size) * w // size).clip(0, w - 1)
    return crop[ys][:, xs]


def make_crops(colors, size: int, rng: np.random.Generator) -> np.ndarray:
    """One distinct (size, size, 3) float32 crop per entry of ``colors``."""
    n = len(colors)
    frames = rng.integers(60, 200, (n, HEIGHT, WIDTH, 3), dtype=np.uint8)
    wh = rng.integers(24, 56, (n, 2))
    x0 = rng.integers(0, WIDTH - wh[:, 0])
    y0 = rng.integers(0, HEIGHT - wh[:, 1])
    out = np.empty((n, size, size, 3), np.float32)
    for i, color in enumerate(colors):
        w, h = int(wh[i, 0]), int(wh[i, 1])
        xa, ya = int(x0[i]), int(y0[i])
        frames[i, ya:ya + h, xa:xa + w] = COLOR_RGB[color]
        mx, my = w // 8, h // 8
        box = frames[i, max(ya - my, 0):min(ya + h + my, HEIGHT),
                     max(xa - mx, 0):min(xa + w + mx, WIDTH)]
        out[i] = crop_to_canonical(box, size)
    return out
