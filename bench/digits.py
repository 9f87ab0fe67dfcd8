"""Seeded 8x8 digit images for the QuClassi cells.

Each image is a 5x7 glyph of the digit at a jittered position, with ink
spread to its lower and right neighbours, a random contrast in [0.8, 1]
and uniform noise: the role MNIST plays in DQuLearn's Sec. IV-B, made
without a download.  The same seed gives the same images.
"""
from __future__ import annotations

import numpy as np

_GLYPHS = {
    0: [" ### ", "#   #", "#  ##", "# # #", "##  #", "#   #", " ### "],
    1: ["  #  ", " ##  ", "  #  ", "  #  ", "  #  ", "  #  ", " ### "],
    2: [" ### ", "#   #", "    #", "   # ", "  #  ", " #   ", "#####"],
    3: [" ### ", "#   #", "    #", "  ## ", "    #", "#   #", " ### "],
    4: ["   # ", "  ## ", " # # ", "#  # ", "#####", "   # ", "   # "],
    5: ["#####", "#    ", "#### ", "    #", "    #", "#   #", " ### "],
    6: [" ### ", "#    ", "#    ", "#### ", "#   #", "#   #", " ### "],
    7: ["#####", "    #", "   # ", "  #  ", " #   ", " #   ", " #   "],
    8: [" ### ", "#   #", "#   #", " ### ", "#   #", "#   #", " ### "],
    9: [" ### ", "#   #", "#   #", " ####", "    #", "    #", " ### "],
}
SIZE = 8
NOISE = 0.15


def _glyph(d: int) -> np.ndarray:
    return np.array([[ch == "#" for ch in row] for row in _GLYPHS[d]], np.float32)


def render(d: int, rng: np.random.Generator) -> np.ndarray:
    canvas = np.zeros((SIZE + 4, SIZE + 4), np.float32)
    r0 = int(np.clip(2 + rng.integers(-1, 2), 0, canvas.shape[0] - 7))
    c0 = int(np.clip(2 + rng.integers(-1, 2) + (SIZE - 5) // 2 - 1, 0, canvas.shape[1] - 5))
    canvas[r0 : r0 + 7, c0 : c0 + 5] = _glyph(d)
    img = canvas[2 : 2 + SIZE, 2 : 2 + SIZE]
    out = img.copy()
    out[1:, :] = np.maximum(out[1:, :], 0.4 * img[:-1, :])
    out[:, 1:] = np.maximum(out[:, 1:], 0.4 * img[:, :-1])
    out = out * rng.uniform(0.8, 1.0)
    out += NOISE * 0.5 * rng.random(out.shape).astype(np.float32)
    return np.clip(out, 0.0, 1.0).astype(np.float32)


def pair_set(a: int, b: int, n: int, rng: np.random.Generator):
    """``n`` images of digits a (label 1) and b (label 0), half each, shuffled."""
    labels = np.array([1] * (n - n // 2) + [0] * (n // 2), np.int32)
    rng.shuffle(labels)
    images = np.stack([render(a if y else b, rng) for y in labels])
    return images, labels


def digit_set(digits, n: int, rng: np.random.Generator) -> np.ndarray:
    return np.stack([render(int(rng.choice(digits)), rng) for _ in range(n)])
