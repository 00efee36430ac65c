"""Synthetic FMNIST-like images: smoothed class prototypes, two modes per
class, random shifts of up to 3 pixels and Gaussian pixel noise.

The same recipe as the program's ``make_fmnist_like``, vectorized: every
(class, mode, shift) image is built once and samples are gathered from
that table, so 70,000 images take about a second instead of a per-sample
Python loop.  It draws from its own generator, so its images are not
those of the program's function.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

SIDE = 28
MAX_SHIFT = 3


def _smooth(img: np.ndarray, iters: int) -> np.ndarray:
    for _ in range(iters):
        img = (img + np.roll(img, 1, -2) + np.roll(img, -1, -2)
               + np.roll(img, 1, -1) + np.roll(img, -1, -1)) / 5.0
    return img


def make_images(n_train: int, n_test: int, seed: int, n_classes: int = 10,
                noise: float = 0.5) -> Dict[str, np.ndarray]:
    """``{"x_train", "y_train", "x_test", "y_test"}``: images
    (n, 28, 28, 1) float32 and labels int32."""
    rng = np.random.default_rng(seed)
    shared = _smooth(rng.standard_normal((SIDE, SIDE)), 3)
    base = shared + 0.45 * _smooth(rng.standard_normal((n_classes, SIDE, SIDE)), 3)
    mode2 = base + 0.3 * _smooth(rng.standard_normal((n_classes, SIDE, SIDE)), 2)
    protos = np.stack([base, mode2], axis=1)                 # (C, 2, 28, 28)
    shifts = np.arange(-MAX_SHIFT, MAX_SHIFT + 1)
    table = np.stack([np.stack([np.roll(np.roll(protos, a, -2), b, -1)
                                for b in shifts], 2) for a in shifts], 2)
    table = table.astype(np.float32)                          # (C, 2, 7, 7, 28, 28)

    def gen(n: int, rs: np.random.Generator):
        labels = rs.integers(0, n_classes, n).astype(np.int32)
        modes = rs.integers(0, 2, n)
        sh = rs.integers(0, len(shifts), (n, 2))
        imgs = table[labels, modes, sh[:, 0], sh[:, 1]]
        imgs += noise * rs.standard_normal((n, SIDE, SIDE), np.float32)
        return imgs[..., None], labels

    xtr, ytr = gen(n_train, rng)
    xte, yte = gen(n_test, np.random.default_rng(rng.integers(2 ** 63)))
    return {"x_train": xtr, "y_train": ytr, "x_test": xte, "y_test": yte}


def partition_iid(n_samples: int, n_devices: int, seed: int) -> List[np.ndarray]:
    """A uniform random split into ``n_devices`` parts of equal size (to
    within one sample), each sorted."""
    idx = np.random.default_rng(seed).permutation(n_samples)
    return [np.sort(p) for p in np.array_split(idx, n_devices)]
