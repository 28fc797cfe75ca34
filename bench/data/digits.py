"""Frozen copy of the program's procedural 28x28 digit generator.

Ten digit glyphs of strokes in the unit square (seven-segment geometry and
a few digit-specific diagonals), each sample rasterized onto a ``side x
side`` grayscale grid under its own affine, stroke-thickness and pixel-noise
jitter drawn from ``SeedSequence([seed, 1 + i])``, then booleanized per
pixel (``pixel >= threshold``): f = side * side inputs, 10 classes. Labels
come in shuffled blocks of the ten classes and depend on the seed and the
index alone, not on ``side``. A copy of ``repro.data.mnist`` as it stood
when the benchmark took it, that imports nothing of the program
(``bench/tests/test_data.py`` holds the two equal).
"""
from __future__ import annotations

import numpy as np

SIDE = 28
N_CLASSES = 10
THRESHOLD = 0.5
N_POINTS = 150

_X0, _X1 = 0.28, 0.72
_Y0, _Y1, _Y2 = 0.16, 0.50, 0.84
_SEG = {
    "A": ((_X0, _Y0), (_X1, _Y0)),
    "B": ((_X1, _Y0), (_X1, _Y1)),
    "C": ((_X1, _Y1), (_X1, _Y2)),
    "D": ((_X0, _Y2), (_X1, _Y2)),
    "E": ((_X0, _Y1), (_X0, _Y2)),
    "F": ((_X0, _Y0), (_X0, _Y1)),
    "G": ((_X0, _Y1), (_X1, _Y1)),
    "slash": ((_X1, _Y0), (0.40, _Y2)),
    "flag": ((0.38, 0.28), (0.50, _Y0)),
    "zdiag": ((_X1, _Y0 + 0.04), (_X0, _Y2 - 0.04)),
}
_GLYPHS: tuple[tuple[str, ...], ...] = (
    ("A", "B", "C", "D", "E", "F"),            # 0
    ("flag", "B", "C"),                        # 1
    ("A", "zdiag", "D"),                       # 2
    ("A", "B", "G", "C", "D"),                 # 3
    ("F", "G", "B", "C"),                      # 4
    ("A", "F", "G", "C", "D"),                 # 5
    ("A", "F", "E", "D", "C", "G"),            # 6
    ("A", "slash"),                            # 7
    ("A", "B", "C", "D", "E", "F", "G"),       # 8
    ("G", "F", "A", "B", "C", "D"),            # 9
)


def labels(n: int, seed: int) -> np.ndarray:
    """[n] i32: rows ``10k .. 10k+9`` are a permutation of the ten classes
    drawn from ``SeedSequence([seed, 0xBA15, k])``."""
    reps = -(-n // N_CLASSES)
    out = np.concatenate([
        np.random.default_rng(
            np.random.SeedSequence([seed, 0xBA15, k])).permutation(N_CLASSES)
        for k in range(reps)
    ])
    return out[:n].astype(np.int32)


def _render(digit: int, side: int, rng: np.random.Generator) -> np.ndarray:
    """One jittered grayscale glyph [side, side] f32 in [0, 1]."""
    segs = np.asarray([_SEG[s] for s in _GLYPHS[digit]], dtype=np.float32)
    scale = rng.uniform(0.85, 1.08)
    theta = rng.uniform(-0.12, 0.12)
    shift = rng.uniform(-0.05, 0.05, size=2)
    thick = rng.uniform(0.055, 0.095)
    rot = np.array([[np.cos(theta), -np.sin(theta)],
                    [np.sin(theta), np.cos(theta)]], dtype=np.float32)
    pts = (segs.reshape(-1, 2) - 0.5) * scale @ rot.T + 0.5 + shift
    segs = pts.reshape(-1, 2, 2)

    c = (np.arange(side, dtype=np.float32) + 0.5) / side
    px = np.stack(np.meshgrid(c, c, indexing="xy"), axis=-1)

    # distance from every pixel centre to the nearest stroke
    a, b = segs[:, 0], segs[:, 1]
    ab = b - a
    denom = np.maximum((ab * ab).sum(-1), 1e-12)
    ap = px[None] - a[:, None, None]
    t = np.clip((ap * ab[:, None, None]).sum(-1) / denom[:, None, None], 0, 1)
    proj = a[:, None, None] + t[..., None] * ab[:, None, None]
    d = np.sqrt(((px[None] - proj) ** 2).sum(-1)).min(axis=0)

    # the soft edge is set by the full-width raster, whatever ``side`` is
    soft = max(0.04, 1.0 / SIDE)
    img = np.clip((thick + soft - d) / soft, 0.0, 1.0)
    img = img + rng.uniform(0.0, 0.22, size=img.shape)
    return np.clip(img, 0.0, 1.0).astype(np.float32)


def raw(n: int, seed: int, side: int) -> tuple[np.ndarray, np.ndarray]:
    """(images [n, side, side] f32 in [0, 1], labels [n] i32)."""
    ys = labels(n, seed)
    imgs = np.empty((n, side, side), dtype=np.float32)
    for i in range(n):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1 + i]))
        imgs[i] = _render(int(ys[i]), side, rng)
    return imgs, ys


def load(seed: int = 2023, n_points: int = N_POINTS, side: int = SIDE,
         threshold: float = THRESHOLD) -> tuple[np.ndarray, np.ndarray]:
    """(xs [n_points, side*side] bool, ys [n_points] i32): each pixel is 1
    where it is at least ``threshold``."""
    imgs, ys = raw(n_points, seed, side)
    return (imgs >= threshold).reshape(n_points, -1), ys
