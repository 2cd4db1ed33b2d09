"""The plain reference every served output is compared with.

Straight numpy, written from the apps' definitions and importing nothing
of the system under test: each library app is a zero-bordered 3x3
correlation ``sum k[j+1][i+1] * img[y+j, x+i]`` with an optional floor
division by a constant, or a threshold ``img > t`` giving 1 or 0.  A chain
applies its stages in turn, each stage reading the previous stage's
output inside the frame and zeros outside it.

``dtype`` is the integer type the arithmetic runs in.  The configurations
state int32; a narrower type wraps as numpy integer arrays do, which is
what the control computes in.
"""

from __future__ import annotations

from typing import Sequence, Union

import numpy as np

SOBEL_X = ((-1, 0, 1), (-2, 0, 2), (-1, 0, 1))
SOBEL_Y = ((-1, -2, -1), (0, 0, 0), (1, 2, 1))
GAUSS3 = ((1, 2, 1), (2, 4, 2), (1, 2, 1))
SHARPEN = ((0, -1, 0), (-1, 5, -1), (0, -1, 0))
LAPLACE = ((0, 1, 0), (1, -4, 1), (0, 1, 0))

#: app -> (3x3 kernel, divisor or None)
STENCILS = {
    "sobel_x": (SOBEL_X, None),
    "sobel_y": (SOBEL_Y, None),
    "gauss3": (GAUSS3, 16),
    "sharpen": (SHARPEN, None),
    "laplace": (LAPLACE, None),
}
THRESHOLD = 128


def stencil(img: np.ndarray, kernel, divisor, dtype) -> np.ndarray:
    H, W = img.shape
    pad = np.zeros((H + 2, W + 2), dtype)
    pad[1:-1, 1:-1] = img
    acc = np.zeros((H, W), dtype)
    for r in range(3):
        for c in range(3):
            k = kernel[r][c]
            if k:
                acc += (pad[r:r + H, c:c + W] * dtype(k)).astype(dtype)
    if divisor is not None:
        acc //= dtype(divisor)
    return acc


def apply(app: str, img: np.ndarray, dtype=np.int32) -> np.ndarray:
    img = np.asarray(img).astype(dtype)
    if app == "threshold":
        return (img > THRESHOLD).astype(dtype)
    kernel, divisor = STENCILS[app]
    return stencil(img, kernel, divisor, dtype)


def serve(work: Union[str, Sequence[str]], img: np.ndarray,
          dtype=np.int32) -> np.ndarray:
    """The reference answer of one request: an app name or a chain."""
    stages = [work] if isinstance(work, str) else list(work)
    out = img
    for app in stages:
        out = apply(app, out, dtype)
    return out.astype(np.int32)
