"""The one traffic generator: a traffic file plus a seed give a run's requests.

A traffic mix is data, ``perfbench/traffic/<name>.json``; this module is
the only code that reads it.  Keys:

``loop``
    ``"open"``: independent users; requests arrive on a schedule whatever
    the server does.  ``"closed"``: ``clients`` callers, each sending its
    next request when the last one has come back.
``arrivals`` / ``rate_per_s`` (open loop)
    ``"poisson"`` at ``rate_per_s``: a Poisson process conditioned on its
    count, so every seed offers the same number of requests in the window
    (``round(rate * seconds)``) at uniformly drawn, sorted times.
``clients`` (closed loop)
    how many callers wait on replies at once.
``frames``
    ``pool`` frames made from the seed in set-up, with pixel values
    uniform over ``values`` (inclusive) and sizes by ``law``: ``"fixed"``
    (every frame ``height`` x ``width``).
``apps``
    how requests pick among the configuration's tenant apps:
    ``{"law": "zipf", "s": 1.1}`` (app i of the configuration's list has
    weight ``1 / (i + 1) ** s``) or ``{"law": "uniform"}``.  A
    configuration that serves one chain ignores it.

Requests are drawn in blocks of ``BLOCK``: each block holds the apps in
their exact shares (largest remainder) and every pool frame equally often,
shuffled by the seed, so seeds differ in order and pixels, not in work.
"""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import numpy as np

BLOCK = 256


def _rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)), int(seed < 0), *stream]))


def shares(weights: Sequence[float], n: int) -> List[int]:
    """``n`` split in proportion to ``weights`` by largest remainder."""
    total = float(sum(weights))
    exact = [w * n / total for w in weights]
    counts = [math.floor(x) for x in exact]
    order = sorted(range(len(weights)), key=lambda i: counts[i] - exact[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def app_weights(apps_spec: dict, n_apps: int) -> List[float]:
    law = apps_spec.get("law", "uniform")
    if law == "uniform":
        return [1.0] * n_apps
    if law == "zipf":
        s = float(apps_spec["s"])
        return [1.0 / (i + 1) ** s for i in range(n_apps)]
    raise ValueError(f"unknown app law {law!r}")


def frame_pool(frames: dict, seed: int, dtype) -> List[np.ndarray]:
    """The run's frames, made in bulk from the seed."""
    if frames["law"] != "fixed":
        raise ValueError(f"unknown frame law {frames['law']!r}")
    lo, hi = frames.get("values", (0, 255))
    shape = (int(frames["pool"]), int(frames["height"]), int(frames["width"]))
    bulk = _rng(seed, 2).integers(lo, hi + 1, shape, dtype=np.int32)
    return list(bulk.astype(dtype, copy=False))


class RequestStream:
    """Request ``k`` of a run: ``(frame index, app index)``."""

    def __init__(self, traffic: dict, n_apps: int, n_frames: int, seed: int):
        self.seed = seed
        self.n_frames = n_frames
        self.app_counts = shares(app_weights(traffic.get("apps", {}), n_apps),
                                 BLOCK)
        self._blocks = {}

    def _block(self, b: int):
        blk = self._blocks.get(b)
        if blk is None:
            rng = _rng(self.seed, 3, b)
            apps = np.repeat(np.arange(len(self.app_counts)), self.app_counts)
            frames = np.arange(BLOCK) % self.n_frames
            blk = (rng.permutation(frames), rng.permutation(apps))
            self._blocks[b] = blk
        return blk

    def __getitem__(self, k: int) -> Tuple[int, int]:
        frames, apps = self._block(k // BLOCK)
        i = k % BLOCK
        return int(frames[i]), int(apps[i])


def arrival_times(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Open-loop schedule: ``round(rate * seconds)`` sorted arrival times
    (seconds after the window opens), uniform over the window -- a
    Poisson process conditioned on its count."""
    n = int(round(rate_per_s * seconds))
    return np.sort(_rng(seed, 4).uniform(0.0, seconds, n))
