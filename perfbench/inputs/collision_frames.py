"""Rate-coded collision scenes: the paper's grayscale frames as spike trains.

A copy of the scene renderer of ``src/repro/data/collision.py`` (kept here
so that the benchmark's inputs cannot change with the program), and a
rate coder over it.

Set-up renders ``scenes`` scenes at ``image_hw`` x ``image_hw`` and draws
``planes_per_scene`` independent Bernoulli spike planes of each (one plane
is one time step: pixel i fires with its intensity as probability).  A
request is one scene and ``num_steps`` distinct planes of it, both drawn
from the seed.  Since every plane of a scene is an independent draw, the
train is a rate code of that scene like any other; requests differ in
their trains while set-up stays a few milliseconds and a few MiB.
"""

from __future__ import annotations

import numpy as np


def _render_scene(rng: np.random.Generator, hw: int, label: int) -> np.ndarray:
    """One grayscale scene in [0, 1] (``repro.data.collision``)."""
    img = np.zeros((hw, hw), dtype=np.float32)
    illum = rng.uniform(0.5, 1.0)
    horizon = int(hw * rng.uniform(0.35, 0.55))
    ys = np.arange(hw)[:, None]
    img += np.where(ys < horizon, 0.75, 0.35).astype(np.float32)
    img[horizon:] += np.linspace(0.0, 0.25, hw - horizon)[:, None]

    vx = hw // 2 + rng.integers(-hw // 8, hw // 8)
    for sign in (-1, 1):
        x0 = hw // 2 + sign * int(hw * rng.uniform(0.3, 0.48))
        for y in range(horizon, hw):
            t = (y - horizon) / max(hw - horizon, 1)
            x = int(vx + (x0 - vx) * t)
            if 0 <= x < hw:
                img[y, max(x - 1, 0) : min(x + 1, hw)] += 0.15

    def draw_obstacle(cx, cy, size, dark):
        kind = rng.integers(0, 3)
        yy, xx = np.mgrid[0:hw, 0:hw]
        if kind == 0:
            m = (np.abs(xx - cx) < size) & (np.abs(yy - cy) < size * 1.3)
        elif kind == 1:
            m = ((xx - cx) / max(size, 1)) ** 2 + (
                (yy - cy) / max(size * 1.2, 1)
            ) ** 2 < 1.0
        else:
            m = (np.abs(xx - cx) < (yy - (cy - size * 1.3)) * 0.6) & (
                yy > cy - size * 1.3
            ) & (yy < cy + size * 1.3)
        img[m] = dark

    if label == 1:
        size = int(hw * rng.uniform(0.18, 0.33))
        cx = hw // 2 + rng.integers(-hw // 6, hw // 6 + 1)
        cy = int(hw * rng.uniform(0.55, 0.8))
        draw_obstacle(cx, cy, size, dark=rng.uniform(0.02, 0.18))
    else:
        for _ in range(int(rng.integers(0, 3))):
            size = int(hw * rng.uniform(0.03, 0.08))
            side = rng.integers(0, 2)
            cx = (
                rng.integers(0, hw // 5)
                if side == 0
                else rng.integers(4 * hw // 5, hw)
            )
            cy = int(hw * rng.uniform(0.45, 0.7))
            draw_obstacle(cx, cy, size, dark=rng.uniform(0.05, 0.25))

    img *= illum
    img += rng.normal(0.0, 0.05, img.shape).astype(np.float32)
    return np.clip(img, 0.0, 1.0)


class Source:
    """Spike trains of rate-coded scenes, indexed by request number."""

    def __init__(self, traffic: dict, num_steps: int, input_size: int,
                 rng: np.random.Generator):
        hw = int(traffic["image_hw"])
        if hw * hw != input_size:
            raise ValueError(
                f"{hw}x{hw} frames do not feed a {input_size}-input network"
            )
        n = int(traffic["scenes"])
        g = int(traffic["planes_per_scene"])
        if g < num_steps:
            raise ValueError(f"{g} planes cannot fill {num_steps} steps")
        labels = rng.integers(0, 2, size=n)
        self.images = np.stack(
            [_render_scene(rng, hw, int(lb)) for lb in labels]
        ).reshape(n, 1, hw * hw)
        # (scenes, planes, K) {0,1} spike planes
        self.planes = (
            rng.random((n, g, hw * hw), dtype=np.float32) < self.images
        ).astype(np.uint8)
        self.num_steps = num_steps
        self._rng = rng
        self.picks: list = []  # per request: (scene, plane indices)

    def draw(self) -> int:
        """Draw the next request's scene and planes; returns its index."""
        n, g, _ = self.planes.shape
        scene = int(self._rng.integers(0, n))
        rows = self._rng.choice(g, size=self.num_steps, replace=False)
        self.picks.append((scene, rows))
        return len(self.picks) - 1

    def train_u8(self, i: int) -> np.ndarray:
        """Request ``i``'s (num_steps, K) {0,1} spike train, uint8."""
        scene, rows = self.picks[i]
        return self.planes[scene, rows]

    def train(self, i: int) -> np.ndarray:
        """Request ``i``'s spike train as the system takes it, float32."""
        return self.train_u8(i).astype(np.float32)

    def mean_rate(self) -> float:
        return float(self.planes.mean())
