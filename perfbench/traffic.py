"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and gives the arrival schedule of a run.

Keys of a mix:

- ``arrival``: "backlog" (requests are submitted whenever fewer than
  ``backlog`` wait in the queue, so the queue never empties) or
  "poisson" (open loop at ``rate_per_s``: exponential gaps drawn from
  the seed, the schedule of ``benchmarks/stream_bench.py``).
- ``input`` names the input generator ``inputs/<input>.py``; the other
  keys are its parameters.
"""

from __future__ import annotations

import math

import numpy as np


def poisson_offsets(traffic: dict, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times of a poisson mix inside the window, in seconds from its
    start."""
    rate = float(traffic["rate_per_s"])
    if rate <= 0:
        raise ValueError(f"rate_per_s must be positive, got {rate}")
    mean = rate * seconds
    n = int(mean + 10 * math.sqrt(mean) + 10)  # never runs short
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    return t[t < seconds]


def is_open_loop(traffic: dict) -> bool:
    kind = traffic["arrival"]
    if kind not in ("backlog", "poisson"):
        raise ValueError(f"unknown arrival process {kind!r}")
    return kind == "poisson"
