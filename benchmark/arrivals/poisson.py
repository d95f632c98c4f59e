"""Arrival process `poisson`: exponential gaps at `rate_per_s`."""
from __future__ import annotations

import random

# keys `arrival` takes for this process beyond process and rate_per_s
REQUIRED: dict = {}
OPTIONAL: dict = {}


def due_times(arrival: dict, seconds: float, seed: int) -> list[float]:
    """Offsets from the window's start at which arrivals are due, all inside
    [0, seconds)."""
    rng = random.Random(seed ^ 0xA881)
    rate = float(arrival["rate_per_s"])
    out = []
    t = rng.expovariate(rate)
    while t < seconds:
        out.append(t)
        t += rng.expovariate(rate)
    return out
