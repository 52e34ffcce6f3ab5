"""Open-loop arrival schedule from a seed: the benchmark's own copy of
``repro.serving.loadgen.arrival_times`` (Poisson arrivals: exponential gaps
at ``rate``)."""
from __future__ import annotations

import numpy as np


def arrival_times(n: int, rate: float, seed: int) -> np.ndarray:
    """(n,) send times in seconds from the start, non-decreasing."""
    if n < 1 or rate <= 0:
        raise ValueError(f"need n >= 1 and rate > 0, got n={n} rate={rate}")
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))
