"""Open-loop arrival times: one fixed set of Poisson gaps, in a seeded order.

The gaps are ``round(rate * seconds) + 1`` exponential draws made from the
traffic's own ``gap_seed`` and scaled to fill the window, so every run
offers the same requests over the same gaps; the run's seed only shuffles
their order, which moves the bursts and not how much work a run holds or
how clumped its arrivals are. (The exponential-gap draw this follows is
``serving/trace_gen.generate_trace``; kept here so a change there cannot
move the yardstick.)
"""
from __future__ import annotations

import numpy as np


def poisson_arrivals(rate: float, seconds: float, gap_seed: int,
                     rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival offsets in (0, seconds), ``round(rate*seconds)`` many:
    the fixed gaps of ``gap_seed`` in the order ``rng`` draws."""
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"rate and seconds must be positive, got {rate}, "
                         f"{seconds}")
    n = max(int(round(rate * seconds)), 1)
    gaps = np.random.default_rng(gap_seed).exponential(1.0, n + 1)
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())
