"""Length distributions for serving traffic, as fixed sets.

A mix is the same multiset of lengths whatever the order (the ``n`` evenly
spaced quantiles of the distribution): the random generator handed in
chooses only the order, so two orders offer the same work and differ only
in when each piece of it arrives. Distributions: ``lognormal`` (``median``, ``sigma``),
``loguniform`` and ``uniform`` (over ``min``..``max``); all are clipped to
``min``..``max`` and rounded to whole tokens.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def quantile(spec: dict, u: float) -> float:
    kind = spec["dist"]
    if kind == "lognormal":
        return spec["median"] * math.exp(spec["sigma"] * statistics.NormalDist().inv_cdf(u))
    if kind == "loguniform":
        return math.exp(math.log(spec["min"]) + u * (math.log(spec["max"]) - math.log(spec["min"])))
    if kind == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    if kind == "exponential":  # inter-arrival gaps of a Poisson process
        return -math.log1p(-u) * spec["mean"]
    raise ValueError(f"unknown distribution {kind!r}")


def fixed_set(spec: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of ``spec``, clipped; floats."""
    values = np.array([quantile(spec, (i + 0.5) / n) for i in range(n)], dtype=np.float64)
    if "min" in spec or "max" in spec:
        values = np.clip(values, spec.get("min", -math.inf), spec.get("max", math.inf))
    return values


def token_counts(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths: the fixed set of ``spec`` in an order drawn from
    ``rng``."""
    return rng.permutation(np.rint(fixed_set(spec, n)).astype(np.int64))


def gaps(spec: dict, n: int, total: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps that sum to ``total`` seconds: the fixed set
    of ``spec`` rescaled, in an order drawn from ``rng``."""
    values = fixed_set(spec, n)
    return rng.permutation(values * (total / values.sum()))
