"""The percentile every metric of the benchmark uses: linear
interpolation between closest ranks, the same arithmetic as the serving
stack's report code, kept here so that a change to the program cannot
move the yardstick."""
from __future__ import annotations

from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) of `xs`; NaN for an empty sample."""
    if not xs:
        return float("nan")
    v = sorted(xs)
    rank = (len(v) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (rank - lo)
