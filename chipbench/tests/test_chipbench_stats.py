"""The benchmark's percentile against the serving stack's report code it
was copied from, and against numpy's linear interpolation."""
import os
import random
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from chipbench.stats import percentile  # noqa: E402


@pytest.mark.parametrize("n", [1, 2, 7, 100])
@pytest.mark.parametrize("q", [0, 50, 95, 100])
def test_percentile_matches_the_original_and_numpy(n, q):
    from repro.serving.metrics import percentile as original
    xs = [random.Random(n).lognormvariate(0, 1) for _ in range(n)]
    assert percentile(xs, q) == original(xs, q)
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_nan():
    assert percentile([], 95) != percentile([], 95)
