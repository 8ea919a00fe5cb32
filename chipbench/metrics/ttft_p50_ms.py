"""Median time to first token of the requests due in the window."""
from chipbench.metrics._window import ttfts_ms
from chipbench.stats import percentile


def read(run):
    xs = ttfts_ms(run)
    return percentile(xs, 50) if xs else None
