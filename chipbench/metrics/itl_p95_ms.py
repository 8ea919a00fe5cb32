"""95th percentile of the gaps between consecutive output tokens, over
every gap that ends inside the window, of every request (the gap between
the prefill's first token and the first decode token included)."""
from chipbench.metrics._window import timelines
from chipbench.stats import percentile


def read(run):
    gaps = [(b - a) * 1000.0 for ts in timelines(run).values()
            for a, b in zip(ts, ts[1:]) if run.in_window(b)]
    return percentile(gaps, 95) if gaps else None
