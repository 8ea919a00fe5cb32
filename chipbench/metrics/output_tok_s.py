"""Output tokens emitted inside the window, over the window's length."""
from chipbench.metrics._window import timelines


def read(run):
    n = sum(1 for ts in timelines(run).values() for t in ts
            if run.in_window(t))
    return n / run.seconds if n else None
