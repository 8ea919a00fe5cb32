"""Scheduler layer: median wait from a request's due time to its first
dispatch to a prefill instance (SBS buffering and PBAA packing)."""
from chipbench.stats import percentile


def read(run):
    xs = [(r.dispatch_time - r.arrival_time) * 1000.0
          for r in run.due_in_window if r.dispatch_time is not None]
    return percentile(xs, 50) if xs else None
