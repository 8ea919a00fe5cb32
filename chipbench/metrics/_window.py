"""Shared arithmetic of the host-clock metrics: the token timeline of
each request (first token from prefill, then every decode token)."""
from __future__ import annotations

from typing import Dict, List


def timelines(run) -> Dict[int, List[float]]:
    out: Dict[int, List[float]] = {}
    for r in run.requests:
        if r.first_token_time is not None:
            out[r.rid] = [r.first_token_time]
    for t, rid, _ctx in run.emissions:
        out.setdefault(rid, []).append(t)
    return out


def ttfts_ms(run) -> List[float]:
    """TTFT of every request due in the window, from its due time.  One
    that never got a first token counts as waiting until the run ended."""
    end = run.window[1] + run.mix["drain_limit_s"]
    return [((r.first_token_time if r.first_token_time is not None
              else end) - r.arrival_time) * 1000.0
            for r in run.due_in_window]
