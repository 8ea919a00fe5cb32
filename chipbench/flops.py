"""Model FLOPs per token and the least time of a decode step, from the
published shapes.

One token's forward pass costs two FLOPs per weight it multiplies with:
every attention and router weight, the top-k experts it is routed to
(capacity the dropless dispatch leaves idle is not useful work and is not
counted), and the output head; the embedding lookup multiplies nothing.
Attention over `ctx` cached positions adds the model's own
`attention_flops`.  Each architecture's counts live in its file under
`models/`.

A decode step can take no less than the larger of two bounds: the bytes
it must read from HBM (every served weight once, and each live row's
cache: `ctx` positions at the model's `cache_bytes_per_token`) over the
chip's HBM bandwidth, and its model FLOPs over the chip's bf16 peak.
"""
from __future__ import annotations

from typing import Dict, Sequence


def per_token(model, conf: Dict, ctx: float) -> float:
    c = model.param_counts(conf)
    head = conf["vocab_size"] * conf["hidden_size"]
    # the tied embedding is the output head; untied, only the head
    # (the input table is counted in c["embed"] too) multiplies
    matmul = c["active"] - c["embed"] + head
    return 2.0 * matmul + model.attention_flops(conf, ctx)


def least_step_s(model, conf: Dict, param_bytes: int, ctxs: Sequence[int],
                 peaks: Dict) -> float:
    """Least seconds of one decode step whose live rows hold `ctxs`
    positions each."""
    read = param_bytes + model.cache_bytes_per_token(conf) * sum(ctxs)
    work = sum(per_token(model, conf, c) for c in ctxs)
    return max(read / peaks["hbm_bytes_per_s"],
               work / peaks["bf16_flops_per_s"])
