"""The one traffic generator: reads a mix from `traffic/<mix>.json`.

A mix file holds parameters only:

    arrivals     {"process": "poisson" | "mmpp", "rate_rps": mean rate,
                  "burst_factor", "burst_duty", "burst_period_s" (mmpp)}
    prompt_len   {"mean", "sigma", "min", "max", "quantum"}  lognormal
    output_len   {"mean", "sigma", "min", "max"}             lognormal
    scheduler    the server's scheduler ("sbs-la", "sbs", "immediate")
    lead_in_s    seconds of traffic served before the window opens
    tail_s       seconds of traffic after the window closes, so that the
                 window's last requests still meet a loaded system
    drain_limit_s  seconds after the window within which a request due
                 in the window must finish, or it counts as failed
    shape_seed   fixes the arrival times and the multiset of lengths

Arrival times and lengths, in their order, come from `shape_seed`
alone, so every run's seed offers the same work at the same moments;
`--seed` draws the prompt token ids (and so what the model computes and
which experts it routes to).  That keeps runs with different seeds as
alike as two runs of one seed.

The lognormal and MMPP arithmetic is that of the serving stack's
synthetic workloads (lognormal with mean `mean`: mu = ln(mean) - sigma²/2;
a two-state Markov-modulated Poisson process whose long-run rate is
`rate_rps`), copied so that the program cannot move it.
"""
from __future__ import annotations

import dataclasses
import json
import math
import random
from typing import Dict, Iterator, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    """One request as the benchmark offers it: due time (seconds from
    the start of serving), prompt token ids and the output length."""
    rid: int
    due: float
    prompt: List[int]
    output_len: int


def load(path: str) -> Dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("arrivals", "prompt_len", "output_len", "scheduler",
                "lead_in_s", "tail_s", "drain_limit_s", "shape_seed"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    q = mix["prompt_len"].get("quantum", 1)
    if mix["prompt_len"]["min"] % q or mix["prompt_len"]["max"] % q:
        raise ValueError(f"{path}: prompt_len min/max must be multiples "
                         f"of the quantum {q}")
    return mix


def arrival_times(arr: Dict, duration: float,
                  rng: random.Random) -> Iterator[float]:
    """Poisson at `rate_rps`, or the two-state MMPP: `burst_factor` times
    the rate for `burst_duty` of each `burst_period_s`, the quiet state
    carrying the rest, so that the long-run rate stays `rate_rps`."""
    qps = float(arr["rate_rps"])
    if arr["process"] == "poisson":
        t = 0.0
        while True:
            t += rng.expovariate(qps)
            if t >= duration:
                return
            yield t
    if arr["process"] != "mmpp":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    duty, period = float(arr["burst_duty"]), float(arr["burst_period_s"])
    bf = float(arr["burst_factor"])
    if bf * duty > 1.0:
        raise ValueError("burst_factor * burst_duty > 1: the quiet state "
                         "cannot absorb the burst")
    hi = bf * qps
    lo = qps * (1.0 - duty * bf) / max(1.0 - duty, 1e-9)
    t = 0.0
    while t < duration:
        cycle0 = math.floor(t / period) * period
        # the epsilon keeps a clock clamped to the burst's end from
        # testing inside the burst again, which would never advance
        in_burst = t < cycle0 + duty * period - 1e-12
        seg_end = cycle0 + (duty * period if in_burst else period)
        rate = hi if in_burst else lo
        if rate <= 0.0:
            t = seg_end
            continue
        t += rng.expovariate(rate)
        if t < seg_end:
            if t >= duration:
                return
            yield t
        else:
            t = seg_end


def _lognormal(spec: Dict, rng: random.Random) -> int:
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - 0.5 * sigma ** 2
    v = rng.lognormvariate(mu, sigma)
    q = int(spec.get("quantum", 1))
    v = int(round(v / q)) * q
    return max(int(spec["min"]), min(int(spec["max"]), v))


def generate(mix: Dict, seed: int, seconds: float,
             vocab: int) -> List[Arrival]:
    """The requests of one run: arrivals over lead-in + window + tail."""
    shape = random.Random(mix["shape_seed"])
    horizon = mix["lead_in_s"] + seconds + mix["tail_s"]
    dues = list(arrival_times(mix["arrivals"], horizon, shape))
    sizes = [(_lognormal(mix["prompt_len"], shape),
              _lognormal(mix["output_len"], shape)) for _ in dues]
    ids = np.random.default_rng(seed).integers(
        0, vocab, size=sum(p for p, _ in sizes), dtype=np.int64)
    out, at = [], 0
    for rid, (due, (plen, olen)) in enumerate(zip(dues, sizes)):
        out.append(Arrival(rid, due, ids[at:at + plen].tolist(), olen))
        at += plen
    return out
