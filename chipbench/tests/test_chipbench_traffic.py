"""The traffic generator: deterministic per seed, every seed the same
work at the same moments with other token ids, prompt lengths on the
quantum, rates as stated."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import traffic  # noqa: E402

MIXES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
NAMES = sorted(f[:-5] for f in os.listdir(MIXES) if f.endswith(".json"))


def _mix(name):
    return traffic.load(os.path.join(MIXES, f"{name}.json"))


@pytest.mark.parametrize("name", NAMES)
def test_same_seed_same_requests(name):
    a = traffic.generate(_mix(name), 2 ** 31 + 17, 20.0, 1000)
    b = traffic.generate(_mix(name), 2 ** 31 + 17, 20.0, 1000)
    assert [(r.due, r.prompt, r.output_len) for r in a] == \
        [(r.due, r.prompt, r.output_len) for r in b]


@pytest.mark.parametrize("name", NAMES)
def test_seeds_share_the_work(name):
    a = traffic.generate(_mix(name), 3, 20.0, 1000)
    b = traffic.generate(_mix(name), 4, 20.0, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    assert [(len(r.prompt), r.output_len) for r in a] == \
        [(len(r.prompt), r.output_len) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("name", NAMES)
def test_lengths_on_the_quantum_and_in_range(name):
    mix = _mix(name)
    p, o = mix["prompt_len"], mix["output_len"]
    reqs = traffic.generate(mix, 5, 30.0, 1000)
    assert reqs
    for r in reqs:
        assert len(r.prompt) % p["quantum"] == 0
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.output_len <= o["max"]
        assert all(0 <= t < 1000 for t in r.prompt)


@pytest.mark.parametrize("process", ["poisson", "mmpp"])
def test_long_run_rate(process):
    import random
    arr = {"process": process, "rate_rps": 5.0, "burst_factor": 3.0,
           "burst_duty": 0.25, "burst_period_s": 2.0}
    n = sum(1 for _ in traffic.arrival_times(arr, 2000.0,
                                             random.Random(0)))
    assert abs(n / 2000.0 - 5.0) < 0.25


def test_mmpp_bursts_carry_the_stated_share():
    import random
    arr = {"process": "mmpp", "rate_rps": 4.0, "burst_factor": 3.0,
           "burst_duty": 0.25, "burst_period_s": 2.0}
    ts = list(traffic.arrival_times(arr, 4000.0, random.Random(1)))
    in_burst = sum(1 for t in ts if t % 2.0 < 0.5)
    # 3x the rate for a quarter of the time: three quarters of arrivals
    assert abs(in_burst / len(ts) - 0.75) < 0.02
