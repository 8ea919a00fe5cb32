"""Scheduler-core micro-benchmarks: allocation-algorithm costs at production
batch sizes (the scheduler must tick every I_opt ≈ 10-80 ms; its own
decision latency has to be orders of magnitude below that).  Host-side
only: the sharded plane's collectives are exercised on real devices by
``chip_smoke.py --chips 4``."""
from __future__ import annotations

import random
import time
from typing import List

from repro.core.decode_alloc import schedule_decode_batch
from repro.core.prefill_alloc import pbaa
from repro.core.types import DecodeDPState, DPState, Request


def _time(fn, reps=20):
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6   # µs


def main(report) -> List[str]:
    rows: List[str] = []
    rng = random.Random(0)
    report("\n## Scheduler micro-benchmarks (decision latency)")
    report(f"{'op':>34} {'us/call':>10}")

    def bench_pbaa():
        dps = [DPState(i, 0, 16384) for i in range(8)]
        reqs = [Request(rid=i, arrival_time=0,
                        input_len=rng.randrange(100, 8000))
                for i in range(64)]
        pbaa([], reqs, dps)
    us = _time(bench_pbaa)
    report(f"{'PBAA (64 reqs × 8 DPs)':>34} {us:>10.1f}")
    rows.append(f"micro/pbaa_64x8,{us:.1f},")

    def bench_decode():
        units = [DecodeDPState(i, 0, batch=rng.randrange(40),
                               kv_tokens=rng.randrange(100_000))
                 for i in range(32)]
        reqs = [Request(rid=i, arrival_time=0,
                        input_len=rng.randrange(100, 8000))
                for i in range(64)]
        schedule_decode_batch(reqs, units)
    us = _time(bench_decode)
    report(f"{'IQR-lex decode (64 reqs × 32 DPs)':>34} {us:>10.1f}")
    rows.append(f"micro/decode_64x32,{us:.1f},")

    from repro.core.prefix_cache import RadixTree
    t = RadixTree(block=16)
    seqs = [tuple(rng.randrange(1000) for _ in range(512)) for _ in range(64)]
    for s in seqs[:32]:
        t.insert(s)

    def bench_radix():
        for s in seqs:
            t.match(s)
    us = _time(bench_radix) / 64
    report(f"{'radix match (512 tokens)':>34} {us:>10.1f}")
    rows.append(f"micro/radix_match_512,{us:.1f},")

    # BlockPool free store: heapq (current) vs the sorted-list it
    # replaced.  Both are deterministic lowest-id-first; the access
    # pattern that matters is serving churn — small per-request
    # alloc/free against a LARGE mostly-free pool, where the list
    # re-sorts the whole store on every free (O(N log N)) and copies it
    # on every alloc (O(N)), while the heap pays O(req log N).
    from repro.serving.kv_pool import BlockPool

    N_BLOCKS, REQ_BLOCKS, ROUNDS = 65536, 8, 256

    class _SortedListStore:
        """The pre-heap free store, inlined for comparison."""
        def __init__(self, num_blocks):
            self.free = list(range(1, num_blocks))
        def alloc(self, n):
            out, self.free = self.free[:n], self.free[n:]
            return out
        def free_blocks(self, ids):
            self.free = sorted(self.free + list(ids))

    def _churn(alloc, free):
        crng = random.Random(42)
        held = []
        for _ in range(ROUNDS):
            held.append(alloc(REQ_BLOCKS))
            if len(held) > 64:
                free(held.pop(crng.randrange(len(held))))

    pool = BlockPool(N_BLOCKS, 16)
    store = _SortedListStore(N_BLOCKS)
    for name, fn in (
            ("pool_heap", lambda: _churn(pool.alloc, pool.free)),
            ("pool_sorted", lambda: _churn(store.alloc,
                                           store.free_blocks))):
        us = _time(fn, reps=5)
        report(f"{f'{name} churn (8-blk reqs, 64K pool)':>34} {us:>10.1f}")
        rows.append(f"micro/{name}_churn_64k,{us:.1f},")

    return rows
