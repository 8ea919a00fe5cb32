"""Knee sweep: serve a cell's traffic at several rates in one process.

    python chipbench/sweep.py --workload <cell> --rates 2,3,4 \
        --seconds 30 --seed <n>

Sets up once (weights, server, warm-up), then for each rate serves the
cell's mix (its own lead-in, window and tail) with `arrivals.rate_rps`
replaced, and prints one JSON line per rate: the host-clock metrics in
`READ`, the requests due and failed, the backlog at
the window's start and end (`harness.backlog`), at each whole second of
the window the requests in the system and those waiting (without a
first token, or with one but not yet joined to the decode pool), the
waiting count's growth over the window by a least-squares line, how
late the generator ran at most, and the most device memory in use.

A rate holds (`"holds": true`) where the waiting count grows by at most
2 over the window and ends at 2 or fewer: no request waits for a
resource longer than a handoff takes.  The in-system count is no test:
the mix's arrivals and lengths are fixed in their order, so it follows
the work offered, which rises and falls over a window of this length
with no backlog at all.  The knee is the highest rate that holds.
Needs the cell's chips, like run.py.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READ = ("ttft_p50_ms", "itl_p95_ms", "output_tok_s",
        "sched_wait_ms_p50", "decode_step_ms")


def growth(counts) -> float:
    """The rise over the whole series of a least-squares line through
    `counts` (one per second)."""
    n = len(counts)
    if n < 2:
        return 0.0
    xm, ym = (n - 1) / 2, sum(counts) / n
    sxy = sum((i - xm) * (c - ym) for i, c in enumerate(counts))
    sxx = sum((i - xm) ** 2 for i in range(n))
    return sxy / sxx * (n - 1)


class MemorySampler(threading.Thread):
    """The most bytes in use on the first chip while a rate is served."""

    def __init__(self, device):
        super().__init__(daemon=True)
        self.device, self.top = device, 0
        self.halt = threading.Event()

    def run(self):
        while not self.halt.wait(0.05):
            self.top = max(self.top, self.device.memory_stats()
                           ["bytes_in_use"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    try:
        ses = harness.open_session(ROOT, args.workload)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    cell = ses.cell
    params, srv = harness.build_server(cell, args.seed)
    param_bytes = harness.tree_bytes(params)
    del params
    harness.warm_up(cell, srv)
    print(f"[sweep] set-up {time.monotonic() - T_PROCESS:.1f} s",
          file=sys.stderr)
    metrics = [{"name": n, "unit": ""} for n in READ]
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate_rps"] = rate
        mem = MemorySampler(ses.devices[0])
        mem.start()
        try:
            run, tokens, rec = harness.serve_window(
                ses, srv, args.seed, args.seconds, param_bytes, mix=mix)
        finally:
            mem.halt.set()
            mem.join()
        due = run.due_in_window
        vals = {k: v["value"] for k, v in
                harness.read_metrics(run, metrics).items()}
        each = [harness.backlog(run, run.window[0] + i)
                for i in range(int(args.seconds) + 1)]
        waits = [b["no_first_token"] + b["not_joined"] for b in each]
        rise = growth(waits)
        print(json.dumps({
            "workload": args.workload, "rate_rps": rate, **vals,
            "due": len(due),
            "failed": len(due) - len(harness.finished(run, tokens)),
            "start": each[0], "end": each[-1],
            "in_system_each_s": [b["in_system"] for b in each],
            "waiting_each_s": waits, "waiting_growth": rise,
            "holds": rise <= 2.0 and waits[-1] <= 2,
            "late_max_ms": max(rec.late, default=0.0) * 1e3,
            "bytes_in_use_max": mem.top}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
