"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--quick] [--json]

Prints human-readable tables plus ``name,us_per_call,derived`` CSV lines
(collected at the end under == CSV ==).  ``--json`` additionally writes
``BENCH_e2e.json`` (TTFT p50/p99 + throughput per scheduler per scenario
from the e2e_pd bench) so the perf trajectory is machine-trackable across
PRs; ``--quick`` asks benches that support it for a reduced sweep (the CI
smoke path).  A bench that raises is reported with its traceback, the
remaining benches still run, and the run exits 1.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
import traceback
from typing import List

BENCHES = [
    ("queueing_theory", "§3.2 T/2 vs T/2N"),
    ("ttft_vs_load", "Fig 6a/6b TTFT vs load"),
    ("chunk_util", "Table 1 chunk utilization"),
    ("decode_balance", "Fig 7/8 decode balance"),
    ("cache_aware", "§4.2.2 cache-aware PBAA"),
    ("e2e_pd", "E2E 3P1D pipeline w/ KV transfer"),
    ("cross_arch", "SBS across architecture families"),
    ("microbench", "scheduler decision latency"),
    ("roofline", "§Roofline dry-run table"),
]

JSON_PATH = "BENCH_e2e.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[name for name, _ in BENCHES])
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps for benches that support it")
    ap.add_argument("--json", action="store_true",
                    help=f"write {JSON_PATH} with the e2e_pd payload")
    args = ap.parse_args()

    csv: List[str] = ["name,us_per_call,derived"]
    payload = None
    failed: List[str] = []
    for mod_name, desc in BENCHES:
        if args.only and args.only != mod_name:
            continue
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
        print(f"\n{'='*72}\n== {mod_name}: {desc}\n{'='*72}", flush=True)
        t0 = time.time()
        kwargs = {}
        if "quick" in inspect.signature(mod.main).parameters:
            kwargs["quick"] = args.quick
        try:
            rows = mod.main(lambda s: print(s, flush=True), **kwargs)
            csv.extend(rows or [])
            if getattr(mod, "JSON_PAYLOAD", None) is not None:
                payload = mod.JSON_PAYLOAD
        except Exception:
            traceback.print_exc()
            print(f"BENCH FAILED: {mod_name}")
            csv.append(f"{mod_name},NaN,FAILED")
            failed.append(mod_name)
        print(f"[{mod_name} took {time.time()-t0:.1f}s]")
    if args.json:
        if payload is None:
            print(f"--json: no payload collected (run the e2e_pd bench)",
                  file=sys.stderr)
            sys.exit(1)
        # merge over the existing file: sections owned by other writers
        # (e.g. the real-plane smoke's `real_plane`) survive a sim rerun
        merged = {}
        if os.path.exists(JSON_PATH):
            try:
                with open(JSON_PATH) as f:
                    merged = json.load(f)
            except (OSError, ValueError):
                merged = {}
        merged.update(payload)
        with open(JSON_PATH, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
        print(f"\nwrote {os.path.abspath(JSON_PATH)}")
    print(f"\n{'='*72}\n== CSV ==\n{'='*72}")
    for line in csv:
        print(line)
    if failed:
        print(f"failed benches: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
