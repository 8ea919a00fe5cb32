"""Run one benchmark cell on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints diagnostics on standard error (set-up by phase, generator
lateness, memory, backlog, then the numbers compared for `correct`, each
beside its limit, last) and, as the last line of standard output, one
JSON object: correct, attempted, failed, metrics (end-to-end with
--trace 0, per-layer with --trace 1), device, with --trace 1 a
breakdown, and the checks last.  Exits 2 with no result where the
serving stack (`src/`) is missing, or JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import repro.serving.server  # noqa: F401  the system under test
    except ImportError as e:
        print(f"chipbench: no serving stack to run: {e}", file=sys.stderr)
        return 2
    from chipbench import harness
    try:
        out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
