"""Readings for the limit of `logit_err_p90`: the program's and the
control's, over several seeds, in one process.

    python chipbench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 --lead-in 10

One server is built and warmed once; for each seed it is served the
weights made from that seed and the cell's traffic, open-loop, for a
short window at the cell's own load (the mix's rate, lengths and drain
limit; `--lead-in` shortens the lead-in), and a sample of finished
requests is drawn as a run draws it.  Then, with the served weights
freed, two readings on the same prompts and served tokens:

    program   what a run compares: the program's captured logits
              against the plain reference;
    control   the plain reference recomputed at the configuration's
              `check.control_matmul` precision, one step below the one
              it states, read on its own top-K entries.

One JSON line per seed: the number compared (`logit_err_p90`, requests
weighted alike), plain per-row gap quantiles, tokens off their row's
argmax, tokens compared, and `correct` as a run decides it
(`check.decide`, the configuration's limits) for each; the control only
on the first CONTROL_SEEDS seeds.  The limit lies
above the program's largest reading and below the control's smallest.
Needs the cell's chips.
"""
import argparse
import copy
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL_SEEDS = 5       # a limit wants the control on three seeds or more


def summary(reading):
    import numpy as np
    from chipbench import check
    g = reading["gaps"]
    qs = [float(np.percentile(g, q)) for q in (50, 75, 90, 99, 100)] \
        if g.size else []
    err = check.weighted_percentile(g, reading["weights"], check.LOGIT_ERR_Q)
    return {"logit_err_p90": err, "gap_p50_p75_p90_p99_max": qs,
            "off": reading["off"],
            "tokens": reading["tokens"], "missing": reading["missing"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--lead-in", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from chipbench import check, harness
    try:
        ses = harness.open_session(ROOT, args.workload)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    cell = ses.cell
    chk = cell.conf["check"]
    mix = copy.deepcopy(cell.mix)
    if args.lead_in is not None:
        mix["lead_in_s"] = args.lead_in
    seeds = [int(x) for x in args.seeds.split(",")]
    params, srv = harness.build_server(cell, seeds[0])
    param_bytes = harness.tree_bytes(params)
    del params
    cap = check.LogitCapture(srv)
    harness.warm_up(cell, srv)
    serve = jax.jit(lambda k: cell.model.serve_weights(
        cell.conf, cell.model.init_weights(cell.conf, k)))
    for seed in seeds:
        t0 = time.monotonic()
        if seed != seeds[0]:
            srv.spec.params = serve(harness.make_key(seed))
        cap.reset()
        run, tokens, _rec = harness.serve_window(
            ses, srv, seed, args.seconds, param_bytes, mix=mix)
        done = harness.finished(run, tokens)
        sample = check.pick_sample(done, chk["sample_requests"], seed)
        prog = check.program_rows(cap, sample, tokens)
        srv.spec.params = None
        gc.collect()
        ref = check.Reference(cell.model, cell.conf,
                              harness.reference_weights(cell, seed))
        factor = cell.model.logit_factor(cell.conf)
        p = check.compare(ref, sample, tokens, prog, factor)
        line = {"workload": args.workload, "seed": seed,
                "due": len(run.due_in_window), "done": len(done),
                "program": summary(p),
                "program_correct": check.decide(chk, p)[0],
                "program_decide": check.decide(chk, p)[1]}
        if seeds.index(seed) < CONTROL_SEEDS:
            c = check.compare(ref, sample, tokens, prog, factor,
                              control=chk["control_matmul"])
            line.update(control=summary(c),
                        control_correct=check.decide(chk, c)[0],
                        control_decide=check.decide(chk, c)[1])
        line["seconds"] = time.monotonic() - t0
        print(json.dumps(line), flush=True)
        del ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
