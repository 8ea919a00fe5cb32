"""Model step: the least time the decode steps that end in the window
could take (chipbench/flops.py `least_step_s`: every served weight and
each live row's cache read once at the HBM bandwidth, or the step's
model FLOPs at the bf16 peak, whichever is longer), over the time they
took on the host clock."""


def read(run):
    steps = [(d, ctxs) for t, d, ctxs in run.steps
             if run.in_window(t) and ctxs]
    took = sum(d for d, _c in steps)
    if not took:
        return None
    least = sum(run.step_least_s(ctxs) for _d, ctxs in steps)
    if not least == least:          # no peaks for this device
        return None
    return 100.0 * least / took
