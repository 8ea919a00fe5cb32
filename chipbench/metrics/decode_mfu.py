"""Model step: model FLOPs of the decode tokens emitted in the window
(chipbench/flops.py, at each token's context) over the summed time of
the decode steps that end in the window, times the chips' bf16 peak."""


def read(run):
    work = sum(run.decode_flops(ctx) for t, _rid, ctx in run.emissions
               if run.in_window(t))
    secs = sum(d for t, d, _c in run.steps if run.in_window(t))
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    if not work or not secs or not peak == peak:
        return None
    return 100.0 * work / (secs * peak)
