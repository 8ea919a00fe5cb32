"""Engine layer: host-clock seconds of the decode steps that end in the
window (each ends in the host reading the argmax), summed and divided by
their count.  On one chip a step also waits behind the prefill programs
that share the device."""


def read(run):
    durs = [d for t, d, _c in run.steps if run.in_window(t)]
    return sum(durs) / len(durs) * 1000.0 if durs else None
