"""Process start to window start: loading, weights, compiling or cache
loading, warm-up and the lead-in of traffic."""


def read(run):
    return run.setup_s
