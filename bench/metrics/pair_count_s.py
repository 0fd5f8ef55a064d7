"""pair_count_s: window seconds over the histograms completed in it."""


def read(run):
    n = sum(c.work for c in run.calls)
    return run.window_s / n if n else None
