"""batch_qps: queries answered over the whole window (host clock, from the
first call's start to the last call's end)."""


def read(run):
    if run.window_s <= 0:
        return None
    return sum(c.work for c in run.calls) / run.window_s
