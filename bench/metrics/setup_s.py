"""setup_s: host seconds from the start of the run to the end of warm-up
(data from the seed, build, warm-up, compile-cache loads)."""


def read(run):
    return run.setup_s
