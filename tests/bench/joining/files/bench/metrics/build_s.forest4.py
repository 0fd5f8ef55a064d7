"""build_s.forest4: host seconds of ``KNNIndex.build`` of the four-shard
forest at set-up (the trees, their stacking and placement by shard)."""


def read(run):
    return run.setup_phases.get("build")
