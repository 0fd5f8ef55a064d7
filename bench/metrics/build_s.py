"""build_s: host seconds of ``KNNIndex.build`` at set-up (tree build, leaf
slabs, device placement)."""


def read(run):
    return run.setup_phases.get("build")
