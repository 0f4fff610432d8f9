"""Input edges matched per second, in millions: edges of one call times the
window's calls, over the wall time of those calls (host clock)."""


def read(run):
    return run.medges_per_s
