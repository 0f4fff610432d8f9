"""Seconds from the start of the run to the window: making the graphs,
building what set-up builds, loading or compiling the programs, and the
warm-up calls."""


def read(run):
    return run.setup_s
