"""Mean host seconds per call of the program's ``schedule.canonical`` span
in ``build_window_schedule``: the canonical edge stream and its fetch to
the host."""
from bench.metrics import program_spans


def read(run):
    return program_spans.span_mean_s(run, "schedule.canonical")
