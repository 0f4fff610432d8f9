"""Mean host seconds per call of the program's ``schedule.rows`` span in
``build_window_schedule``: window bucketing, the dense/sparse split, the
stable bucket sort and the row fill."""
from bench.metrics import program_spans


def read(run):
    return program_spans.span_mean_s(run, "schedule.rows")
