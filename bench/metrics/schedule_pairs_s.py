"""Mean host seconds per call of the program's ``schedule.pairs`` span in
``build_window_schedule``: the global tier's block-pair grouping."""
from bench.metrics import program_spans


def read(run):
    return program_spans.span_mean_s(run, "schedule.pairs")
