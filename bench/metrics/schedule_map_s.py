"""Mean host seconds per call of the program's ``schedule.stream_map``
span in ``build_window_schedule``: the stream-to-slot map that the
decision gathers read."""
from bench.metrics import program_spans


def read(run):
    return program_spans.span_mean_s(run, "schedule.stream_map")
