"""Mean host seconds per call of the program's ``match.to_device`` span in
``skipper_match``: the copy of the schedule's arrays to the device, until
they have landed."""
from bench.metrics import program_spans


def read(run):
    return program_spans.span_mean_s(run, "match.to_device")
