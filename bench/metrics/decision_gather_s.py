"""Device seconds per call of the ops in the pipeline's
``decision_gather`` scope: the match mask gathered from slot order to
stream order."""
from bench.metrics import program_spans


def read(run):
    return program_spans.scope_s_per_call(run, "decision_gather")
