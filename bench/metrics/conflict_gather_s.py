"""Device seconds per call of the ops in the pipeline's
``conflict_gather`` scope: the per-edge conflicts gathered from slot order
to stream order, and the ``Counters`` summed from them."""
from bench.metrics import program_spans


def read(run):
    return program_spans.scope_s_per_call(run, "conflict_gather")
