"""Device seconds per call of the ops in the pipeline's
``state_unpermute`` scope: the vertex state gathered back to the original
vertex ids through ``perm``."""
from bench.metrics import program_spans


def read(run):
    return program_spans.scope_s_per_call(run, "state_unpermute")
