"""Gigabytes that ``skipper_match`` copied from the host per call (the
program's ``match.h2d_bytes`` counter, mean over the window's calls)."""
from bench.metrics import program_spans


def read(run):
    copied = program_spans.counts(run, "match.h2d_bytes")
    return sum(copied) / len(copied) / 1e9 if copied else None
