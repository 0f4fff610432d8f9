"""Share of the window tier's tiles that took the exact fallback: the
program's ``match.window_fallback_tiles`` counter, summed over the
window's calls, over calls x rows x tiles per row of the schedule."""
from bench.metrics import program_spans


def read(run):
    taken = program_spans.counts(run, "match.window_fallback_tiles")
    if not taken:
        return None
    s = run.schedule
    return 100.0 * sum(taken) / (len(taken) * s["num_rows"]
                                 * s["tiles_per_window"])
