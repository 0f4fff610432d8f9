"""Share of the window's calls whose schedule was already on the device:
100 x the sum of the program's ``match.schedule_resident`` counter (1 where
``skipper_match`` found its schedule's device copy resident, 0 where it
copied the schedule) over the window's calls, over their number. None where
nothing was counted."""
from bench.metrics import program_spans


def read(run):
    resident = program_spans.counts(run, "match.schedule_resident")
    return 100.0 * sum(resident) / len(resident) if resident else None
