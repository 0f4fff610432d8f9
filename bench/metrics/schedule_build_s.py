"""Mean host seconds of the ``schedule_build`` span (the program's
``build_window_schedule``) per call in the traced window."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.span_s.get("schedule_build")
    return sum(spans) / len(spans) if spans else None
