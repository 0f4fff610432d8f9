"""What the program recorded about itself during the window's calls: its
spans and counters (``repro.spans``, filtered to the first call's start
and the last call's end) and the named scopes of the pipeline's device
ops (``ops.op_scopes``). A program that records none of them, or a run in
which nothing was recorded, gives None."""
from __future__ import annotations


def snapshot(run):
    try:
        from repro import spans
    except ImportError:
        return None
    if not run.calls:
        return None
    return spans.snapshot(round(run.calls[0][0] * 1e9),
                          round(run.calls[-1][1] * 1e9))


def span_mean_s(run, name: str):
    """Mean seconds of the program's span ``name`` in the window."""
    snap = snapshot(run)
    if snap is None:
        return None
    took = [(t1 - t0) * 1e-9 for n, t0, t1 in snap.spans if n == name]
    return sum(took) / len(took) if took else None


def counts(run, name: str) -> list:
    """Every value the program counted under ``name`` in the window."""
    snap = snapshot(run)
    return [] if snap is None else [v for n, _, v in snap.counts if n == name]


def scope_s_per_call(run, scope: str):
    """Device seconds per call of the traced ops that the program's
    pipeline ran in its named scope ``scope``.

    The trace names an op by its bare HLO instruction name, summed over
    every program that ran in the window, and ``op_scopes()`` maps the
    instructions of the newest pipeline alone: this holds only where that
    pipeline is the one program in the window that uses those names. An
    op that ran other than once per call breaks that, and the scope then
    reads None rather than another program's time."""
    if run.trace is None or not run.calls:
        return None
    from repro.kernels.skipper_match import ops

    if not hasattr(ops, "op_scopes"):
        return None
    _, scopes = ops.op_scopes()
    mine = [op for op in run.trace.op_s if scopes.get(op) == scope]
    if not mine or any(run.trace.op_calls[op] != len(run.calls)
                       for op in mine):
        return None
    return sum(run.trace.op_s[op] for op in mine) / len(run.calls)
