"""The bytes each tier of the matcher has to move, whatever implements it.

A tier's work is 9 B for each valid edge it decides (two int32 endpoints
in, one decision byte out) and 2 B for each vertex of the state it covers
(the one-byte state read once and written once): ``num_rows * window``
vertices for the window tier, ``num_windows * window`` for the boundary
tier. Padding slots and the kernels' own matmuls are not work, and no
operation is counted, so the bound is the chip's HBM bandwidth.
"""
from __future__ import annotations

EDGE_BYTES = 9
STATE_BYTES = 2


def window_tier(s: dict) -> int:
    return (EDGE_BYTES * s["num_windowed"]
            + STATE_BYTES * s["num_rows"] * s["window"])


def boundary_tier(s: dict) -> int:
    return (EDGE_BYTES * (s["num_valid"] - s["num_windowed"])
            + STATE_BYTES * s["num_windows"] * s["window"])


def roofline_share(run, kernel: str, nbytes: int):
    """Percent: the least time the window's calls could take for
    ``nbytes`` each at peak HBM bandwidth, over the summed device time of
    ``kernel``'s events in the trace; None where the trace has none."""
    if run.trace is None:
        return None
    seconds, events = run.trace.kernel(kernel)
    if not events or seconds <= 0 or nbytes <= 0:
        return None
    least = len(run.calls) * nbytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
