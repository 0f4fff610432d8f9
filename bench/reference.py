"""The plain reference that decides ``correct``.

A maximal matching has no single right answer: the paper's claim is that
any order of the per-edge steps ends in *a* valid maximal matching. So the
reference does not compute one to compare with; it decides, in plain
numpy and from the benchmark's own edge list, whether the program's answer
is one — the guarantees the configurations state:

* ``double_matched``: vertices covered by more than one selected edge (a
  selected self-loop covers its vertex twice). Validity: limit 0.
* ``uncovered_edges``: edges that are not self-loops and have both
  endpoints uncovered. Maximality: limit 0.
* ``state_mismatches``: vertices whose returned state is not MCHD (2)
  where the mask covers them and ACC (0) where it does not. Limit 0.

It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

ACC, MCHD = 0, 2
LIMITS = {"double_matched": 0, "uncovered_edges": 0, "state_mismatches": 0}


def check(u: np.ndarray, v: np.ndarray, num_vertices: int,
          mask: np.ndarray, state: np.ndarray) -> dict:
    """Counts of broken guarantees in one answer (``mask`` in stream order,
    ``state`` by original vertex id)."""
    m, n = u.shape[0], num_vertices
    mask = np.asarray(mask)
    state = np.asarray(state)
    if mask.shape != (m,) or state.shape != (n,):
        # an answer of the wrong shape breaks every guarantee
        return {"double_matched": n, "uncovered_edges": m,
                "state_mismatches": n}
    mask = mask.astype(bool)
    cover = (np.bincount(u[mask], minlength=n)
             + np.bincount(v[mask], minlength=n))
    covered = cover > 0
    free = ~mask & (u != v) & ~covered[u] & ~covered[v]
    expect = np.where(covered, MCHD, ACC)
    return {
        "double_matched": int(np.count_nonzero(cover > 1)),
        "uncovered_edges": int(np.count_nonzero(free)),
        "state_mismatches": int(np.count_nonzero(state != expect)),
    }


def worst(counts: list) -> dict:
    """The largest reading of each number over several answers."""
    return {k: max((c[k] for c in counts), default=0) for k in LIMITS}


def passes(readings: dict) -> bool:
    return all(readings[k] <= lim for k, lim in LIMITS.items())
