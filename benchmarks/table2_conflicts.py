"""Table II analogue: JIT conflict statistics.

The paper: conflict ratio < 0.1% of edges on every dataset; max conflicts per
edge 410; most conflicting edges see < 16 conflicts. We report the identical
statistics from the tiled matcher's blocked-edge instrumentation, plus the
cross-device conflicts (lost proposals / requeues) of the distributed run.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import graph_suite, emit
from repro.core import skipper, conflict_table
from repro.core.distributed import distributed_skipper


def run(scale: str = "small"):
    rows = []
    for name, g in graph_suite(scale).items():
        _, conf = skipper(g, tile_size=32, vector_rounds=1, with_conflicts=True)
        tbl = conflict_table(np.asarray(conf))
        rows.append(emit(
            f"table2/{name}", 0.0,
            f"total={tbl['total_cnf']};edges={tbl['edges_exp_cnf']};"
            f"max={tbl['max_cnf_per_edge']};avg={tbl['avg_cnf_per_edge']:.1f};"
            f"ratio={tbl['conflict_ratio']:.5f};dist={tbl['distribution']}"
        ))
        _, st = distributed_skipper(g, block_size=512)
        rows.append(emit(
            f"table2/{name}/distributed", 0.0,
            f"proposals={int(st.proposals)};lost={int(st.lost_proposals)};"
            f"requeued={int(st.requeued)};overflow={int(st.retry_overflow)}"
        ))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
