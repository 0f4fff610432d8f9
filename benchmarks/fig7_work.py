"""Fig. 7 analogue: memory accesses per edge (work efficiency).

The paper: SGMM 0.3-0.8, Skipper 1.2-3.4 (geomean 2.1), SIDMM 16.7-26.9
(geomean 21.0). Our counters instrument the same quantity — state-array
loads/stores + topology reads — inside each algorithm.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import graph_suite, emit
from repro.core import sgmm, skipper, sidmm, ems_israeli_itai
from repro.core.distributed import distributed_skipper


def run(scale: str = "small"):
    rows = []
    ratios = {"skipper": [], "sidmm": []}
    for name, g in graph_suite(scale).items():
        m = g.num_edges
        for algo, fn in [
            ("sgmm", lambda: sgmm(g)),
            ("skipper", lambda: skipper(g, tile_size=32, vector_rounds=1)[0]),
            ("sidmm", lambda: sidmm(g, batch_size=4096)),
            ("ems_ii", lambda: ems_israeli_itai(g)),
            # distributed counters use the same real-edge-work accounting
            # (sentinel slots scanned during drain rounds count nothing),
            # so this row is directly comparable to skipper's
            ("skipper_dist", lambda: distributed_skipper(g, block_size=4096)[0]),
        ]:
            r = fn()
            per_edge = float(r.counters.total_accesses) / m
            rounds = int(r.counters.rounds)
            if algo in ratios:
                ratios[algo].append(per_edge)
            rows.append(
                emit(f"fig7/{name}/{algo}", 0.0,
                     f"accesses_per_edge={per_edge:.2f};rounds={rounds}")
            )
    for algo, vals in ratios.items():
        geo = float(np.exp(np.mean(np.log(vals))))
        rows.append(emit(f"fig7/geomean/{algo}", 0.0, f"accesses_per_edge={geo:.2f}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
