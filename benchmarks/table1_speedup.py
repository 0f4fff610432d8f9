"""Table I analogue: Skipper vs SIDMM execution time (+SGMM reference).

The paper reports 4.9-15.6x (geomean 8.0x) over SIDMM on 64 threads with
2.4G-224G-edge graphs; here both algorithms are jit-compiled XLA:CPU programs
over laptop-scale graphs of the same families. The measured quantity is the
same: end-to-end matching time after the topology is in memory.

Tile size: the JIT-conflict mask is O(T^2) per T-edge tile, i.e. O(T) per
edge — lanes on a TPU VPU, real scalar work on 1-core CPU. Benchmarks use
the CPU-optimal (tile=32, rounds=1); the library default (512) is the
MXU/VPU-aligned choice (EXPERIMENTS §Perf iteration 12).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import graph_suite, time_call, emit
from repro.core import sgmm, skipper, sidmm, assert_matching


def run(scale: str = "small"):
    rows = []
    speedups = []
    for name, g in graph_suite(scale).items():
        t_skip = time_call(lambda: skipper(g, tile_size=32, vector_rounds=1)[0].match_mask)
        t_sidmm = time_call(lambda: sidmm(g, batch_size=4096).match_mask)
        t_sgmm = time_call(lambda: sgmm(g).match_mask)
        assert_matching(g, skipper(g, tile_size=32, vector_rounds=1)[0].match_mask, name)
        sp = t_sidmm / t_skip
        speedups.append(sp)
        rows.append(emit(f"table1/{name}/skipper", t_skip, f"|E|={g.num_edges}"))
        rows.append(emit(f"table1/{name}/sidmm", t_sidmm, f"speedup={sp:.2f}x"))
        rows.append(emit(f"table1/{name}/sgmm_1t", t_sgmm, "sequential_reference"))
    geo = float(np.exp(np.mean(np.log(speedups))))
    rows.append(emit("table1/geomean_speedup_vs_sidmm", 0.0, f"{geo:.2f}x"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
