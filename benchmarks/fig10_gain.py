"""Fig. 10/11 analogue: Parallelization Gain and Serial Slowdown.

The paper defines gain = t_sequential / t_parallel and slowdown =
t_parallel_1thread / t_sequential. On this 1-core container wall-clock
parallel gain is not measurable, so we report the two *work-side* components
the paper identifies as its drivers (§VI-D): excess memory accesses
(slowdown proxy — Skipper ~1.4x vs SIDMM ~10.7x in the paper) plus the
single-thread wall-time ratio of each parallel algorithm against SGMM, which
IS the paper's Serial Slowdown (Fig. 11), measurable here exactly.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import graph_suite, time_call, emit
from repro.core import sgmm, skipper, sidmm


def run(scale: str = "small"):
    rows = []
    slow_skip, slow_sidmm = [], []
    for name, g in graph_suite(scale).items():
        t_sgmm = time_call(lambda: sgmm(g).match_mask)
        t_skip = time_call(lambda: skipper(g, tile_size=32, vector_rounds=1)[0].match_mask)
        t_sidmm = time_call(lambda: sidmm(g, batch_size=4096).match_mask)
        s1 = t_skip / t_sgmm
        s2 = t_sidmm / t_sgmm
        slow_skip.append(s1)
        slow_sidmm.append(s2)
        rows.append(emit(f"fig11/{name}/skipper_serial_slowdown", t_skip, f"{s1:.2f}x"))
        rows.append(emit(f"fig11/{name}/sidmm_serial_slowdown", t_sidmm, f"{s2:.2f}x"))
    rows.append(emit("fig11/geomean/skipper", 0.0,
                     f"{float(np.exp(np.mean(np.log(slow_skip)))):.2f}x"))
    rows.append(emit("fig11/geomean/sidmm", 0.0,
                     f"{float(np.exp(np.mean(np.log(slow_sidmm)))):.2f}x"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
