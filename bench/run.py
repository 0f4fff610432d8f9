"""Run one cell of the benchmark once, on the chip this process finds.

    python bench/run.py --workload g500-s22.warm --seed 7 --seconds 10 --trace 0

Set-up makes the cell's graphs from ``--seed``, builds what its traffic
builds in set-up and warms up every program the window runs; then one
caller drives the program for ``--seconds`` seconds. ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` profiles the window and
reports its per-layer metrics. Once the window has closed, the plain
reference checks the kept answers. The last line of stdout is the result
as one JSON object; the numbers the check compared, with their limits, are
the last lines of stderr.

Exits 2, printing no result, where JAX finds no TPU, fewer chips than the
cell asks for, or a chip that ``bench/peaks.json`` does not list, and 1
where the program is missing.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def fail(msg: str, code: int) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"the program (src/repro) is not in {ROOT}", 1)
    from bench import harness

    cell = harness.load_cell(args.workload)

    import jax

    # The persistent compilation cache at a fixed path inside the checkout,
    # or where JAX_COMPILATION_CACHE_DIR says: only a cell's first run in a
    # checkout compiles. Small programs are cached too.
    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform!r}", 2)
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devices)}", 2)
    try:
        peaks = harness.lookup_peaks(devices[0].device_kind)
    except KeyError as e:
        fail(str(e), 2)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, peaks)
    harness.print_result(result)


if __name__ == "__main__":
    main()
