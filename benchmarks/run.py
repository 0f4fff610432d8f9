"""Benchmark aggregator: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV lines (benchmarks.common.emit).

  table1_speedup    — Table I: Skipper vs SIDMM wall time (+SGMM ref)
  fig7_work         — Fig. 7: memory accesses per edge
  fig10_gain        — Fig. 10/11: serial slowdown vs SGMM
  table2_conflicts  — Table II: JIT conflict statistics (+distributed)
  kernel_bench      — matcher/router throughput micro-benches

Run ``--scale large`` for the multi-million-edge suite (slower).
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["small", "large"])
    ap.add_argument("--only", default=None)
    ap.add_argument("--matcher", default="both",
                    choices=["both", "jnp", "windowed", "distributed"],
                    help="which matcher path kernel_bench times (jnp tiled, "
                         "device-resident windowed pipeline, or the "
                         "4-device distributed matcher; on CPU run with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    ap.add_argument("--reorder", default="degree",
                    choices=["none", "degree", "bfs", "greedy"],
                    help="locality reordering for the windowed schedule")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        table1_speedup, fig7_work, fig10_gain, table2_conflicts,
        kernel_bench,
    )

    modules = {
        "table1": table1_speedup,
        "fig7": fig7_work,
        "fig10": fig10_gain,
        "table2": table2_conflicts,
        "kernels": kernel_bench,
    }
    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules.items():
        if args.only and name != args.only:
            continue
        try:
            if name == "kernels":
                mod.run(args.scale, matcher=args.matcher, reorder=args.reorder)
            else:
                mod.run(args.scale)
        except Exception as e:
            failed.append(name)
            traceback.print_exc()
            print(f"{name},0.0,ERROR:{type(e).__name__}", flush=True)
    if failed:
        sys.exit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
