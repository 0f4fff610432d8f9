"""Smoke run of the Skipper matcher on TPU.

One chip (the default)::

    python chip_smoke.py

builds a Graph500 Kronecker graph (``rmat_graph(22, 16, seed=1)``: a, b, c
= 0.57, 0.19, 0.19, 4,194,304 vertices, 67,108,864 edges) and its two-tier
window schedule (W=2048, T=256, degree reorder), then runs
``skipper_match(schedule=..., backend="pallas", interpret=False)``: the
window-tier kernel and the block-pair boundary kernel inside one jit. One
warm-up call, then a few timed calls. Every result must be a valid, maximal
matching, and its mask and state must be bit-identical to the XLA twin
(``backend="xla"``) on the same schedule and chip.

Four chips::

    python chip_smoke.py --four-chips

runs only ``distributed_skipper`` on the locality-sharded schedule over the
four devices of one host, and the one-chip ``skipper_match`` it is compared
with: valid and maximal, ``DistStats.ok``, window-tier decisions equal to
one chip, and the run spread over four devices.

Progress lines go to stdout; the last line is one JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure, or a JAX that finds no TPU, exits non-zero without it. The
times printed are smoke timings, not benchmark results. One process, no
children: the process that touches JAX holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

WINDOW, TILE, BLOCK = 2048, 256, 512
CALLS = 3  # timed one-chip calls after the warm-up


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def build(scale: int):
    from repro.graphs import build_window_schedule, rmat_graph

    t0 = time.perf_counter()
    g = rmat_graph(scale, 16, seed=1)
    t1 = time.perf_counter()
    sched = build_window_schedule(
        g, window=WINDOW, tile_size=TILE, reorder="degree"
    )
    t2 = time.perf_counter()
    log(f"graph: Graph500 RMAT scale {scale}, edgefactor 16: "
        f"{g.num_vertices} vertices, {g.num_edges} edges "
        f"(host generation {t1 - t0:.1f} s)")
    log(f"schedule: W={WINDOW} T={TILE} reorder=degree: "
        f"{sched.num_windows} windows, {sched.num_rows} dense rows x "
        f"{sched.tiles_per_window} tiles, "
        f"{sched.num_boundary_padded // TILE} global-tier tiles "
        f"({sched.num_boundary_padded} padded slots), "
        f"intra {sched.intra_fraction:.4f} (host build {t2 - t1:.1f} s)")
    return g, sched


def check_valid_maximal(g, mask, label: str) -> int:
    import jax

    from repro.core import check_matching

    chk = jax.device_get(check_matching(g, mask))
    if not (bool(chk["valid"]) and bool(chk["maximal"])):
        fail(f"{label}: valid={bool(chk['valid'])} "
             f"maximal={bool(chk['maximal'])}")
    return int(chk["num_matches"])


def one_chip(g, sched) -> None:
    """skipper_match on the Pallas path: warm-up, timed calls, checks."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.skipper_match import skipper_match

    def pallas():
        return skipper_match(schedule=sched, backend="pallas",
                             interpret=False)

    first, t_first = timed(pallas)
    results, times = [], []
    for _ in range(CALLS):
        res, t = timed(pallas)
        results.append(res)
        times.append(t)
    log(f"pallas: first call (compile + run) {t_first:.2f} s; "
        f"compile estimate {t_first - min(times):.2f} s")
    log("pallas per-call seconds (smoke timing, not a benchmark): "
        + ", ".join(f"{t:.3f}" for t in times))

    xla, t_xla = timed(lambda: skipper_match(schedule=sched, backend="xla"))
    log(f"xla twin: first call (compile + run) {t_xla:.2f} s")
    for k, res in enumerate([first] + results):
        n = check_valid_maximal(g, res.match_mask, f"pallas call {k}")
        same = bool(jax.device_get(
            jnp.array_equal(res.match_mask, xla.match_mask)
            & jnp.array_equal(res.state, xla.state)
        ))
        if not same:
            fail(f"pallas call {k}: mask/state differ from the xla twin")
    log(f"matches: {n} (valid, maximal; all {CALLS + 1} pallas results "
        f"bit-identical to the xla twin)")


def four_chips(g, sched) -> None:
    """distributed_skipper over 4 devices vs one-chip skipper_match."""
    import jax
    import numpy as np

    from repro.core.distributed import distributed_skipper
    from repro.graphs import partition_schedule
    from repro.kernels.skipper_match import skipper_match

    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(devs)}")
    mesh = jax.make_mesh(
        (4,), ("data",), devices=devs[:4],
        axis_types=(jax.sharding.AxisType.Auto,),
    )
    ds = partition_schedule(sched, 4, BLOCK)

    def dist():
        return distributed_skipper(
            mesh=mesh, device_schedule=ds, block_size=BLOCK,
            tile_size=TILE, backend="pallas", interpret=False,
        )

    (rd, st), t_first = timed(dist)
    (rd, st), t = timed(dist)
    log(f"distributed (4 devices): first call (compile + run) "
        f"{t_first:.2f} s; second call {t:.3f} s (smoke timing)")
    if not st.ok:
        fail(f"DistStats not ok: {st}")
    n = check_valid_maximal(g, rd.match_mask, "distributed")
    spread = {d.id for d in st.proposals.sharding.device_set}
    if len(spread) != 4:
        fail(f"distributed outputs live on devices {sorted(spread)}, not 4")

    rk, t_one = timed(lambda: skipper_match(
        schedule=sched, backend="pallas", interpret=False))
    log(f"one-chip skipper_match: first call (compile + run) {t_one:.2f} s")
    slots = sched.num_rows * sched.tiles_per_window * sched.tile_size
    wsel = sched.stream_src < slots
    dmask, kmask = np.asarray(rd.match_mask), np.asarray(rk.match_mask)
    if not (dmask[wsel] == kmask[wsel]).all():
        fail("window-tier decisions differ from one-chip skipper_match")
    log(f"matches: {n} (valid, maximal, DistStats.ok, "
        f"{int(wsel.sum())} window-tier decisions equal to one chip, "
        f"outputs on devices {sorted(spread)})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run distributed_skipper over 4 devices instead")
    ap.add_argument("--scale", type=int, default=22,
                    help="Graph500 scale (default 22)")
    args = ap.parse_args()

    enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found platform {devs[0].platform!r}")
    log(f"device: {devs[0].device_kind} x {len(devs)}")

    g, sched = build(args.scale)
    if args.four_chips:
        four_chips(g, sched)
    else:
        one_chip(g, sched)
        peak = devs[0].memory_stats().get("peak_bytes_in_use")
        log(f"peak_bytes_in_use: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)


if __name__ == "__main__":
    main()
