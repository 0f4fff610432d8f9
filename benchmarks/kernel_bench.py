"""Kernel micro-benchmarks.

Three matcher paths are timed, selectable with ``--matcher`` (``both`` runs
all of them):

* ``jnp``      — the single-device tiled matcher (``core.skipper``), the
                 windowed-oracle micro-bench, and the MoE b-matching router
                 (``kernel/bmatch/*``: tokens x experts sweep; accept-rate
                 and Medges/s recorded, gated in check_regression.py
                 normalized by the same-run ``window_match/tile128`` row).
* ``windowed`` — the device-resident window pipeline (``skipper_match``):
                 schedule precomputed once on the host, then the COMPILED
                 (non-interpret) pipeline is timed end-to-end. On CPU the
                 compiled path is the pipeline's XLA twin — identical
                 schedule and semantics, one compilation unit; on TPU the
                 same driver compiles the Pallas kernel via Mosaic. The host
                 precompute (including block-pair grouping, DESIGN.md §10)
                 is recorded per row as ``schedule_build_ms`` in the JSON —
                 visible in the trajectory but EXCLUDED from the Medges/s
                 cells, which time only the device pipeline. A
                 boundary-heavy pair of rows (``kernel/boundary_pipeline/*``
                 normalized by ``kernel/boundary_jnp/*``: rmat14, no
                 reorder, intra~0.13 — the global tier dominates) gates the
                 block-pair epilogue specifically, in smoke too.
* ``distributed`` — the multi-device matcher on 4 devices of this process
                 (on CPU: start it with ``XLA_FLAGS=
                 --xla_force_host_platform_device_count=4``; with fewer
                 than 4 devices the phase exits with that advice). Two
                 rows per graph:
                 ``kernel/distributed_pipeline/*`` (locality-sharded: the
                 window tier runs the device-resident pipeline per device,
                 only the global tier pays propose/gather/replay) and
                 ``kernel/distributed_jnp_local/*`` (the dispersed-block
                 jnp-local-pass baseline). The recorded JSON carries the
                 achieved ``intra`` fraction and collective payload
                 (``gathered_bytes``); check_regression.py gates the pipeline
                 row normalized by the jnp-local row of the same run.

A state-width A/B pair rides with the windowed rows (``kernel/state_u8/*``
vs ``kernel/state_legacy_i32/*``, interleaved min-of-N on the same
schedule): the u8 row runs the default single-byte ``StateSpec``, the twin
runs ``StateSpec.legacy_i32()`` (the exact pre-refactor i32 graph). The
recorded extras carry ``state_bytes_per_vertex`` and the analytic
VMEM/wire state payloads per spec; check_regression.py gates the u8 row's
throughput normalized by the legacy twin AND hard-fails if the byte
reduction drops below 3.5x.

``--reorder {none,degree,bfs,greedy}`` selects the locality renumbering the
windowed pipeline's schedule is built with (``graphs/reorder.py``; default
``degree``). The headline ``kernel/windowed_pipeline/*`` rows use it; a
``kernel/windowed_pipeline_noreorder/*`` row is always recorded next to them
so the trajectory captures the reorder win, and the recorded JSON carries the
achieved ``intra`` fraction and ``padding_waste`` per windowed row.

``--smoke`` runs a seconds-scale subset (CI); ``--record out.json`` writes
the rows as JSON, each with the ``platform``, ``device_kind`` and
``device_count`` it ran on and the ``backend`` it used, so later PRs have a
perf trajectory
(benchmarks/baseline_small.json / baseline_smoke.json are the committed
baselines; benchmarks/check_regression.py compares against them in CI).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.compile_cache import enable_compile_cache
from repro.core.bipartite import bmatch_assign
from repro.core.skipper import skipper
from repro.core.statespec import StateSpec
from repro.graphs import build_window_schedule, grid_graph, rmat_graph
from repro.kernels.skipper_match import skipper_match
from repro.kernels.skipper_match.ref import ref_match_window


def _pipeline_backend() -> str:
    """On TPU the pipeline compiles the Pallas kernels via Mosaic; elsewhere
    its compiled path is the XLA twin (identical schedule/semantics)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _bench_jnp(rows, extras, smoke: bool):
    """Windowed-oracle + MoE b-matching rows, measured INTERLEAVED
    (min-of-N round-robin, like _bench_windowed): check_regression gates the
    ``kernel/bmatch/*`` rows normalized by the same-run
    ``window_match/tile128`` row, and sequential medians let host-load drift
    between the two measurements poison the ratio (observed 2x)."""
    cells = []

    # windowed matcher throughput (edges/s) across tile sizes
    rng = np.random.default_rng(0)
    w, m = 2048, 1 << (13 if smoke else 16)
    u = jnp.asarray(rng.integers(0, w, m), jnp.int32)
    v = jnp.asarray(rng.integers(0, w, m), jnp.int32)
    st0 = jnp.zeros((w,), jnp.int32)
    for tile in (128,) if smoke else (128, 256, 512):
        ut = u.reshape(-1, tile)
        vt = v.reshape(-1, tile)
        cells.append((
            f"kernel/window_match/tile{tile}",
            lambda ut=ut, vt=vt: ref_match_window(ut, vt, st0)[1],
            lambda t, m=m: f"{m / t / 1e6:.1f}Medges_s",
            None,
        ))

    # MoE b-matching router (engine.tile_pass_capacitated): tokens x experts
    # sweep over a score-sorted candidate stream (gated, see docstring).
    cases = ((1024, 8, 2),) if smoke else ((4096, 8, 2), (4096, 40, 8))
    for n_tok, n_exp, k in cases:
        kp = min(n_exp, k + 2)
        scores = jax.random.normal(jax.random.PRNGKey(1), (n_tok, n_exp))
        vals, idx = jax.lax.top_k(scores, kp)
        tok = jnp.repeat(jnp.arange(n_tok, dtype=jnp.int32), kp)
        exp = idx.reshape(-1).astype(jnp.int32)
        order = jnp.argsort(-vals.reshape(-1))
        cap = int(n_tok * k / n_exp * 1.25)
        m_edges = n_tok * kp

        def assign(tok=tok, exp=exp, order=order, n_tok=n_tok, n_exp=n_exp,
                   k=k, cap=cap):
            return bmatch_assign(
                tok[order], exp[order], num_tokens=n_tok, num_experts=n_exp,
                token_budget=k, expert_capacity=cap,
            )

        accept_rate = float(jnp.mean(assign().astype(jnp.float32)))
        cells.append((
            f"kernel/bmatch/t{n_tok}_e{n_exp}_k{k}",
            assign,
            lambda t, m_edges=m_edges, a=accept_rate:
                f"{m_edges / t / 1e6:.1f}Medges_s_acc{a:.2f}",
            {"accept_rate": round(accept_rate, 4)},
        ))

    iters = 7
    times = {name: [] for name, _, _, _ in cells}
    for _ in range(iters + 1):  # first pass = warmup/compile
        for name, fn, _, _ in cells:
            times[name].append(time_call(fn, warmup=0, iters=1))
    for name, _, derived, extra in cells:
        t = min(times[name][1:])
        rows.append(emit(name, t, derived(t)))
        if extra is not None:
            extras[name] = extra


def _bench_windowed(rows, extras, scale: str, smoke: bool, reorder: str):
    """Compiled windowed-pipeline timings vs the jnp matcher, RMAT + grid."""
    if smoke:
        graphs = {"rmat12": rmat_graph(12, 8, seed=1), "grid_128": grid_graph(128, 128)}
        window, tile = 1024, 256
    elif scale == "large":
        graphs = {"rmat16": rmat_graph(16, 16, seed=1), "grid_1k": grid_graph(1024, 1024)}
        window, tile = 4096, 256
    else:
        graphs = {"rmat14": rmat_graph(14, 16, seed=1), "grid_256": grid_graph(256, 256)}
        window, tile = 2048, 256

    backend = _pipeline_backend()
    # min-of-9, INTERLEAVED: these rows gate the CI regression check
    # (check_regression.py) via the windowed/jnp ratio, and the shared
    # CI/dev hosts drift — measuring the cells round-robin makes every
    # cell's min sample the same wall-clock window, so the ratio stays
    # stable; the min itself estimates capability (noise is additive).
    iters = 9

    def _timed_schedule(g, **kw):
        t0 = time.perf_counter()
        s = build_window_schedule(g, window=window, tile_size=tile, **kw)
        return s, (time.perf_counter() - t0) * 1e3

    for name, g in graphs.items():
        m = g.num_edges
        # headline row: the requested reorder policy; plus the reorder-off
        # twin so the trajectory captures the locality win.
        cells = []
        sched, sched_ms = _timed_schedule(g, reorder=reorder)
        cells.append((f"kernel/windowed_pipeline/{name}", sched, sched_ms,
                      lambda s=sched: skipper_match(schedule=s, backend=backend)))
        if reorder != "none":
            off, off_ms = _timed_schedule(g)
            cells.append((f"kernel/windowed_pipeline_noreorder/{name}", off,
                          off_ms,
                          lambda s=off: skipper_match(schedule=s, backend=backend)))
        cells.append((f"kernel/jnp_matcher/{name}", None, None,
                      lambda: skipper(g, tile_size=tile)))

        times = {row_name: [] for row_name, _, _, _ in cells}
        for _ in range(iters + 1):  # first pass = warmup/compile
            for row_name, _, _, fn in cells:
                times[row_name].append(time_call(fn, warmup=0, iters=1))
        for row_name, sched_i, sched_ms_i, _ in cells:
            t = min(times[row_name][1:])
            if sched_i is None:
                rows.append(emit(row_name, t, f"{m / t / 1e6:.1f}Medges_s"))
                continue
            rows.append(emit(
                row_name, t,
                f"{m / t / 1e6:.1f}Medges_s_intra{sched_i.intra_fraction:.2f}"
                f"_pad{sched_i.padding_waste:.2f}",
            ))
            extras[row_name] = {
                "backend": backend,
                "reorder": sched_i.reorder,
                "intra": round(sched_i.intra_fraction, 4),
                "windowed": round(sched_i.windowed_fraction, 4),
                "padding_waste": round(sched_i.padding_waste, 4),
                # host precompute, NOT in the Medges/s cell (device-only)
                "schedule_build_ms": round(sched_ms_i, 2),
            }


def _bench_statewidth(rows, extras, scale: str, smoke: bool, reorder: str):
    """State-width A/B on the full windowed pipeline: the default
    single-byte spec vs ``StateSpec.legacy_i32()`` on the SAME schedule,
    interleaved min-of-N. check_regression gates
    ``kernel/state_u8/<graph>`` normalized by the same-run legacy twin
    (>20% throughput regression fails) and hard-checks the recorded
    VMEM/wire state-byte reduction (>= 3.5x — the refactor's memory
    claim, DESIGN.md §12)."""
    if smoke:
        name, g = "rmat12", rmat_graph(12, 8, seed=1)
        window, tile = 1024, 256
    elif scale == "large":
        name, g = "rmat16", rmat_graph(16, 16, seed=1)
        window, tile = 4096, 256
    else:
        name, g = "rmat14", rmat_graph(14, 16, seed=1)
        window, tile = 2048, 256
    m = g.num_edges
    backend = _pipeline_backend()
    sched = build_window_schedule(g, window=window, tile_size=tile,
                                  reorder=reorder)

    specs = {
        f"kernel/state_u8/{name}": StateSpec.u8(),
        f"kernel/state_legacy_i32/{name}": StateSpec.legacy_i32(),
    }
    cells = [
        (cell, lambda s=spec: skipper_match(schedule=sched, backend=backend,
                                            spec=s))
        for cell, spec in specs.items()
    ]
    iters = 9
    times = {cell: [] for cell, _ in cells}
    for _ in range(iters + 1):  # first pass = warmup/compile
        for cell, fn in cells:
            times[cell].append(time_call(fn, warmup=0, iters=1))
    for cell, _ in cells:
        spec = specs[cell]
        t = min(times[cell][1:])
        rows.append(emit(
            cell, t,
            f"{m / t / 1e6:.1f}Medges_s_{spec.vmem_bytes}B_state",
        ))
        extras[cell] = {
            "backend": backend,
            "reorder": sched.reorder,
            "state_bytes_per_vertex": spec.vmem_bytes,
            # analytic per-spec payloads of THIS schedule (windows.py):
            # the revolving VMEM block(s) and the D=4 PHASE A wire combine
            "vmem_state_bytes": sched.vmem_state_bytes(spec),
            "wire_state_bytes": sched.wire_state_bytes(spec, num_devices=4),
        }


def _bench_boundary(rows, extras):
    """Boundary-heavy gated pair (runs in smoke too): rmat14 with NO reorder
    leaves the global tier dominant (intra ~0.13), so
    ``kernel/boundary_pipeline/rmat14`` times the block-pair epilogue
    specifically; check_regression gates it normalized by the same-run
    ``kernel/boundary_jnp/rmat14`` tiled-matcher row (interleaved min-of-N,
    same protocol as the windowed cells)."""
    g = rmat_graph(14, 16, seed=1)
    m = g.num_edges
    tile = 256
    backend = _pipeline_backend()
    t0 = time.perf_counter()
    sched = build_window_schedule(g, window=2048, tile_size=tile)
    sched_ms = (time.perf_counter() - t0) * 1e3

    cells = [
        ("kernel/boundary_pipeline/rmat14",
         lambda: skipper_match(schedule=sched, backend=backend)),
        ("kernel/boundary_jnp/rmat14", lambda: skipper(g, tile_size=tile)),
    ]
    iters = 9
    times = {cell: [] for cell, _ in cells}
    for _ in range(iters + 1):  # first pass = warmup/compile
        for cell, fn in cells:
            times[cell].append(time_call(fn, warmup=0, iters=1))
    for cell, _ in cells:
        t = min(times[cell][1:])
        if cell.startswith("kernel/boundary_pipeline/"):
            rows.append(emit(
                cell, t,
                f"{m / t / 1e6:.1f}Medges_s_intra{sched.intra_fraction:.2f}",
            ))
            extras[cell] = {
                "backend": backend,
                "reorder": sched.reorder,
                "intra": round(sched.intra_fraction, 4),
                "boundary_pairs": sched.num_boundary_pairs,
                "schedule_build_ms": round(sched_ms, 2),
            }
        else:
            rows.append(emit(cell, t, f"{m / t / 1e6:.1f}Medges_s"))


def _distributed_cases(scale: str, smoke: bool):
    """Graphs + schedule params for the distributed rows."""
    if smoke:
        return {"rmat12": ("rmat", 12, 8, 1)}, 1024, 256, 512, 5
    if scale == "large":
        return {"rmat16": ("rmat", 16, 16, 1)}, 4096, 256, 512, 5
    return (
        {"rmat14": ("rmat", 14, 16, 1), "grid_256": ("grid", 256, 256, 0)},
        2048, 256, 512, 7,
    )


def _build_case(spec):
    kind, a, b, seed = spec
    return rmat_graph(a, b, seed=seed) if kind == "rmat" else grid_graph(a, b)


def _bench_distributed(rows, extras, scale: str, smoke: bool, reorder: str):
    """Times the locality-sharded distributed matcher against the dispersed
    jnp-local-pass baseline on 4 devices of THIS process (interleaved
    min-of-N, like the windowed cells). On CPU, start the process with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``: the flag is
    read once, before jax first initializes, so no child is started."""
    from repro.core.distributed import distributed_skipper
    from repro.core.faults import FaultPlan
    from repro.graphs import partition_schedule

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(
            f"the distributed rows need 4 devices; JAX found {len(devs)} "
            f"{devs[0].platform} device(s). On CPU, run with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=4."
        )
    mesh = jax.make_mesh(
        (4,), ("data",), devices=devs[:4],
        axis_types=(jax.sharding.AxisType.Auto,),
    )
    backend = _pipeline_backend()

    # Active-but-inert plan: truncate_retry far above any retry capacity, so
    # the compiled work is identical to the plain row — the cell times what
    # the fault-harness plumbing itself costs (threading a plan through the
    # compile cache + the policy epilogue). check_regression gates this
    # against the plain pipeline row at 2%.
    inert = FaultPlan(seed=0, truncate_retry=1 << 30)

    specs, window, tile, block, iters = _distributed_cases(scale, smoke)
    for name, spec in specs.items():
        g = _build_case(spec)
        m = g.num_edges
        sched = build_window_schedule(g, window=window, tile_size=tile,
                                      reorder=reorder)
        ds = partition_schedule(sched, 4, block)
        last = {}  # the timed calls' stats — no extra stat-collection runs
        kw = dict(mesh=mesh, tile_size=tile, backend=backend)

        def keep(cell, out):
            last[cell] = out[1]
            return out

        cells = [
            (f"kernel/distributed_pipeline/{name}",
             lambda ds=ds, c=f"kernel/distributed_pipeline/{name}": keep(
                 c, distributed_skipper(device_schedule=ds, **kw))),
            (f"kernel/distributed_pipeline_hooks/{name}",
             lambda ds=ds, c=f"kernel/distributed_pipeline_hooks/{name}": keep(
                 c, distributed_skipper(device_schedule=ds, faults=inert,
                                        **kw))),
            (f"kernel/distributed_jnp_local/{name}",
             lambda g=g, c=f"kernel/distributed_jnp_local/{name}": keep(
                 c, distributed_skipper(g, block_size=block, **kw))),
        ]
        times = {cell: [] for cell, _ in cells}
        for _ in range(iters + 1):  # first pass = warmup/compile
            for cell, fn in cells:
                times[cell].append(time_call(fn, warmup=0, iters=1))
        # one NON-timed verified run: a fault-free bench must report zero on
        # every recovery field (check_regression hard-fails otherwise —
        # nonzero here means the matcher silently dropped work)
        _, vstats = distributed_skipper(
            g, device_schedule=ds, on_fault="report", verify=True, **kw,
        )
        recovery = {
            k: int(getattr(vstats, k)) for k in (
                "recovery_attempts", "residual_edges",
                "recovered_matches", "corrupted_cells",
            )
        }
        for cell, _ in cells:
            t = min(times[cell][1:])
            gbytes = int(last[cell].gathered_bytes)
            if cell.startswith("kernel/distributed_pipeline/"):
                derived = (f"{m / t / 1e6:.1f}Medges_s"
                           f"_intra{sched.intra_fraction:.2f}")
                extras[cell] = {
                    "reorder": sched.reorder,
                    "intra": round(sched.intra_fraction, 4),
                    "gathered_bytes": gbytes,
                    "num_devices": 4,
                    "backend": backend,
                    **recovery,
                }
            else:
                derived = f"{m / t / 1e6:.1f}Medges_s"
                extras[cell] = {
                    "gathered_bytes": gbytes,
                    "num_devices": 4,
                    "backend": "jnp" if "jnp_local" in cell else backend,
                }
            rows.append(emit(cell, t, derived))


def run(scale: str = "small", matcher: str = "both", smoke: bool = False,
        record: str | None = None, reorder: str = "degree"):
    rows = []
    extras = {}
    if matcher in ("both", "jnp"):
        _bench_jnp(rows, extras, smoke)
    if matcher in ("both", "windowed"):
        _bench_windowed(rows, extras, scale, smoke, reorder)
        _bench_statewidth(rows, extras, scale, smoke, reorder)
        _bench_boundary(rows, extras)
    if matcher in ("both", "distributed"):
        _bench_distributed(rows, extras, scale, smoke, reorder)
    if record:
        # every row names the device it ran on and the backend it used, so a
        # CPU / XLA-twin row is never read as a chip row
        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "device_kind": devs[0].device_kind,
                  "device_count": len(devs)}
        data = {}
        for line in rows:
            name, us, derived = line.split(",", 2)
            data[name] = {"us_per_call": float(us), "derived": derived,
                          "backend": "jnp", **device}  # jnp unless the cell says
            data[name].update(extras.get(name, {}))
        with open(record, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
            f.write("\n")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["small", "large"])
    ap.add_argument("--matcher", default="both",
                    choices=["both", "jnp", "windowed", "distributed"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--record", default=None)
    ap.add_argument("--reorder", default="degree",
                    choices=["none", "degree", "bfs", "greedy"])
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(args.scale, matcher=args.matcher, smoke=args.smoke,
        record=args.record, reorder=args.reorder)
