"""Production trace targets: every pallas kernel + jitted entry point.

Each target builds a ClosedJaxpr for one production surface via abstract
eval at small canonical shapes (CPU-only — nothing executes). The shapes
are chosen to exercise the real grid structure (multi-window grids, a
non-empty block-pair boundary tier, cross- and same-block pairs) while
keeping tracing fast enough for CI.

Targets that declare ``rescale`` can be re-traced with the vertex count
scaled by an integer factor at the SAME window/tile geometry — that is
what lets ``rules/vmem_budget.py`` *prove* the per-grid-step VMEM
footprint is independent of V (the O(window + tile^2) claim of DESIGN.md
§10) instead of asserting it in prose.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.statespec import DEFAULT, StateSpec

# canonical geometry: small but structurally faithful
_TILE = 256
_WINDOW = 256
_NUM_WINDOWS = 4
_TILES_PER_WINDOW = 2
_SEED = 0


@dataclasses.dataclass(frozen=True)
class Target:
    """One analyzable surface.

    ``build(scale)`` traces at ``scale``x the canonical vertex count
    (same window/tile geometry). ``expect_pallas`` is the number of
    pallas_call kernels the trace must contain — a structural conformance
    check: if a refactor silently drops a kernel from an entry point, the
    analyzer fails rather than passing vacuously.
    """

    name: str
    build: Callable[[int], object]     # scale -> ClosedJaxpr
    expect_pallas: int = 0
    rescalable: bool = False
    vmem_claim: str = ""

    def trace(self, scale: int = 1):
        return self.build(scale)


def _spec() -> StateSpec:
    return DEFAULT


# --------------------------------------------------------------------------
# kernel targets
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _trace_window_kernel(scale: int):
    from repro.kernels.skipper_match.kernel import build_window_matcher

    spec = _spec()
    call = build_window_matcher(2, _TILE, _WINDOW, 1, True, True, spec)
    uv = jax.ShapeDtypeStruct((2 * _TILE,), jnp.int32)
    st = jax.ShapeDtypeStruct((_WINDOW,), spec.vmem_dtype)
    return jax.make_jaxpr(call)(uv, uv, st)


@functools.lru_cache(maxsize=None)
def _trace_pipeline_kernel(scale: int):
    from repro.kernels.skipper_match.kernel import build_pipeline_matcher

    spec = _spec()
    nw = _NUM_WINDOWS * scale
    call = build_pipeline_matcher(
        nw, _TILES_PER_WINDOW, _TILE, _WINDOW, 1, True, True, spec
    )
    uv = jax.ShapeDtypeStruct((nw, _TILES_PER_WINDOW * _TILE), jnp.int32)
    st = jax.ShapeDtypeStruct((nw, _WINDOW), spec.vmem_dtype)
    return jax.make_jaxpr(call)(uv, uv, st)


@functools.lru_cache(maxsize=None)
def _trace_boundary_kernel(scale: int):
    from repro.kernels.skipper_match.kernel import build_boundary_matcher

    spec = _spec()
    nw = _NUM_WINDOWS * scale
    call = build_boundary_matcher(2, _TILE, nw, _WINDOW, 1, True, True, spec)
    blk = jax.ShapeDtypeStruct((2,), jnp.int32)
    uv = jax.ShapeDtypeStruct((2, _TILE), jnp.int32)
    st = jax.ShapeDtypeStruct((nw, _WINDOW), spec.vmem_dtype)
    return jax.make_jaxpr(call)(blk, blk, uv, uv, st)


# --------------------------------------------------------------------------
# entry-point targets
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _small_graph(scale: int = 1):
    from repro.graphs.types import EdgeList

    rng = np.random.default_rng(_SEED)
    n = _NUM_WINDOWS * _WINDOW * scale
    m = 4 * n
    u = rng.integers(0, n, m).astype(np.int32)
    v = rng.integers(0, n, m).astype(np.int32)
    return EdgeList(
        u=jnp.asarray(u), v=jnp.asarray(v), num_vertices=n
    )


@functools.lru_cache(maxsize=None)
def _small_schedule(scale: int = 1):
    from repro.graphs.windows import build_window_schedule

    return build_window_schedule(_small_graph(scale), _WINDOW, _TILE, True)


def _trace_skipper_match(backend: str, scale: int):
    from repro.kernels.skipper_match import ops

    spec = _spec()
    sched = _small_schedule(scale)
    fn = ops._build_pipeline(
        sched.num_windows, sched.num_rows, sched.tiles_per_window,
        sched.tile_size, sched.window, sched.num_boundary_padded,
        sched.num_edges, sched.num_vertices, 1, True, backend, "auto",
        None, spec,
    )
    sd = lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape, np.asarray(a).dtype)
    perm = jax.ShapeDtypeStruct((sched.num_vertices,), jnp.int32)
    return jax.make_jaxpr(fn)(
        sd(sched.u_tiles), sd(sched.v_tiles), sd(sched.stream_src),
        sd(sched.boundary_blk_u), sd(sched.boundary_blk_v),
        sd(sched.boundary_ulocal), sd(sched.boundary_vlocal),
        sd(sched.window_ids), perm,
    )


@functools.lru_cache(maxsize=None)
def _trace_skipper_match_pallas(scale: int):
    return _trace_skipper_match("pallas", scale)


@functools.lru_cache(maxsize=None)
def _trace_skipper_match_xla(scale: int):
    return _trace_skipper_match("xla", scale)


@functools.lru_cache(maxsize=None)
def _trace_distributed_sharded(scale: int):
    from repro import compat
    from repro.core import distributed
    from repro.graphs.partition import locality_device_schedule

    spec = _spec()
    ds = locality_device_schedule(
        _small_graph(scale), 1, 512, window=_WINDOW, tile_size=_TILE,
        reorder="none",
    )
    sched = ds.schedule
    mesh = compat.make_mesh((1,), ("data",))
    run = distributed._compiled_sharded(
        mesh, "data", 1, sched.window, sched.tiles_per_window,
        sched.tile_size, sched.num_rows, sched.num_windows,
        sched.num_boundary_padded, 1, 4, "xla", True, None, spec,
    )
    sd = lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape, np.asarray(a).dtype)
    return jax.make_jaxpr(run)(
        sd(ds.u_rows), sd(ds.v_rows), sd(ds.row_slot),
        sd(ds.boundary_ub), sd(ds.boundary_vb), sd(ds.boundary_ib),
        sd(sched.window_ids), sd(sched.boundary_u), sd(sched.boundary_v),
    )


@functools.lru_cache(maxsize=None)
def _trace_distributed_dispersed(scale: int):
    from repro import compat
    from repro.core import distributed
    from repro.graphs.partition import dispersed_blocks

    spec = _spec()
    g = _small_graph(scale)
    ub, vb = dispersed_blocks(g.canonical(), 1, 512)
    num_rounds = ub.shape[1]
    mesh = compat.make_mesh((1,), ("data",))
    run = distributed._compiled_dispersed(
        mesh, "data", 1, g.num_vertices, num_rounds * 512, 1, _TILE, 4,
        None, spec,
    )
    sd = lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape, np.asarray(a).dtype)
    ib = jax.ShapeDtypeStruct(np.asarray(ub).shape, jnp.int32)
    return jax.make_jaxpr(run)(sd(ub), sd(vb), ib)


@functools.lru_cache(maxsize=None)
def _trace_bmatch_assign(scale: int):
    from repro.core.bipartite import bmatch_assign

    fn = functools.partial(
        bmatch_assign, num_tokens=512, num_experts=8, token_budget=2,
        expert_capacity=128, tile_size=512,
    )
    tok = jax.ShapeDtypeStruct((1024,), jnp.int32)
    exp = jax.ShapeDtypeStruct((1024,), jnp.int32)
    return jax.make_jaxpr(fn)(tok, exp)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

TARGETS: Dict[str, Target] = {
    t.name: t for t in [
        Target(
            name="window_kernel",
            build=_trace_window_kernel,
            expect_pallas=1,
            vmem_claim="O(window + tile^2): single-window debug surface",
        ),
        Target(
            name="pipeline_kernel",
            build=_trace_pipeline_kernel,
            expect_pallas=1,
            rescalable=True,
            vmem_claim="O(window + tile^2), independent of V "
                       "(state block revolves per window)",
        ),
        Target(
            name="boundary_kernel",
            build=_trace_boundary_kernel,
            expect_pallas=1,
            rescalable=True,
            vmem_claim="O(window + tile^2), independent of V "
                       "(DESIGN.md §10: (2, W) pair scratch, ANY state)",
        ),
        Target(
            name="skipper_match_pallas",
            build=_trace_skipper_match_pallas,
            expect_pallas=2,  # pipeline sweep + boundary epilogue
        ),
        Target(
            name="skipper_match_xla",
            build=_trace_skipper_match_xla,
            expect_pallas=0,  # the jnp twin must stay pallas-free
        ),
        Target(
            name="distributed_sharded",
            build=_trace_distributed_sharded,
            expect_pallas=0,  # xla backend on CPU CI
        ),
        Target(
            name="distributed_dispersed",
            build=_trace_distributed_dispersed,
            expect_pallas=0,
        ),
        Target(
            name="bmatch_assign",
            build=_trace_bmatch_assign,
            expect_pallas=0,
        ),
    ]
}


def get_targets(names: Optional[List[str]] = None) -> List[Target]:
    if names is None:
        return list(TARGETS.values())
    missing = [n for n in names if n not in TARGETS]
    if missing:
        raise KeyError(
            f"unknown analysis target(s) {missing}; "
            f"known: {sorted(TARGETS)}"
        )
    return [TARGETS[n] for n in names]
