"""Jit'd wrappers around the skipper_match Pallas kernels.

``skipper_match_window`` — raw windowed matcher (edges already window-local).
``skipper_match``        — full-graph driver, device-resident: a one-shot host
    precompute (``graphs/windows.build_window_schedule``, optionally behind a
    ``reorder=`` locality renumbering) packs the canonical edge stream into a
    static two-tier ``[num_rows, tiles_per_window, tile_size]`` schedule,
    then ONE traced function covers the whole graph: a single ``pallas_call``
    over the 2-D (row, tile) grid of dense windows — the vertex-state block
    revolves through VMEM per window, no host round-trips — followed by an
    in-device first-claim epilogue (a second, scalar-prefetch Pallas kernel
    streaming only the TWO window-sized state blocks each block-pair tile
    touches; ``engine.tile_pass_pair`` scan on the xla twin) that resolves
    the block-pair grouped global tier (cross-window + coalesced
    sparse-window edges). Every edge is still decided exactly once;
    Counters are summed on device over the slot-order decisions;
    mask/state (and the per-edge conflicts, gathered only when a caller
    asks ``with_conflicts=True``) come back in original stream order /
    vertex ids even when the schedule is reordered.

``interpret`` is a debug flag: ``None`` (default) resolves to False on TPU
(compiled Mosaic) and True elsewhere (Pallas' interpreter is the only Pallas
path on CPU). ``backend="xla"`` selects the jnp twin of the same schedule —
one compilation unit, identical semantics — which is what CPU benchmarks time
(see benchmarks/kernel_bench.py).
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import functools
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import engine
from repro.core.faults import (
    CORRUPT,
    FaultPlan,
    RecoveryReport,
    corruption_mask,
    detect_residual,
    proposal_drop_mask,
    residual_replay,
)
from repro.core.statespec import DEFAULT, StateSpec, resolve as resolve_spec
from repro.core.types import Counters, MatchResult
from repro.core.validate import check_matching
from repro.graphs.types import EdgeList
from repro.graphs.windows import WindowSchedule, build_window_schedule
from repro.kernels.skipper_match.kernel import (
    build_boundary_matcher,
    build_window_matcher,
)

# Incremented at TRACE time inside the pipeline body: the number of actual
# compilations of the full-graph pipeline. Tests use it to prove the driver
# performs zero per-window host round-trips (one trace covers all windows).
_PIPELINE_TRACES = 0


def pipeline_trace_count() -> int:
    return _PIPELINE_TRACES


# The pipeline's output gathers, as ``jax.named_scope``s: each HLO
# instruction's ``op_name`` carries the one it was traced in (``op_scopes``).
SCOPES = ("decision_gather", "conflict_gather", "state_unpermute")
# The AOT executable of each ``_build_pipeline`` result, newest call last:
# the calls run it, so ``op_scopes`` reads the HLO they ran.
_COMPILED: dict = {}
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bop_name="([^"]*)"',
                      re.M)


def op_scopes() -> Tuple[Optional[str], dict]:
    """``(module, {instruction: scope})`` of the executable that the newest
    ``skipper_match`` call ran: its HLO module name, and for each of its
    instructions traced in one of ``SCOPES``, that scope. A device trace
    names an op by its bare instruction name, which another module may
    also use: only the ops of ``module`` are the pipeline's. ``(None, {})``
    before the first call."""
    if not _COMPILED:
        return None, {}
    text = next(reversed(_COMPILED.values())).as_text()
    out = {}
    for name, path in _OP_NAME.findall(text):
        inner = [part for part in path.split("/") if part in SCOPES]
        if inner:
            out[name] = inner[-1]
    return _MODULE.match(text).group(1), out


def _executable(fn, args):
    """``fn`` compiled ahead of time for ``args``, once per ``fn``: every
    argument's shape is fixed by ``_build_pipeline``'s key."""
    exe = _COMPILED.pop(fn, None)
    if exe is None:
        exe = fn.lower(*args).compile()
    _COMPILED[fn] = exe
    while len(_COMPILED) > 64:
        del _COMPILED[next(iter(_COMPILED))]
    return exe


# The one schedule whose device copy outlives a call: (a weak reference to
# the host schedule, the device it was copied to, the copied arguments).
# Held here and not on the schedule, so that a caller that keeps many
# schedules (a partitioner's coarsening levels) keeps one copy in HBM.
_RESIDENT: Optional[tuple] = None


def _drop_resident(ref=None) -> None:
    """Forget the resident copy; given a weak reference (the callback of a
    collected schedule), only if it is that schedule's."""
    global _RESIDENT
    if ref is None or (_RESIDENT is not None and _RESIDENT[0] is ref):
        _RESIDENT = None


def _immutable(a) -> bool:
    """Whether nothing can write ``a`` in place: a ``jax.Array``, or a numpy
    array that is read-only, as is every numpy array it is a view of."""
    if isinstance(a, jax.Array):
        return True
    if not isinstance(a, np.ndarray):
        return False
    while isinstance(a, np.ndarray) and not a.flags.writeable:
        a = a.base
    return not isinstance(a, np.ndarray)


def _schedule_args(schedule: WindowSchedule) -> Tuple[tuple, bool, int]:
    """The pipeline's schedule arguments on the default device, landed;
    whether they were resident; and the bytes copied from the host to put
    them there (0 where they were resident).

    Otherwise the resident copy is dropped before the new one is made, so
    at most one schedule's copy is live between calls; the new copy stays
    resident while its schedule lives, if every array of the schedule that
    it holds is immutable (``build_window_schedule``'s are read-only)."""
    global _RESIDENT
    device = jax.config.jax_default_device or jax.local_devices()[0]
    if isinstance(device, str):
        device = jax.local_devices(backend=device)[0]
    if (_RESIDENT is not None and _RESIDENT[0]() is schedule
            and _RESIDENT[1] == device):
        return _RESIDENT[2], True, 0
    _RESIDENT = None
    perm = schedule.perm
    if perm is None:
        perm = jnp.arange(schedule.num_vertices, dtype=jnp.int32)
    host = (schedule.u_tiles, schedule.v_tiles, schedule.stream_src,
            schedule.boundary_blk_u, schedule.boundary_blk_v,
            schedule.boundary_ulocal, schedule.boundary_vlocal,
            schedule.window_ids, perm)
    args = jax.block_until_ready(jax.device_put(host))
    if all(_immutable(h) for h in host):
        _RESIDENT = (weakref.ref(schedule, _drop_resident), device, args)
    return args, False, sum(d.nbytes for h, d in zip(host, args)
                            if not isinstance(h, jax.Array))


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def skipper_match_window(
    u: jax.Array,
    v: jax.Array,
    state0: jax.Array,
    tile_size: int = 256,
    vector_rounds: int = 1,
    fallback: bool = True,
    interpret: Optional[bool] = None,
    spec: Optional[StateSpec] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Match a window-local edge stream. u/v: int32[M] (padded to tile
    multiple with -1), state0: [W] (coerced to ``spec.vmem``). Returns
    (state, matched, conflicts) in spec.vmem / spec.counter widths, and the
    int32 count of tiles that took the exact fallback.
    """
    spec = resolve_spec(spec)
    if interpret is None:
        interpret = _auto_interpret()
    m = u.shape[0]
    pad = (-m) % tile_size
    if pad:
        u = jnp.concatenate([u, jnp.full((pad,), -1, jnp.int32)])
        v = jnp.concatenate([v, jnp.full((pad,), -1, jnp.int32)])
    num_tiles = u.shape[0] // tile_size
    window = state0.shape[0]
    call = build_window_matcher(
        num_tiles, tile_size, window, vector_rounds, fallback, interpret,
        spec,
    )
    state, matched, conflicts, taken = call(u, v,
                                            state0.astype(spec.vmem_dtype))
    return state, matched[:m], conflicts[:m], taken


@functools.lru_cache(maxsize=64)
def _build_pipeline(
    num_windows: int,
    num_rows: int,
    tiles_per_window: int,
    tile_size: int,
    window: int,
    num_boundary_padded: int,
    num_edges: int,
    num_vertices: int,
    vector_rounds: int,
    interpret: bool,
    backend: str,
    conflict_method: str,
    faults: Optional[FaultPlan] = None,
    spec: StateSpec = DEFAULT,
    with_conflicts: bool = False,
):
    """One jitted compilation unit per static schedule shape: windowed kernel
    sweep over the dense rows + boundary epilogue + on-device counters.

    ``row_ids`` maps schedule rows to window ids (two-tier compaction);
    ``perm`` maps original vertex ids to renumbered ids (identity when the
    schedule was built without reordering) — the returned state is gathered
    through it so callers always see original ids.

    ``faults`` (frozen, part of the lru key; default None == zero extra ops)
    injects the single-device analogues of the distributed failure sites at
    the SAME stream positions / state cells (DESIGN.md §11): drop global-tier
    slots before the epilogue, lose one window row's tier contribution,
    corrupt assembled-state bytes.

    The output gathers run in the named scopes of ``SCOPES``. The per-edge
    conflicts are gathered to stream order only under ``with_conflicts``
    (the jit then returns them, else None); ``Counters`` are summed over
    the slot-order buffers either way, in the ``conflict_gather`` scope.
    """
    n_flat = num_windows * window
    nb_tiles = num_boundary_padded // tile_size
    m = num_edges

    def pipeline(u2, v2, src, blk_u, blk_v, bu, bv, row_ids, perm):
        global _PIPELINE_TRACES
        _PIPELINE_TRACES += 1  # trace-time side effect (compilation counter)

        # window tier: the engine entry point shared with the distributed
        # matcher's per-device LOCAL PASS (pallas kernel / jnp twin).
        state2, matched2, conf2, wfall = engine.window_tier_pass(
            u2, v2,
            window=window,
            tiles_per_window=tiles_per_window,
            tile_size=tile_size,
            vector_rounds=vector_rounds,
            backend=backend,
            interpret=interpret,
            spec=spec,
        )
        if faults is not None and faults.lose_shard is not None and num_rows:
            # FAULT: lost-shard analogue — one window row's tier
            # contribution (state AND matched bits) vanishes
            lost_row = faults.lose_shard % num_rows
            rowsel = (
                jax.lax.broadcasted_iota(jnp.int32, state2.shape, 0)
                == lost_row
            )
            state2 = jnp.where(rowsel, jnp.zeros_like(state2), state2)
            matched2 = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, matched2.shape, 0)
                == lost_row,
                jnp.zeros_like(matched2),
                matched2,
            )

        # Rows hold only the dense windows: scatter them into the full
        # [num_windows, window] state (coalesced windows stay all-ACC — their
        # edges are decided by the epilogue below) at the spec's kernel-tier
        # width: both backends carry spec.vmem here, so the Pallas boundary
        # kernel's aliased ANY-memory state and the xla twin's scan carry
        # are the same buffer layout (1 B/vertex under the default spec).
        state_dt = spec.vmem_dtype
        flat = (
            jnp.zeros((num_windows, window), state_dt)
            .at[row_ids].set(state2.astype(state_dt))
        )
        if faults is not None and faults.corrupt_state > 0.0:
            # FAULT: out-of-domain bytes in the assembled committed state —
            # same cells (renumbered-flat id space) as the distributed
            # locality-sharded injection
            flat = jnp.where(
                corruption_mask(faults, n_flat).reshape(num_windows, window),
                jnp.asarray(CORRUPT, state_dt),
                flat,
            )
        if faults is not None and faults.drop_proposals > 0.0 and nb_tiles:
            # FAULT: dropped global-tier slots — mark them invalid before
            # the epilogue so the edge is silently never decided (same
            # victims as the distributed gather-drop: the mask is keyed by
            # boundary stream position)
            dmask = proposal_drop_mask(faults, num_boundary_padded)
            bu = jnp.where(dmask, -1, bu)
            bv = jnp.where(dmask, -1, bv)

        # Global-tier epilogue: the block-pair grouped cross-window +
        # coalesced edges, same first-claim tile pass, still inside this
        # trace. On the pallas path this is the second kernel of the
        # compilation unit — a scalar-prefetch grid that DMAs only the two
        # state rows each pair tile touches (O(window) VMEM, DESIGN.md §10);
        # the xla twin runs the bit-identical tile_pass_pair scan over the
        # same offset-local tiles.
        if nb_tiles:
            but = bu.reshape(nb_tiles, tile_size)
            bvt = bv.reshape(nb_tiles, tile_size)
            if backend == "pallas":
                bcall = build_boundary_matcher(
                    nb_tiles, tile_size, num_windows, window,
                    vector_rounds, True, interpret, spec,
                )
                flat, bmt, bcf = bcall(blk_u, blk_v, but, bvt, flat)
            else:

                def bstep(rows, xs):
                    uloc, vloc, pbu, pbv = xs
                    rows, mt, cf, _fb = engine.tile_pass_pair(
                        rows, uloc, vloc, pbu, pbv, window=window,
                        vector_rounds=vector_rounds,
                        conflict_method=conflict_method, spec=spec,
                    )
                    return rows, (mt, cf)

                flat, (bmt, bcf) = jax.lax.scan(
                    bstep, flat, (but, bvt, blk_u, blk_v)
                )

        # Gather slot-order decisions back to stream order through the
        # host-precomputed map (``WindowSchedule.stream_src``): decision
        # slot layout is [windowed ++ global-tier ++ one zero pad slot].
        # A gather, not a scatter — a |E|-index scatter costs ~100x more on
        # CPU XLA and the map is static per schedule.
        cdt = spec.counter_dtype
        with jax.named_scope("decision_gather"):
            dec = [matched2.reshape(-1)]
            if nb_tiles:
                dec.append(bmt.reshape(-1).astype(cdt))
            dec.append(jnp.zeros((1,), cdt))
            mask = jnp.concatenate(dec)[src] > 0
        with jax.named_scope("conflict_gather"):
            # Every edge is decided in exactly one slot, and a slot that no
            # edge holds (padding, dropped) counts no conflict: the sum over
            # the slots is the sum over the stream.
            nconf = jnp.sum(conf2.reshape(-1), dtype=jnp.int32)
            if nb_tiles:
                nconf = nconf + jnp.sum(bcf.reshape(-1), dtype=jnp.int32)
            conf = None
            if with_conflicts:
                # per-edge conflicts stay i32 at the public boundary; the
                # narrow width is the O(E) buffer inside
                cfs = [conf2.reshape(-1)]
                if nb_tiles:
                    cfs.append(bcf.reshape(-1).astype(cdt))
                cfs.append(jnp.zeros((1,), cdt))
                conf = jnp.concatenate(cfs)[src].astype(jnp.int32)

            nmatch = jnp.sum(mask).astype(jnp.int32)
            counters = Counters(
                edge_reads=jnp.asarray(m, jnp.int32),
                state_loads=jnp.asarray(2 * m, jnp.int32) + 2 * nconf,
                state_stores=2 * nmatch,
                rounds=jnp.asarray(1, jnp.int32),
                fallback_tiles=wfall,
            )
        # back to ORIGINAL vertex ids: original vertex i lives at renumbered
        # slot perm[i] of the flattened state (perm = arange when unordered).
        with jax.named_scope("state_unpermute"):
            state_out = flat.reshape(n_flat)[perm].astype(spec.at_rest_dtype)
        return mask, state_out, conf, counters

    return jax.jit(pipeline)


def skipper_match(
    edges: Optional[EdgeList] = None,
    window: int = 2048,
    tile_size: int = 256,
    vector_rounds: int = 1,
    interpret: Optional[bool] = None,
    backend: str = "pallas",
    schedule: Optional[WindowSchedule] = None,
    dispersed: bool = True,
    reorder: str = "none",
    with_conflicts: bool = False,
    conflict_method: str = "auto",
    faults: Optional[FaultPlan] = None,
    on_fault: str = "raise",
    verify: bool = False,
    spec: Optional[StateSpec] = None,
) -> Union[MatchResult, Tuple]:
    """Full-graph device-resident matcher: one traced pipeline for all
    windows plus the in-device boundary epilogue.

    HBM lifetime of the schedule: at most one schedule's device copy
    outlives a call. A call on the schedule whose copy is resident (the
    same object, on the same default device) copies nothing and
    dispatches the pipeline at once (counter ``match.schedule_resident``
    1). Any other call drops the resident copy, copies its schedule and
    waits until the copy has landed (span ``match.to_device``, counter
    ``match.h2d_bytes``), then dispatches: a caller cannot overlap host
    work with the copy. That copy stays resident until the next such call
    or until its schedule is collected, and only if every numpy array it
    was made from is read-only (``build_window_schedule`` marks them so);
    a schedule with writable arrays is copied on every call. The pipeline
    runs asynchronously. The window tier's count of
    tiles that took the exact fallback (``Counters.fallback_tiles``) is
    logged as the device scalar it is (counter
    ``match.window_fallback_tiles``), so the call does not wait for it.

    Pass ``schedule`` (from ``build_window_schedule``) to skip the host
    precompute — e.g. when timing the compiled device path; ``window`` /
    ``tile_size`` / ``dispersed`` / ``reorder`` are then taken from the
    schedule. ``reorder`` selects a locality renumbering policy
    (``graphs/reorder.py``); results — mask, conflicts AND state — are
    always in the original edge-stream order / vertex ids regardless.
    The per-edge conflicts (int32[|E|]) are gathered back to stream order
    only under ``with_conflicts=True``, which compiles a pipeline of its
    own; ``Counters`` are summed over the slot-order decisions on every
    call and do not need them.
    ``conflict_method`` reaches the XLA twin's boundary-epilogue
    ``engine.tile_pass`` (the Pallas kernels force the share-matrix form —
    Mosaic has no sort/scatter); the choice never changes output.

    ``spec`` (a frozen :class:`StateSpec`, ``None`` -> the uint8 default)
    picks the vertex-state width of every tier — VMEM blocks, the boundary
    kernel's ANY-memory state, the matched/conflicts buffers, the returned
    at-rest state. ``StateSpec.legacy_i32()`` compiles the historical
    all-i32 graph; matchings are bit-identical across specs (test-pinned).

    Failure handling (DESIGN.md §11): ``faults=`` threads a frozen
    :class:`FaultPlan` into the compiled pipeline (``None``, the default,
    compiles the exact pre-harness graph). ``on_fault`` decides what to do
    about damage — the single-device pipeline has no runtime tripwire
    (nothing overflows), so ``"raise"`` only has teeth with ``verify=True``:

    * ``"raise"`` (default): return the result as-is; with ``verify=True``
      raise ``RuntimeError`` if the matching fails ``check_matching`` or
      residual/corrupted damage is detected.
    * ``"report"``: append a :class:`RecoveryReport` (detection only) to
      the return tuple. Needs ``edges``.
    * ``"recover"``: run the residual replay (``faults.residual_replay`` —
      rebuild state from the mask, complete the matching over undecided
      edges); the result is provably valid+maximal on the uncorrupted
      graph. Appends the :class:`RecoveryReport`. Needs ``edges``.
      ``Counters`` still describe the faulted run, not the replay.

    Return value order: ``result`` [, ``conflicts`` if ``with_conflicts``]
    [, ``report`` if ``on_fault != "raise"``].
    """
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown backend {backend!r}")
    if on_fault not in ("raise", "recover", "report"):
        raise ValueError(
            f"on_fault must be 'raise', 'recover' or 'report', got {on_fault!r}"
        )
    if (verify or on_fault in ("recover", "report")) and edges is None:
        raise ValueError(
            "on_fault='recover'/'report' and verify=True need the original "
            "edge list — pass edges even when a prebuilt schedule is given"
        )
    if faults is not None and not faults.active:
        faults = None  # all sites off: share the clean compiled pipeline
    if schedule is None:
        if edges is None:
            raise ValueError("need either edges or a prebuilt schedule")
        schedule = build_window_schedule(
            edges, window, tile_size, dispersed, reorder=reorder
        )
    if interpret is None:
        interpret = _auto_interpret()
    spec = resolve_spec(spec)
    # ``with_conflicts`` is passed only when set, so that a call without it
    # shares the lru entry of the callers that leave it out
    # (``analysis/targets.py``, the tests)
    fn = _build_pipeline(
        schedule.num_windows,
        schedule.num_rows,
        schedule.tiles_per_window,
        schedule.tile_size,
        schedule.window,
        schedule.num_boundary_padded,
        schedule.num_edges,
        schedule.num_vertices,
        vector_rounds,
        bool(interpret),
        backend,
        conflict_method,
        faults,
        spec,
        *((True,) if with_conflicts else ()),
    )
    # The schedule copy ends when the arrays have landed; the pipeline
    # could not start before that anyway. On the resident schedule the
    # span is the lookup alone.
    with spans.span("match.to_device"):
        args, resident, copied = _schedule_args(schedule)
    spans.count("match.h2d_bytes", copied)
    spans.count("match.schedule_resident", int(resident))
    mask, state, conflicts, counters = _executable(fn, args)(*args)
    spans.count("match.window_fallback_tiles", counters.fallback_tiles)
    result = MatchResult(match_mask=mask, state=state, counters=counters)

    report = None
    if on_fault == "recover":
        rmask, rstate, residual, recovered, corrupted = residual_replay(
            edges, result.match_mask, result.state,
            tile_size=schedule.tile_size, vector_rounds=vector_rounds,
            spec=spec,
        )
        res_i, cor_i = (
            int(x) for x in
            jax.device_get((residual, corrupted))  # host-sync: ok (fault recovery)
        )
        result = MatchResult(match_mask=rmask, state=rstate, counters=counters)
        report = RecoveryReport(
            recovery_attempts=1 if (res_i or cor_i) else 0,
            residual_edges=res_i,
            recovered_matches=int(jax.device_get(recovered)),  # host-sync: ok
            corrupted_cells=cor_i,
        )
    elif on_fault == "report" or verify:
        residual, corrupted = detect_residual(
            edges, result.match_mask, result.state
        )
        res_i, cor_i = (
            int(x) for x in
            jax.device_get((residual, corrupted))  # host-sync: ok (fault report)
        )
        report = RecoveryReport(
            residual_edges=res_i, corrupted_cells=cor_i
        )
    if verify:
        chk = check_matching(edges, result.match_mask)
        ok_v, ok_m = (bool(x) for x in jax.device_get(  # host-sync: ok (verify path)
            (chk["valid"], chk["maximal"])
        ))
        if on_fault == "recover" and not (ok_v and ok_m):
            raise RuntimeError(
                "verify=True after on_fault='recover': recovered matching "
                f"failed validation (valid={ok_v}, maximal={ok_m}) — this "
                "is a bug in the recovery ladder, please report it"
            )
        if on_fault == "raise" and not (
            ok_v and ok_m
            and report.residual_edges == 0 and report.corrupted_cells == 0
        ):
            raise RuntimeError(
                "verify=True: matching failed validation "
                f"(valid={ok_v}, maximal={ok_m}, "
                f"residual_edges={report.residual_edges}, "
                f"corrupted_cells={report.corrupted_cells}) — run "
                "on_fault='recover' to complete it or 'report' to inspect"
            )

    out = (result,)
    if with_conflicts:
        out = out + (conflicts,)
    if on_fault != "raise":
        out = out + (report,)
    return out if len(out) > 1 else result
