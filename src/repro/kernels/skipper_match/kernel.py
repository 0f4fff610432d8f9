"""Pallas TPU kernel: windowed single-pass greedy matching (Skipper core).

TPU mapping of the paper's hot loop (Alg. 1 lines 5-18). Three entry points:

* ``build_window_matcher``   — 1-D grid over the tiles of ONE vertex window
  (the unit-test / debugging surface; never reached from
  ``skipper_match``).
* ``build_pipeline_matcher`` — 2-D grid ``(row, tile)`` over the dense tier
  of the graph's window schedule (``graphs/windows.py``; a row is a dense
  window). The state BlockSpec index map depends only on the row coordinate,
  so the W-vertex state block stays resident in VMEM across all tile steps
  of a window and is swapped (written back to HBM, next block DMA'd in)
  exactly once per window — zero host round-trips for the full graph. TPU
  grids iterate the LAST dimension innermost, which is what makes the
  residency work.
* ``build_boundary_matcher`` — scalar-prefetch 1-D grid over the global-tier
  tiles (cross-window + coalesced sparse-window edges), block-pair grouped
  by the host schedule (``graphs/windows.py``; DESIGN.md §10): each grid
  step DMAs only the TWO ``window``-sized state blocks its pair touches
  into a (2, W/128, 128) VMEM scratch — O(window) VMEM, independent of V —
  and the pair tile is ``engine.tile_pass_pair``'s concatenated-state tile,
  so the jnp reference epilogue stays bit-identical by construction.

Layout (what Mosaic lowers on v5e): every block's last two dims are a
``(rows, 128)`` slab. A T-edge tile is a ``(T/128, 128)`` int32 slab, a
W-vertex state block a ``(W/128, 128)`` slab, and each block's last two
dims equal the array's, so no block is sublane-1 and none is rank-1. The
builders return wrappers that take and give the flat ``[tiles, T]`` /
``[windows, W]`` arrays, so callers never see the slabs. Sizes that are
not multiples of 128 (small test shapes) use one row of that many lanes.

All the kernels share one per-tile body, ``_match_tile``. The first-claim
decision logic (conflict matrix + commit rule) is ``core/engine.py`` —
shared verbatim with the jnp matchers so the invariant cannot drift; only
the data movement is kernel-specific, and it is all 2-D:

  * per-edge vectors are ``(T, 1)`` columns. The ids arrive as a slab; the
    ``(1, T)`` row is its lane concatenation and the column an aligned
    transpose of that row broadcast to 128 sublanes.
  * state gather  : vertex id = (block row, lane). ``onehot(row) @ state``
    is a ``(T, W/128) x (W/128, 128)`` int8 matmul with int32 accumulation
    (exact: operands are 0/1 and states are at most 2) that brings each
    edge its endpoint's 128-lane state row; a lane mask and a lane sum pick
    the state out. v5e's MXU refuses int32 operands, and uint8 state does
    not cast to a float type there — int8 is the path it accepts.
  * JIT conflicts : the T x T triangular share matrix (VPU compares). The
    blocked test "some free j < i shares an endpoint" is the matmul
    ``share @ free`` on the MXU, so no per-round transpose is needed.
    Blocked edges retry in the next unrolled round, NOT in a later pass:
    single pass over edges is preserved.
  * state scatter : ``onehot(row)^T @ (lane mask * commit)``, the
    transposed one-hot built directly from the row form of the ids;
    committed edges are mutually endpoint-disjoint by construction, so the
    scatter is conflict-free (the kernel-level linearization point).
  * fallback      : rare leftover chains resolved by iterated first-claim
    rounds to fixpoint (``engine.greedy_fallback_rounds`` — exactly the
    sequential greedy's result), all VPU/MXU work, in-VMEM, still same-pass.
    The window-tier kernels count the tiles that took it in one int32 per
    call, summed in SMEM (``_count_fallback``).

States: ACC=0, MCHD=2. Every width (VMEM state, matched/conflicts outputs)
comes from the builder's ``StateSpec`` (``core/statespec.py``); the default
spec keeps the paper's 1 B/vertex claim honest in VMEM too, the
``legacy_i32`` spec compiles the historical all-i32 graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import engine
from repro.core.engine import MCHD
from repro.core.statespec import DEFAULT, StateSpec


def slab_shape(n: int):
    """``(rows, lanes)`` of the 2-D slab holding ``n`` values: 128 lanes
    when ``n`` is a multiple of 128 (every real size), else one row."""
    lanes = 128 if n % 128 == 0 else n
    return n // lanes, lanes


def _split(ids: jax.Array, lanes: int):
    """Vertex id -> (slab row, lane)."""
    if lanes & (lanes - 1) == 0:
        return ids >> (lanes.bit_length() - 1), ids & (lanes - 1)
    return ids // lanes, ids % lanes


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """int8 x int8 -> int32 matmul (exact for the 0/1/2 operands here)."""
    return jnp.dot(a, b, preferred_element_type=jnp.int32)


def _to_row(slab: jax.Array) -> jax.Array:
    """(S, L) slab -> (1, S*L) row by lane concatenation."""
    if slab.shape[0] == 1:
        return slab
    return jnp.concatenate(
        [slab[k:k + 1, :] for k in range(slab.shape[0])], axis=1
    )


def _to_col(row: jax.Array) -> jax.Array:
    """(1, T) int32 row -> (T, 1) column: transpose the row broadcast to 128
    sublanes (an aligned 2-D transpose) and keep lane 0."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]


def _col_to_slab(col: jax.Array, shape) -> jax.Array:
    """(T, 1) int32 column -> (S, L) slab (inverse of ``_to_row``/``_to_col``)."""
    rows, lanes = shape
    row = jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[:1, :]
    if rows == 1:
        return row
    return jnp.concatenate(
        [row[:, k * lanes:(k + 1) * lanes] for k in range(rows)], axis=0
    )


def _blocked_on_mxu(conflict: jax.Array):
    """``engine.blocked_from_matrix`` with the row reduction done on the MXU:
    edge i is blocked iff ``(share @ free)[i] > 0`` and i is free. Same
    function; ``free`` stays a column, so no round needs a transpose."""
    share = conflict.astype(jnp.int8)
    t = conflict.shape[0]

    def blocked_fn(free):
        f = jnp.broadcast_to(free.astype(jnp.int32).astype(jnp.int8), (t, 128))
        return (_dot(share, f)[:, :1] > 0) & free

    return blocked_fn


def _match_tile(u_slab, v_slab, cell, *, blocks: int, window: int,
                vector_rounds: int, fallback: bool):
    """Run one tile of T local edges against VMEM-resident state.

    ``u_slab``/``v_slab`` are the tile's int32 id slabs (-1 = padding).
    ``cell[...]`` is a tuple of ``blocks`` state blocks, each the
    ``slab_shape(window)`` slab of one window; ids index their
    concatenation (``blocks * window`` vertices). Commits are written into
    the cell round by round. Returns (matched bool[T, 1], conflicts
    int32[T, 1])."""
    rows, lanes = slab_shape(window)
    u_row, v_row = _to_row(u_slab), _to_row(v_slab)
    u, v = _to_col(u_row), _to_col(v_row)
    t = u.shape[0]
    valid = (u >= 0) & (u != v)
    valid_row = (u_row >= 0) & (u_row != v_row)
    # matrix blocked-impl: T x T VPU compares are native here, and Mosaic
    # has no sort for the claim-sort twin (engine docstring) — same function.
    conflict = engine.share_matrix(u, v, valid, rows=(u_row, v_row, valid_row))
    blocked_fn = _blocked_on_mxu(conflict)

    def onehots(col, row):
        # reused by every round: gather (T, rows) and scatter (rows, T)
        # one-hots per block, plus the (T, lanes) lane mask
        hi, lo = _split(col, lanes)
        hi = jnp.where(valid, hi, -1)
        hi_r = jnp.where(valid_row, _split(row, lanes)[0], -1)
        pick = [(_iota((t, rows), 1) == hi - k * rows).astype(jnp.int8)
                for k in range(blocks)]
        put = [(_iota((rows, t), 0) == hi_r - k * rows).astype(jnp.int8)
               for k in range(blocks)]
        return pick, put, _iota((t, lanes), 1) == lo

    pick_u, put_u, lane_u = onehots(u, u_row)
    pick_v, put_v, lane_v = onehots(v, v_row)

    # gather/scatter read and write the cell itself: the state never rides
    # a loop carry (Mosaic cannot carry packed uint8 slabs through one)
    def gather(_=()):
        s8 = [s.astype(jnp.int32).astype(jnp.int8) for s in cell[...]]

        def one(pick, lane):
            sel = sum(_dot(p, s) for p, s in zip(pick, s8))  # (T, lanes)
            return jnp.sum(jnp.where(lane, sel, 0), axis=1, keepdims=True)

        return one(pick_u, lane_u), one(pick_v, lane_v)

    def scatter(_, commit):
        # conflict-free scatter: committed edges are endpoint-disjoint
        cu = (lane_u & commit).astype(jnp.int32).astype(jnp.int8)
        cv = (lane_v & commit).astype(jnp.int32).astype(jnp.int8)
        out = []
        for k, s in enumerate(cell[...]):
            hit = _dot(put_u[k], cu) + _dot(put_v[k], cv)  # (rows, lanes)
            out.append(
                jnp.where(hit > 0, MCHD, s.astype(jnp.int32)).astype(s.dtype)
            )
        cell[...] = tuple(out)
        return ()

    matched, conflicts = engine.run_first_claim_rounds(
        u, v, valid, gather, lambda commit: scatter((), commit),
        vector_rounds, blocked_fn,
    )

    taken = False
    if fallback:
        # exact vectorized cleanup of pathological chains (rare): iterated
        # first-claim rounds to fixpoint == the sequential index-order greedy
        # (engine.greedy_fallback_rounds), all VPU/MXU work — no scalar loop.
        _, matched, taken = engine.greedy_fallback_rounds(
            (), u, v, valid, matched, blocked_fn,
            gather=gather, scatter=scatter,
        )

    return matched, conflicts, taken


def _count_fallback(count_ref, acc_ref, sem, first, last, taken):
    """Add the tile's ``taken`` to the call's fallback count: an int32 in
    SMEM scratch (``acc_ref``) that the first grid step starts from 0 and
    the last copies to ``count_ref`` in HBM. An output block instead costs
    every grid step the pipeline's bookkeeping: 2% of the kernel's time on
    the v5e, against 0.1% for this."""
    acc_ref[0] = jnp.where(first, 0, acc_ref[0]) + jnp.asarray(taken,
                                                               jnp.int32)

    @pl.when(last)
    def _copy_out():
        cp = pltpu.make_async_copy(acc_ref, count_ref, sem)
        cp.start()
        cp.wait()


def _store_decisions(matched_ref, conflicts_ref, matched, conflicts, spec):
    shape = matched_ref.shape
    matched_ref[...] = _col_to_slab(
        matched.astype(jnp.int32), shape).astype(spec.counter_dtype)
    conflicts_ref[...] = _col_to_slab(conflicts, shape).astype(
        spec.counter_dtype)


def _state_cell(ref):
    """One-block ``StateCell`` over a whole state-slab ref."""

    def _set(state):
        ref[...] = state[0]

    return engine.StateCell(get=lambda: (ref[...],), set=_set)


def skipper_window_kernel(
    u_ref,
    v_ref,
    state_in_ref,
    state_ref,
    matched_ref,
    conflicts_ref,
    fallback_ref,
    count_ref,
    count_sem,
    *,
    vector_rounds: int,
    window: int,
    fallback: bool,
    spec: StateSpec = DEFAULT,
):
    """One grid step = one tile of T window-local edges (1-D grid, one window).

    u_ref/v_ref: int32 id slab of the tile (-1 = padding).
    state_in_ref: spec.vmem state slab (read at step 0 only).
    state_ref: spec.vmem in/out VMEM-resident state slab (aliased).
    matched_ref: spec.counter slab, per-edge decision (1 = matched).
    conflicts_ref: spec.counter slab, rounds spent blocked (Table II
    instrumentation; conflicts <= vector_rounds, so the narrow store is
    exact — guarded by ``spec.validate_rounds`` at build time).
    fallback_ref: int32[1] in HBM, the tiles that took the fallback,
    counted in the SMEM scratch ``count_ref`` (``_count_fallback``).
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        state_ref[...] = state_in_ref[...]

    matched, conflicts, taken = _match_tile(
        u_ref[...], v_ref[...], _state_cell(state_ref), blocks=1,
        window=window, vector_rounds=vector_rounds, fallback=fallback,
    )
    _store_decisions(matched_ref, conflicts_ref, matched, conflicts, spec)
    _count_fallback(fallback_ref, count_ref, count_sem, step == 0,
                    step == pl.num_programs(0) - 1, taken)


def skipper_pipeline_kernel(
    u_ref,
    v_ref,
    state_in_ref,
    state_ref,
    matched_ref,
    conflicts_ref,
    fallback_ref,
    count_ref,
    count_sem,
    *,
    vector_rounds: int,
    window: int,
    fallback: bool,
    spec: StateSpec = DEFAULT,
):
    """One grid step = (window w, tile t). The window and tile axes of every
    block are squeezed, so the refs are the tile's id slabs and the window's
    state slab; the state block is swapped per *window*, not per step, so it
    is initialized when t == 0 and stays VMEM-resident for all tiles of w.
    The block dtype is ``spec.vmem`` — window * spec.vmem_bytes resident
    bytes per step. ``fallback_ref`` (int32[1], HBM) gets the count of the
    grid's tiles that took the fallback (``_count_fallback``)."""
    w = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        state_ref[...] = state_in_ref[...]

    matched, conflicts, taken = _match_tile(
        u_ref[...], v_ref[...], _state_cell(state_ref), blocks=1,
        window=window, vector_rounds=vector_rounds, fallback=fallback,
    )
    _store_decisions(matched_ref, conflicts_ref, matched, conflicts, spec)
    last = (w == pl.num_programs(0) - 1) & (t == pl.num_programs(1) - 1)
    _count_fallback(fallback_ref, count_ref, count_sem, (w == 0) & (t == 0),
                    last, taken)


def pair_tile(u_ref, v_ref, matched_ref, conflicts_ref, pair_ref, *,
              vector_rounds: int, window: int, fallback: bool,
              spec: StateSpec):
    """The boundary kernel's tile body, between the loads and the
    write-back: the two ``pair_ref`` scratch slabs are
    ``engine.tile_pass_pair``'s concatenated state."""

    def _set_pair(state):
        pair_ref[0] = state[0]
        pair_ref[1] = state[1]

    cell = engine.StateCell(
        get=lambda: (pair_ref[0], pair_ref[1]), set=_set_pair
    )
    matched, conflicts, _ = _match_tile(
        u_ref[...], v_ref[...], cell, blocks=2, window=window,
        vector_rounds=vector_rounds, fallback=fallback,
    )
    _store_decisions(matched_ref, conflicts_ref, matched, conflicts, spec)


def skipper_boundary_kernel(
    blk_u_ref,
    blk_v_ref,
    u_ref,
    v_ref,
    state_in_ref,
    state_ref,
    matched_ref,
    conflicts_ref,
    pair_ref,
    sem_u,
    sem_v,
    *,
    vector_rounds: int,
    window: int,
    fallback: bool,
    spec: StateSpec = DEFAULT,
):
    """One grid step = one tile of T global-tier edges, all sharing ONE
    (window-block of u, window-block of v) pair — the host schedule groups
    the stream so this holds by construction (``graphs/windows.py``,
    DESIGN.md §10).

    blk_u_ref/blk_v_ref are the scalar-prefetch per-tile block ids; the full
    state, one slab per window, lives in ANY memory (HBM), aliased in/out,
    and each step manually DMAs the pair's two state slabs into the
    (2, W/128, 128) VMEM ``pair_ref`` scratch. Edge ids are OFFSET-LOCAL: u
    in [0, W), v in [W, 2W) for cross-block pairs and [0, W) for same-block
    pairs, so the two scratch slabs together are exactly the concatenated
    state of ``engine.tile_pass_pair`` — the jnp reference epilogue is
    bit-identical by construction, and the gather/scatter are one-hot
    matmuls like the windowed kernel (no dynamic fancy indexing).

    Aliasing contract: writes go back v-row first, u-row second, both before
    the step ends (DMA waits serialize them), so a later pair (b, c) reads
    the commits of an earlier pair (a, b), and same-block pairs — which load
    only the u row and leave the v half of the scratch untouched — store the
    u row last so it wins unconditionally.

    VMEM per grid step: 2 * window * spec.vmem_bytes of state + the
    T x (2W/128) one-hots + the T x T share matrix — O(window + tile^2),
    independent of V.
    """
    i = pl.program_id(0)
    bu = blk_u_ref[i]
    bv = blk_v_ref[i]

    cp_u = pltpu.make_async_copy(state_ref.at[bu], pair_ref.at[0], sem_u)
    cp_u.start()
    cp_u.wait()

    @pl.when(bv != bu)
    def _load_v():
        cp = pltpu.make_async_copy(state_ref.at[bv], pair_ref.at[1], sem_v)
        cp.start()
        cp.wait()

    pair_tile(u_ref, v_ref, matched_ref, conflicts_ref, pair_ref,
              vector_rounds=vector_rounds, window=window, fallback=fallback,
              spec=spec)

    # write-back: v row first, u row second (same-block pairs skip v and the
    # u row — the only row touched — lands last; see tile_pass_pair)
    @pl.when(bv != bu)
    def _store_v():
        cp = pltpu.make_async_copy(pair_ref.at[1], state_ref.at[bv], sem_v)
        cp.start()
        cp.wait()

    cp_u2 = pltpu.make_async_copy(pair_ref.at[0], state_ref.at[bu], sem_u)
    cp_u2.start()
    cp_u2.wait()


# The scalar-prefetched pair block ids live in SMEM (1 MiB on v5e, two
# int32 per tile), so one pallas_call covers at most this many tiles; longer
# global tiers run as a scan of such calls over the aliased state.
PREFETCH_TILES = 16384


def _boundary_pallas_call(kernel, num_tiles, tile, num_windows, srow,
                          interpret, spec):
    edges = pl.BlockSpec((None,) + tile, lambda i, bu, bv: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_tiles,),
        in_specs=[
            edges,                                # u tiles
            edges,                                # v tiles
            pl.BlockSpec(memory_space=pl.ANY),    # state
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),    # state
            edges,                                # matched
            edges,                                # conflicts
        ],
        scratch_shapes=[
            pltpu.VMEM((2,) + srow, spec.vmem_dtype),  # the pair's state slabs
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_windows,) + srow, spec.vmem_dtype),
            jax.ShapeDtypeStruct((num_tiles,) + tile, spec.counter_dtype),
            jax.ShapeDtypeStruct((num_tiles,) + tile, spec.counter_dtype),
        ],
        # state input (after the 2 prefetch scalars + u + v) -> state output
        input_output_aliases={4: 0},
        interpret=interpret,
        name=kernel.func.__name__,
    )


def boundary_call(kernel_fn, num_tiles: int, tile_size: int,
                  num_windows: int, window: int, vector_rounds: int = 1,
                  fallback: bool = True, interpret: bool = True,
                  spec: StateSpec = DEFAULT):
    """The scalar-prefetch block-pair ``pallas_call`` around ``kernel_fn``
    (``skipper_boundary_kernel``'s signature), with its flat-array wrapper.

    Call the result as ``fn(blk_u, blk_v, u, v, state)`` with blk_u/blk_v
    int32[num_tiles] pair block ids (scalar-prefetched), u/v
    int32[num_tiles, tile_size] OFFSET-LOCAL ids (-1 padding), and state
    spec.vmem[num_windows, window] (aliased in/out — the caller's buffer is
    donated, so its dtype must match the spec). Returns (state, matched,
    conflicts) with matched/conflicts shaped spec.counter[num_tiles,
    tile_size]. Tiles run in stream order: a scan of ``PREFETCH_TILES``-tile
    calls, then one call for the rest."""
    spec.validate_rounds(vector_rounds)
    kernel = functools.partial(
        kernel_fn,
        vector_rounds=vector_rounds,
        window=window,
        fallback=fallback,
        spec=spec,
    )
    tile = slab_shape(tile_size)
    srow = slab_shape(window)
    full, rest = divmod(num_tiles, PREFETCH_TILES)
    chunk = _boundary_pallas_call(
        kernel, PREFETCH_TILES, tile, num_windows, srow, interpret, spec
    ) if full else None
    tail = _boundary_pallas_call(
        kernel, rest, tile, num_windows, srow, interpret, spec
    ) if rest else None

    def run(blk_u, blk_v, u, v, state):
        u = u.reshape((num_tiles,) + tile)
        v = v.reshape((num_tiles,) + tile)
        state = state.reshape((num_windows,) + srow)
        outs = []
        if full:
            cut = full * PREFETCH_TILES

            def step(st, xs):
                st, m, c = chunk(*xs, st)
                return st, (m, c)

            state, (m, c) = jax.lax.scan(step, state, tuple(
                a[:cut].reshape((full, PREFETCH_TILES) + a.shape[1:])
                for a in (blk_u, blk_v, u, v)
            ))
            outs.append((m.reshape((cut,) + tile), c.reshape((cut,) + tile)))
        if rest:
            cut = full * PREFETCH_TILES
            state, m, c = tail(blk_u[cut:], blk_v[cut:], u[cut:], v[cut:],
                               state)
            outs.append((m, c))
        matched = jnp.concatenate([m for m, _ in outs])
        conflicts = jnp.concatenate([c for _, c in outs])
        return (
            state.reshape(num_windows, window),
            matched.reshape(num_tiles, tile_size),
            conflicts.reshape(num_tiles, tile_size),
        )

    return run


@functools.lru_cache(maxsize=None)
def build_boundary_matcher(
    num_tiles: int,
    tile_size: int,
    num_windows: int,
    window: int,
    vector_rounds: int = 1,
    fallback: bool = True,
    interpret: bool = True,
    spec: StateSpec = DEFAULT,
):
    """Construct the scalar-prefetch pallas_call resolving the block-pair
    grouped global-tier stream (``boundary_call`` documents the calling
    convention). Cached per static shape+spec so repeated ``skipper_match``
    calls reuse one pallas_call (and one trace)."""
    return boundary_call(
        skipper_boundary_kernel, num_tiles, tile_size, num_windows, window,
        vector_rounds, fallback, interpret, spec,
    )


# The window-tier kernels' fallback count: one int32 for the whole grid,
# summed in SMEM scratch and copied to HBM by the last step
# (``_count_fallback``).
_FALLBACK_COUNT = pl.BlockSpec(memory_space=pl.ANY)
_FALLBACK_COUNT_SHAPE = jax.ShapeDtypeStruct((1,), jnp.int32)
_FALLBACK_SCRATCH = [pltpu.SMEM((1,), jnp.int32), pltpu.SemaphoreType.DMA]


@functools.lru_cache(maxsize=None)
def build_window_matcher(
    num_tiles: int,
    tile_size: int,
    window: int,
    vector_rounds: int = 1,
    fallback: bool = True,
    interpret: bool = True,
    spec: StateSpec = DEFAULT,
):
    """Construct the pallas_call for a (num_tiles x tile_size) edge stream
    over a single ``window``-vertex state window. Call as ``fn(u, v,
    state0)`` with u/v int32[num_tiles * tile_size] and state0
    spec.vmem[window]; returns (state, matched, conflicts, fallback_tiles)
    — the first three in the same flat shapes (state in ``spec.vmem``,
    matched/conflicts in ``spec.counter``), then the int32 count of tiles
    that took the exact fallback."""
    spec.validate_rounds(vector_rounds)
    kernel = functools.partial(
        skipper_window_kernel,
        vector_rounds=vector_rounds,
        window=window,
        fallback=fallback,
        spec=spec,
    )
    tile = slab_shape(tile_size)
    srow = slab_shape(window)
    edges = pl.BlockSpec((None,) + tile, lambda i: (i, 0, 0))
    state = pl.BlockSpec(srow, lambda i: (0, 0))
    call = pl.pallas_call(
        kernel,
        grid=(num_tiles,),
        in_specs=[edges, edges, state],
        out_specs=[state, edges, edges, _FALLBACK_COUNT],
        out_shape=[
            jax.ShapeDtypeStruct(srow, spec.vmem_dtype),
            jax.ShapeDtypeStruct((num_tiles,) + tile, spec.counter_dtype),
            jax.ShapeDtypeStruct((num_tiles,) + tile, spec.counter_dtype),
            _FALLBACK_COUNT_SHAPE,
        ],
        scratch_shapes=_FALLBACK_SCRATCH,
        interpret=interpret,
        name=kernel.func.__name__,
    )

    def run(u, v, state0):
        state, matched, conflicts, taken = call(
            u.reshape((num_tiles,) + tile), v.reshape((num_tiles,) + tile),
            state0.reshape(srow),
        )
        return (state.reshape(window), matched.reshape(-1),
                conflicts.reshape(-1), taken[0])

    return run


@functools.lru_cache(maxsize=None)
def build_pipeline_matcher(
    num_windows: int,
    tiles_per_window: int,
    tile_size: int,
    window: int,
    vector_rounds: int = 1,
    fallback: bool = True,
    interpret: bool = True,
    spec: StateSpec = DEFAULT,
):
    """Construct ONE pallas_call covering every (window, tile) of the graph's
    schedule.

    Inputs: u/v int32[num_windows, tiles_per_window * tile_size] window-local
    ids, state0 spec.vmem[num_windows, window]. Outputs: (state, matched,
    conflicts, fallback_tiles) — state in spec.vmem, matched/conflicts in
    spec.counter, in the input shapes, and the int32 count of the grid's
    tiles that took the exact fallback. The state index map
    ``(w, t) -> (w, 0, 0)`` ignores t:
    the revolving VMEM block is written back only when w changes — one HBM
    round-trip per window, zero host round-trips.
    """
    spec.validate_rounds(vector_rounds)
    kernel = functools.partial(
        skipper_pipeline_kernel,
        vector_rounds=vector_rounds,
        window=window,
        fallback=fallback,
        spec=spec,
    )
    tile = slab_shape(tile_size)
    srow = slab_shape(window)
    edges = pl.BlockSpec((None, None) + tile, lambda w, t: (w, t, 0, 0))
    state = pl.BlockSpec((None,) + srow, lambda w, t: (w, 0, 0))
    eshape = (num_windows, tiles_per_window) + tile
    call = pl.pallas_call(
        kernel,
        grid=(num_windows, tiles_per_window),
        in_specs=[edges, edges, state],
        # state resident per window
        out_specs=[state, edges, edges, _FALLBACK_COUNT],
        out_shape=[
            jax.ShapeDtypeStruct((num_windows,) + srow, spec.vmem_dtype),
            jax.ShapeDtypeStruct(eshape, spec.counter_dtype),
            jax.ShapeDtypeStruct(eshape, spec.counter_dtype),
            _FALLBACK_COUNT_SHAPE,
        ],
        scratch_shapes=_FALLBACK_SCRATCH,
        interpret=interpret,
        name=kernel.func.__name__,
    )

    def run(u, v, state0):
        state, matched, conflicts, taken = call(
            u.reshape(eshape), v.reshape(eshape),
            state0.reshape((num_windows,) + srow),
        )
        slots = tiles_per_window * tile_size
        return (
            state.reshape(num_windows, window),
            matched.reshape(num_windows, slots),
            conflicts.reshape(num_windows, slots),
            taken[0],
        )

    return run
