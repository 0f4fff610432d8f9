"""Pure-jnp oracle for the skipper_match kernels.

Implements *bit-identical* semantics to ``kernel.skipper_window_kernel`` /
``kernel.skipper_pipeline_kernel`` (same tile order, same vector rounds, same
first-claim rule, same fallback), so tests can assert exact equality of the
matched mask and final state, plus the algorithm-level properties (validity,
maximality) against core.sgmm.

Both the kernel and this oracle consume ``core/engine.py`` for the conflict
matrix and commit rule; only the gather/scatter differs (MXU one-hot matmuls
there, ``.at`` indexing here), which is exactly the part exact-equality tests
pin down.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.statespec import StateSpec, resolve as resolve_spec


@partial(jax.jit, static_argnames=("vector_rounds", "fallback", "spec"))
def ref_match_window(
    u_tiles: jax.Array,   # int32[num_tiles, T]
    v_tiles: jax.Array,   # int32[num_tiles, T]
    state0: jax.Array,    # spec.vmem[W]
    vector_rounds: int = 1,
    fallback: bool = True,
    spec: StateSpec | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (state, matched spec.counter[num_tiles*T], conflicts[...],
    fallback_tiles int32). ``state0``'s dtype is the caller's;
    matched/conflicts follow the spec like ``build_window_matcher``'s
    outputs do."""
    spec = resolve_spec(spec)
    w = state0.shape[0]
    cdt = spec.counter_dtype

    def tile_step(state, uv):
        u, v = uv
        state, matched, conflicts, taken = engine.tile_pass(
            state, u, v, n=w, vector_rounds=vector_rounds, fallback=fallback,
            spec=spec,
        )
        return state, (matched.astype(cdt), conflicts, taken)

    state, (matched, conflicts, taken) = jax.lax.scan(
        tile_step, state0, (u_tiles, v_tiles))
    return (state, matched.reshape(-1), conflicts.reshape(-1),
            jnp.sum(taken, dtype=jnp.int32))


def make_ref_pipeline(window: int, vector_rounds: int = 1,
                      spec: StateSpec | None = None):
    """Build the jnp twin of ``build_pipeline_matcher`` for a fixed window
    size: every window starts from all-ACC state and runs its tiles in order.

    ONE flat sequential scan over the (row, tile) steps, tile innermost —
    exactly the Pallas grid's iteration order, so decisions are
    bit-identical; the state carry is reset to all-ACC at each row's first
    tile (the revolving VMEM block's re-initialization). Windows are
    independent, so a vmap over rows would also be correct — but under vmap
    the fallback ``while_loop`` pays the batch-max iteration count on every
    row and ``lax.cond`` can't skip, which measured ~2-4x slower on CPU than
    this serial form (the XLA twin exists to be timed on CPU; the Pallas
    path owns the parallel hardware). A scan-of-scans over (rows, tiles)
    is equivalent but measured ~20% slower (per-row output stacking).

    State and counter widths come from the spec (``core/statespec.py``):
    the default carries uint8 end-to-end — the paper's 1 B/vertex encoding —
    and the engine compares against plain ints so any width computes the
    same values (bit-equal across specs, test-pinned). The twin and the
    Pallas kernel share the spec, so their output *dtypes* match too.

    The returned callable maps (u_tiles, v_tiles)
    int32[num_rows, tiles_per_window, T] (window-local ids) to
    (state spec.vmem[num_rows, window], matched spec.counter[num_rows,
    tpw*T], conflicts spec.counter[...], fallback_tiles int32): the last is
    the count of tiles that took the exact fallback, as the kernel counts.
    """
    spec = resolve_spec(spec)
    cdt = spec.counter_dtype

    def run(u3, v3):
        num_rows, tpw, t = u3.shape
        uf = u3.reshape(num_rows * tpw, t)
        vf = v3.reshape(num_rows * tpw, t)
        steps = jnp.arange(num_rows * tpw, dtype=jnp.int32)
        fresh = steps % tpw == 0  # first tile of each row: reset the block

        def tile_step(state, uvf):
            u, v, fr = uvf
            state = jnp.where(fr, jnp.zeros_like(state), state)
            state, matched, conflicts, taken = engine.tile_pass(
                state, u, v, n=window, vector_rounds=vector_rounds, spec=spec
            )
            return state, (state, matched.astype(cdt), conflicts, taken)

        state0 = jnp.zeros((window,), spec.vmem_dtype)
        _, (states, matched, conflicts, taken) = jax.lax.scan(
            tile_step, state0, (uf, vf, fresh)
        )
        return (
            states[tpw - 1 :: tpw],          # each row's final state
            matched.reshape(num_rows, tpw * t),
            conflicts.reshape(num_rows, tpw * t),
            jnp.sum(taken, dtype=jnp.int32),
        )

    return run
