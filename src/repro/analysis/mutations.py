"""Seeded mutants that prove the analyzer has teeth.

Each mutant is a faithful copy of ``skipper_boundary_kernel`` (the kernel
with the richest invariant surface: manual DMA, ANY-memory aliasing,
ordered write-back) with exactly ONE conformance invariant broken:

* ``dropped_dma_wait``      — the u-row load's ``wait()`` is gone: the tile
  body reads ``pair_ref`` while the copy may still be in flight.
* ``swapped_writeback``     — write-back order inverted (u row first,
  v row last-and-conditional): same-block pairs now let a stale v row win,
  breaking the DESIGN.md §10 aliasing contract.
* ``dynamic_gather``        — the one-hot matmul gather replaced by traced
  fancy indexing on the VMEM scratch (the exact pattern that blocks Mosaic
  lowering and that PR 5 removed).
* ``hardcoded_state_dtype`` — a SOURCE fixture (string, materialized to a
  temp file at analysis time — it cannot live as a real module here or the
  tree-wide state-dtype rule would flag the repo itself) that allocates a
  state buffer with a literal dtype instead of ``StateSpec``.

``tests/test_analysis.py`` and the CI canary assert each mutant yields a
rule-named ERROR finding; a mutant that analyzes clean means the analyzer
lost its teeth and fails the build.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import engine
from repro.core.statespec import DEFAULT, StateSpec
from repro.kernels.skipper_match.kernel import (
    _boundary_pallas_call,
    _store_decisions,
    pair_tile,
    slab_shape,
)

_TILE = 256
_WINDOW = 256
_NUM_WINDOWS = 4


def _mutant_dropped_dma_wait(
    blk_u_ref, blk_v_ref, u_ref, v_ref, state_in_ref, state_ref,
    matched_ref, conflicts_ref, pair_ref, sem_u, sem_v,
    *, vector_rounds: int, window: int, fallback: bool, spec: StateSpec,
):
    """Boundary kernel minus the u-row load wait (use-before-arrival race)."""
    i = pl.program_id(0)
    bu = blk_u_ref[i]
    bv = blk_v_ref[i]

    cp_u = pltpu.make_async_copy(state_ref.at[bu], pair_ref.at[0], sem_u)
    cp_u.start()
    # MUTATION: cp_u.wait() dropped — pair_ref[0] may not have landed.

    @pl.when(bv != bu)
    def _load_v():
        cp = pltpu.make_async_copy(state_ref.at[bv], pair_ref.at[1], sem_v)
        cp.start()
        cp.wait()

    pair_tile(u_ref, v_ref, matched_ref, conflicts_ref, pair_ref,
              vector_rounds=vector_rounds, window=window, fallback=fallback,
              spec=spec)

    @pl.when(bv != bu)
    def _store_v():
        cp = pltpu.make_async_copy(pair_ref.at[1], state_ref.at[bv], sem_v)
        cp.start()
        cp.wait()

    cp_u2 = pltpu.make_async_copy(pair_ref.at[0], state_ref.at[bu], sem_u)
    cp_u2.start()
    cp_u2.wait()


def _mutant_swapped_writeback(
    blk_u_ref, blk_v_ref, u_ref, v_ref, state_in_ref, state_ref,
    matched_ref, conflicts_ref, pair_ref, sem_u, sem_v,
    *, vector_rounds: int, window: int, fallback: bool, spec: StateSpec,
):
    """Boundary kernel with the write-back order inverted (u first, v last)."""
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

    # MUTATION: u row stored FIRST, v row last (and conditionally) — a
    # same-block pair's only meaningful row no longer wins unconditionally.
    cp_u2 = pltpu.make_async_copy(pair_ref.at[0], state_ref.at[bu], sem_u)
    cp_u2.start()
    cp_u2.wait()

    @pl.when(bv != bu)
    def _store_v():
        cp = pltpu.make_async_copy(pair_ref.at[1], state_ref.at[bv], sem_v)
        cp.start()
        cp.wait()


def _mutant_dynamic_gather(
    blk_u_ref, blk_v_ref, u_ref, v_ref, state_in_ref, state_ref,
    matched_ref, conflicts_ref, pair_ref, sem_u, sem_v,
    *, vector_rounds: int, window: int, fallback: bool, spec: StateSpec,
):
    """Boundary kernel with the one-hot MXU gather replaced by traced fancy
    indexing on the VMEM scratch — the pre-PR-5 pattern Mosaic cannot lower."""
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

    u = u_ref[...].reshape(-1)
    v = v_ref[...].reshape(-1)
    valid = (u >= 0) & (u != v)
    flat = jnp.concatenate([pair_ref[0].reshape(-1), pair_ref[1].reshape(-1)])
    # MUTATION: data-dependent vector gather (jaxpr `gather` with a traced
    # index operand) instead of the one-hot matmul gather.
    su = flat[jnp.where(valid, u, 0)]
    sv = flat[jnp.where(valid, v, 0)]
    matched = valid & (su == 0) & (sv == 0)

    hit = jnp.zeros(flat.shape, jnp.int32)
    hit = hit.at[jnp.where(matched, u, 0)].max(matched.astype(jnp.int32))
    hit = hit.at[jnp.where(matched, v, 0)].max(matched.astype(jnp.int32))
    flat = jnp.where(hit > 0, engine.MCHD, flat).astype(spec.vmem_dtype)
    pair_ref[0] = flat[:window].reshape(pair_ref.shape[1:])
    pair_ref[1] = flat[window:].reshape(pair_ref.shape[1:])

    _store_decisions(matched_ref, conflicts_ref, matched[:, None],
                     jnp.zeros((u.shape[0], 1), jnp.int32), spec)

    @pl.when(bv != bu)
    def _store_v():
        cp = pltpu.make_async_copy(pair_ref.at[1], state_ref.at[bv], sem_v)
        cp.start()
        cp.wait()

    cp_u2 = pltpu.make_async_copy(pair_ref.at[0], state_ref.at[bu], sem_u)
    cp_u2.start()
    cp_u2.wait()


# Source-rule fixture: a literal state dtype outside core/statespec. Kept as
# a string so the repo-wide state-dtype scan stays clean; the runner writes
# it to a temp file and lints that.
HARDCODED_STATE_DTYPE_SRC = '''\
"""Mutation fixture: hard-coded state dtype (must trip the state-dtype rule)."""
import jax.numpy as jnp


def make_state(num_vertices):
    state = jnp.zeros((num_vertices,), dtype=jnp.int32)
    return state
'''


def _build_mutant_call(kernel_fn, spec: StateSpec = DEFAULT):
    """Wrap a mutant kernel in the production boundary grid spec
    (``kernel._boundary_pallas_call``) at the canonical shapes."""
    num_tiles, tile_size = 2, _TILE
    num_windows, window = _NUM_WINDOWS, _WINDOW
    spec.validate_rounds(1)
    kernel = functools.partial(
        kernel_fn, vector_rounds=1, window=window, fallback=True, spec=spec
    )
    tile, srow = slab_shape(tile_size), slab_shape(window)
    call = _boundary_pallas_call(
        kernel, num_tiles, tile, num_windows, srow, True, spec
    )
    blk = jax.ShapeDtypeStruct((num_tiles,), jnp.int32)
    uv = jax.ShapeDtypeStruct((num_tiles,) + tile, jnp.int32)
    st = jax.ShapeDtypeStruct((num_windows,) + srow, spec.vmem_dtype)
    return jax.make_jaxpr(call)(blk, blk, uv, uv, st)


KERNEL_MUTATIONS = {
    "dropped_dma_wait": _mutant_dropped_dma_wait,
    "swapped_writeback": _mutant_swapped_writeback,
    "dynamic_gather": _mutant_dynamic_gather,
}

SOURCE_MUTATIONS = {
    "hardcoded_state_dtype": HARDCODED_STATE_DTYPE_SRC,
}

MUTATION_NAMES = sorted(KERNEL_MUTATIONS) + sorted(SOURCE_MUTATIONS)


def trace_kernel_mutation(name: str, spec: StateSpec = DEFAULT):
    return _build_mutant_call(KERNEL_MUTATIONS[name], spec)
