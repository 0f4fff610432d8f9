"""Shared types for the matching core.

Vertex states follow the paper (Alg. 1): ACC(0) accessible, RSVD(1) reserved,
MCHD(2) matched. The at-rest state array is uint8 — the paper's "one byte per
vertex" memory claim (§I, §IV) preserved verbatim. Per-tier widths (VMEM,
wire, counters) live in ``core/statespec.py``; ``STATE_DTYPE`` here is the
default spec's at-rest dtype, kept as the legacy alias most callers use.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.statespec import DEFAULT as DEFAULT_STATE_SPEC

STATE_DTYPE = DEFAULT_STATE_SPEC.at_rest_dtype

ACC = STATE_DTYPE(0)
RSVD = STATE_DTYPE(1)
MCHD = STATE_DTYPE(2)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Counters:
    """Work-efficiency instrumentation (paper §VI-C, Fig. 7).

    All counts are *memory accesses* in the paper's sense: loads + stores of
    the shared state array plus edge-topology reads. Derived analytically from
    what each algorithm actually touches, mirroring the PAPI counters used in
    the paper.
    """

    edge_reads: jax.Array       # topology loads (each edge endpoint pair = 1)
    state_loads: jax.Array      # loads of state[]
    state_stores: jax.Array     # stores to state[]
    rounds: jax.Array           # iterations / passes over (parts of) the graph
    # window-tier tiles that took the exact fallback (the device-resident
    # pipeline counts them; None where a matcher does not)
    fallback_tiles: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.edge_reads, self.state_loads, self.state_stores,
                self.rounds, self.fallback_tiles), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def total_accesses(self) -> jax.Array:
        return self.edge_reads + self.state_loads + self.state_stores

    @staticmethod
    def zeros() -> "Counters":
        z = jnp.zeros((), jnp.int32)
        return Counters(z, z, z, z)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MatchResult:
    """Output of a matcher.

    match_mask: bool[|E|] aligned with the input edge order — True iff that
        edge was selected. (The paper emits per-thread match buffers; a mask
        over the single-pass edge stream is the equivalent, order-preserving
        representation and what the validators consume.)
    state: uint8[|V|] final vertex states (ACC or MCHD; RSVD never survives).
    counters: work instrumentation.
    """

    match_mask: jax.Array
    state: jax.Array
    counters: Counters

    def tree_flatten(self):
        return (self.match_mask, self.state, self.counters), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def num_matches(self) -> jax.Array:
        return jnp.sum(self.match_mask)
