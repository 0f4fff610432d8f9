"""StateSpec — the single source of truth for vertex-state width.

Skipper's memory claim is "a single byte per vertex". Historically this
repro honored that only *at rest* (``types.STATE_DTYPE = uint8``): the
Pallas VMEM window state, the aliased ANY-memory state in the block-pair
boundary epilogue, the distributed O(V) state assembly, and the per-edge
matched/conflict outputs were all ``int32`` — 4x the paper's footprint in
every hot tier and 4x the collective payload.

``StateSpec`` names one dtype per tier and every layer takes the spec
instead of hardcoding a width:

====================  =====================================================
field                 governs
====================  =====================================================
``at_rest``           HBM / returned vertex-state arrays (``MatchResult``,
                      residual-replay rebuilds, ``skipper()`` init state)
``vmem``              kernel-tier working state: Pallas VMEM window blocks,
                      the boundary kernel's ANY-memory state + pair
                      scratch, and the XLA twin's scan carry
``wire``              distributed state-assembly payload (the O(V)
                      cross-device combine in the sharded matcher)
``counter``           per-edge matched/conflicts output arrays (the O(E)
                      buffers written by the kernels and the twin)
``accum``             index math and one-hot/matmul accumulators — always
                      ``int32``; the MXU gathers widen state to this dtype
                      *inside* the kernel (``hu @ state`` promotes u8 to
                      i32) and narrow back only at the scatter
``combine``           state-assembly combine policy: ``"max"`` (width
                      honest — rows are device-disjoint so ``pmax`` is
                      exact at any width and cannot overflow) or
                      ``"psum"`` (the legacy i32 graph)
====================  =====================================================

Two blessed specs:

* ``StateSpec.u8()`` (the module ``DEFAULT``) — single-byte state in every
  tier; bit-identical matchings to legacy (pinned by
  ``tests/test_statespec.py``'s equivalence matrix).
* ``StateSpec.legacy_i32()`` — compiles the exact pre-refactor Pallas
  graph (i32 VMEM state, i32 counters, psum assembly) for A/B benching.

The spec is a frozen dataclass holding dtype *names* (strings), so it is
hashable and participates directly in every ``lru_cache`` key and jit
static argument along the build path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

_DTYPES = {"uint8": jnp.uint8, "int32": jnp.int32}
_DTYPE_BYTES = {"uint8": 1, "int32": 4}
_DTYPE_MAX = {"uint8": 255, "int32": 2**31 - 1}
_COMBINES = ("max", "psum")


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """Per-tier vertex-state widths (see module docstring for the table)."""

    at_rest: str = "uint8"
    vmem: str = "uint8"
    wire: str = "uint8"
    counter: str = "uint8"
    accum: str = "int32"
    combine: str = "max"

    def __post_init__(self):
        for field in ("at_rest", "vmem", "wire", "counter", "accum"):
            name = getattr(self, field)
            if name not in _DTYPES:
                raise ValueError(
                    f"StateSpec.{field}={name!r}: must be one of "
                    f"{sorted(_DTYPES)}")
        if self.combine not in _COMBINES:
            raise ValueError(
                f"StateSpec.combine={self.combine!r}: must be one of "
                f"{_COMBINES}")
        if self.accum != "int32":
            # index math / one-hot accumulators are what the MXU and the
            # scatter adds run in; nothing narrower is sound for V > 255
            raise ValueError("StateSpec.accum must be 'int32'")

    # --- dtypes ----------------------------------------------------------
    @property
    def at_rest_dtype(self):
        return _DTYPES[self.at_rest]

    @property
    def vmem_dtype(self):
        return _DTYPES[self.vmem]

    @property
    def wire_dtype(self):
        return _DTYPES[self.wire]

    @property
    def counter_dtype(self):
        return _DTYPES[self.counter]

    @property
    def accum_dtype(self):
        return _DTYPES[self.accum]

    # --- widths ----------------------------------------------------------
    @property
    def at_rest_bytes(self) -> int:
        return _DTYPE_BYTES[self.at_rest]

    @property
    def vmem_bytes(self) -> int:
        return _DTYPE_BYTES[self.vmem]

    @property
    def wire_bytes(self) -> int:
        return _DTYPE_BYTES[self.wire]

    @property
    def counter_bytes(self) -> int:
        return _DTYPE_BYTES[self.counter]

    # --- guards ----------------------------------------------------------
    def validate_rounds(self, vector_rounds: int) -> None:
        """Raise if the narrowed conflict counter cannot hold the bound.

        A conflict counter increments at most once per first-claim round,
        so ``conflicts <= vector_rounds`` and narrowing the O(E) conflicts
        output to ``counter`` is exact iff ``vector_rounds`` fits. (The
        fallback tier reports a boolean flag, not a count, so it never
        exceeds the bound.) Called by every kernel builder at build time.
        """
        if vector_rounds > _DTYPE_MAX[self.counter]:
            raise ValueError(
                f"vector_rounds={vector_rounds} overflows the "
                f"{self.counter} conflict counter (max "
                f"{_DTYPE_MAX[self.counter]}); use a wider "
                f"StateSpec.counter")

    def validate_capacity(self, cap: int) -> bool:
        """True iff a used-count bounded by ``cap`` fits ``at_rest``.

        The capacitated engine's used-counts are per-vertex state; they
        never exceed the static capacity, so the narrow width is exact iff
        the capacity itself fits. Callers fall back to ``accum`` when not.
        """
        return cap <= _DTYPE_MAX[self.at_rest]

    # --- distributed combine --------------------------------------------
    def combine_rows(self, rows, axis_name):
        """Width-honest cross-device combine of the O(V) state assembly.

        Each (row, slot) cell is written by exactly one device (the row
        owner) and is zero (ACC) everywhere else, so the per-cell combine
        over disjoint contributions is exact under ``max`` at ANY width:
        a real value v > 0 beats the zeros, and ties (all-zero) stay zero.
        ``psum`` is equally exact on disjoint rows but only at widths
        where ``num_devices * max_state_value`` cannot wrap — which is why
        the legacy i32 graph could use it and a u8 wire cannot.
        """
        if self.combine == "psum":
            return jax.lax.psum(rows, axis_name)
        return jax.lax.pmax(rows, axis_name)

    # --- blessed specs ---------------------------------------------------
    @classmethod
    def u8(cls) -> "StateSpec":
        """Single-byte state in every tier (the default)."""
        return cls()

    @classmethod
    def legacy_i32(cls) -> "StateSpec":
        """The exact pre-refactor graph: i32 kernel/wire state, i32
        counters, psum state assembly. At-rest state was already uint8."""
        return cls(at_rest="uint8", vmem="int32", wire="int32",
                   counter="int32", combine="psum")


DEFAULT = StateSpec()


def resolve(spec: "StateSpec | None") -> StateSpec:
    """Normalize an optional spec argument (None -> DEFAULT)."""
    return DEFAULT if spec is None else spec
