"""Capacity-constrained bipartite b-matching — the Skipper technique applied
to MoE token-expert assignment, as a standalone entry point
(``bmatch_assign``; DESIGN.md §3, §9).

Problem: tokens x experts, candidate edges (t, e) with router scores; each
token may take at most ``token_budget`` experts, each expert at most
``expert_capacity`` tokens. A maximal b-matching over the score-sorted edge
stream is the single-pass analogue of auction/Sinkhorn routing.

Since PR 4 this module is a THIN ADAPTER over the shared claim engine: the
round/fallback machinery lives in ``core/engine.py`` (the capacitated
first-K-claim generalization — ``tile_pass_capacitated`` built on
``run_first_claim_rounds`` / ``greedy_fallback_rounds``), so the b-matching
inherits every engine speedup (per-side blocked implementations, future
Pallas tiling) for free, and its output is *exactly* the sequential greedy
over the score-sorted stream: accept each edge iff, at its stream position,
its token still has budget and its expert still has capacity (test-pinned
against a numpy oracle). The previous private implementation's one-commit-
per-token-per-round rule and vmap-degrading ``lax.cond`` + ``lax.scan``
fallback (the same pathology PR 2 removed from the engine) are gone.

Work: O(#candidate edges), one pass, no iteration over the token set — the
same work-efficiency story the paper tells for graphs.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.statespec import StateSpec, resolve as resolve_spec

# Default unrolled rounds per tile. NOT a correctness knob (the engine's
# exact fallback reaches the sequential-greedy fixpoint from any unroll
# depth — rounds-invariance is test-pinned), but unlike the unipartite
# matchers the capacitated default is 2, not 1: the score-sorted MoE stream
# is *structurally* contended — hot experts draw claimants until they fill,
# and a token's budget-k candidates land in the same tile — so round-2 work
# is common rather than Θ(λ²)-rare. Round 1 commits each vertex's first
# `room` claims; round 2 retires the cross-side chains that round 1's
# commits unblock (see DESIGN.md §9). With vector_rounds=1 those chains fall
# into the while_loop fallback, which under a caller's vmap over token
# groups costs every group the batch-max iteration count;
# tests/test_bipartite.py::test_rounds_sensitivity pins both the invariance
# and the round-2 economics.
BMATCH_VECTOR_ROUNDS = 2


@partial(
    jax.jit,
    static_argnames=(
        "num_tokens",
        "num_experts",
        "token_budget",
        "expert_capacity",
        "tile_size",
        "vector_rounds",
        "conflict_method",
        "with_stats",
        "spec",
    ),
)
def bmatch_assign(
    token_ids: jax.Array,
    expert_ids: jax.Array,
    *,
    num_tokens: int,
    num_experts: int,
    token_budget: int,
    expert_capacity: int,
    tile_size: int = 1024,
    vector_rounds: int = BMATCH_VECTOR_ROUNDS,
    conflict_method: str = "auto",
    with_stats: bool = False,
    spec: Optional[StateSpec] = None,
) -> Union[jax.Array, Tuple[jax.Array, Dict[str, jax.Array]]]:
    """Greedy maximal b-matching over a (pre-sorted) candidate edge stream.

    token_ids/expert_ids: int32[M] candidate edges, highest score first;
    invalid candidates marked token_id = -1. Returns bool[M] accept mask —
    exactly the sequential greedy: edge i is accepted iff at stream position
    i its token has budget left and its expert has capacity left.

    The work is ``engine.tile_pass_capacitated`` scanned over
    ``tile_size``-edge tiles with the per-side used counts as carry
    (DESIGN.md §9); ``conflict_method`` is forwarded to the engine's
    per-side rank implementations (``"auto"`` picks the one-hot prefix for
    the expert side and claim-sort for the token side at typical sizes —
    never changes output).

    ``with_stats=True`` additionally returns
    ``{"conflicts": int32, "fallback_tiles": int32}`` — total blocked-round
    count (Table II analogue) and how many tiles entered the exact
    while_loop fallback (the rounds-sensitivity instrumentation).

    ``spec`` (``core/statespec.StateSpec``) sets the used-count width —
    used counts ARE this problem's vertex state, so the default spec keeps
    them at 1 B per token/expert whenever the static budgets fit the
    at-rest dtype (``validate_capacity``), falling back to the i32
    accumulator width otherwise; the engine widens to i32 at the gather
    either way. Stats accumulate in int32 regardless of the spec.
    """
    spec = resolve_spec(spec)
    fits = spec.validate_capacity(max(token_budget, expert_capacity))
    used_dt = spec.at_rest_dtype if fits else spec.accum_dtype
    m = token_ids.shape[0]
    pad = (-m) % tile_size
    tok = jnp.concatenate(
        [token_ids.astype(jnp.int32), jnp.full((pad,), -1, jnp.int32)]
    )
    exp = jnp.concatenate(
        [expert_ids.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)]
    )
    num_tiles = tok.shape[0] // tile_size
    tok = tok.reshape(num_tiles, tile_size)
    exp = exp.reshape(num_tiles, tile_size)

    def tile_step(carry, te):
        used_t, used_e = carry
        (used_t, used_e), matched, conflicts, fb = engine.tile_pass_capacitated(
            used_t, used_e, te[0], te[1],
            cap_u=token_budget, cap_v=expert_capacity,
            vector_rounds=vector_rounds, conflict_method=conflict_method,
        )
        return (used_t, used_e), (matched, conflicts, fb)

    carry0 = (
        jnp.zeros((num_tokens,), used_dt),
        jnp.zeros((num_experts,), used_dt),
    )
    _, (matched, conflicts, fb) = jax.lax.scan(tile_step, carry0, (tok, exp))
    accept = matched.reshape(-1)[:m]
    if with_stats:
        # conflicts come back i32 from the engine (no spec forwarded — they
        # are summed here and must not wrap at a narrow width)
        stats = {
            "conflicts": jnp.sum(conflicts.astype(jnp.int32)),
            "fallback_tiles": jnp.sum(fb.astype(jnp.int32)),
        }
        return accept, stats
    return accept
