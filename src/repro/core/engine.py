"""The shared first-claim engine — Skipper's invariant in ONE place.

Every matcher in this repo (the single-device tiled matcher in
``core/skipper.py``, the shard_map distributed matcher in
``core/distributed.py``, the Pallas TPU kernel in
``kernels/skipper_match/kernel.py`` and its jnp oracle in
``kernels/skipper_match/ref.py``) enforces the same invariant, ported from the
paper's per-edge CAS protocol (Alg. 1):

    every edge is decided (matched / dead) at the moment it is touched, and an
    edge is dead only if one of its endpoints is already MCHD.

The vectorized form of that invariant is the *first-claim round* over a tile
of T edges:

    free_i    = both endpoints ACC and edge undecided
    blocked_i = exists j < i in the tile: free_j and edges i, j share an endpoint
    commit_i  = free_i and not blocked_i      # mutually endpoint-disjoint!

Since PR 4 the same invariant also exists in a *capacitated* form: the
first-K-claim round (``first_k_claim_commit`` + the ``ranks_*`` builders +
``tile_pass_capacitated``), which generalizes the reservation step to
per-side budgets (MoE token budgets / expert capacities — consumed by
``core/bipartite.bmatch_assign``) and degenerates bit-identically to the
unit-capacity rule at cap = 1. See DESIGN.md §9 and the section comment
above ``first_k_claim_commit``.

This module owns the pieces that must never drift between matchers. The
``blocked`` predicate has THREE interchangeable implementations computing
the exact same function (tests pin bit-equality across them):

* ``share_matrix`` + ``blocked_from_matrix`` — the triangular
  endpoint-sharing (JIT-conflict) matrix, O(T^2) VPU compares. Built with
  2-D ``broadcasted_iota`` so the exact same code traces inside a Pallas
  TPU kernel and in plain XLA; the T x T work is native MXU/VPU food, which
  is why the compiled kernel keeps it.
* ``blocked_by_claim_sort`` — per-vertex minimum free claimant via one sort
  of the tile's 2T endpoint slots: edge i is blocked iff some free edge
  j < i claims one of its endpoints, i.e. ``min(claimant(u_i),
  claimant(v_i)) < i``. O(T log T) — the CPU/XLA twin's hot-path version
  (~2.5x end-to-end on the jnp matchers, measured rmat14).
* ``blocked_by_claim_scatter`` — the same claimant function via scatter-min
  into a vertex-indexed [n] claim array; wins when n is small relative to
  the tile (window-local tiles).

``first_claim_commit`` turns gathered endpoint states plus a blocked
predicate into one round's commit/blocked decision. On top sit the standard
drivers:

* ``run_first_claim_rounds`` — the unrolled round loop, parameterized over the
  caller's gather/scatter (the kernel passes MXU one-hot matmuls closing over
  a VMEM ref; jnp callers pass ``.at`` indexing).
* ``greedy_fallback_rounds`` — the exact cleanup of edges that survive the
  unrolled rounds (long conflict chains): iterated first-claim rounds in a
  ``while_loop`` until no free edge remains. The fixpoint is *exactly* the
  sequential index-order greedy matching (see its docstring), so the result
  is identical to a scalar scan of the tile — but each iteration is one
  vectorized round, and under vmap/scan the loop costs only as many
  iterations as the worst surviving chain actually needs (a serial scan
  fallback under vmap degrades to always paying T steps: ``lax.cond``
  becomes ``select`` and runs both branches).
* ``tile_pass`` — the full jnp tile pass (rounds + exact fallback) consumed
  by the single-device and distributed matchers.
* ``tile_pass_pair`` — the two-block variant driving the block-pair
  boundary epilogue (DESIGN.md §10): slice two ``window``-sized state rows,
  run ``tile_pass`` on their concatenation with the schedule's offset-local
  ids, write the halves back. The Pallas pair kernel runs the same rounds +
  fallback over the same concatenation (DMA'd into VMEM scratch), so the
  jnp twin is bit-identical by construction.
* ``window_tier_pass`` — the shared *window tier* entry point: runs a
  ``[num_rows, tiles_per_window * tile_size]`` window-local schedule slab
  through the device-resident pipeline — the Pallas 2-D-grid kernel
  (``backend="pallas"``) or its bit-identical jnp twin (``"xla"``). Both
  ``kernels/skipper_match/ops.skipper_match`` and the distributed
  matcher's per-device LOCAL PASS (``core/distributed.py``) consume this
  one function, so the two matchers cannot drift.

State encoding is the paper's: ACC=0, MCHD=2 (comparisons below use plain
ints so they work at every ``StateSpec`` width — uint8 at-rest / VMEM state
and the legacy int32 graph alike; ``core/statespec.py`` is the single
source of truth for which tier carries which dtype).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.statespec import StateSpec, resolve as resolve_spec

ACC = 0
MCHD = 2


class StateCell:
    """One mutable state slot with ref-style ``cell[...]`` access — the ONE
    state-cell shim shared by every tile driver (replaces the ad-hoc ``_Row``
    / ``_Cell`` classes that used to live in the pipeline kernel and the two
    ``tile_pass`` variants).

    Backed either by a plain value (``StateCell(value)`` — the jnp tile
    passes thread jax arrays / pytrees through it) or by caller get/set
    closures (``StateCell(get=..., set=...)`` — the Pallas kernels' views
    over VMEM refs, e.g. the pipeline's state slab or the boundary
    kernel's two pair slabs). Only whole-cell ``cell[...]`` reads/writes
    are supported; the index is ignored.
    """

    __slots__ = ("_get", "_set", "value")

    def __init__(self, value=None, *, get=None, set=None):
        if get is None:
            self.value = value

            def get():
                return self.value

            def set(v):
                self.value = v

        self._get, self._set = get, set

    def __getitem__(self, _):
        return self._get()

    def __setitem__(self, _, value):
        self._set(value)


def share_matrix(u: jax.Array, v: jax.Array, valid: jax.Array,
                 rows=None) -> jax.Array:
    """conflict[i, j] = True iff j < i, both valid, and edges i, j share an
    endpoint. TPU-safe: strictly-lower-triangular mask via 2-D iota (Pallas
    TPU requires >= 2-D iota; XLA lowers it identically).

    Args: u/v int32[T] endpoint ids, valid bool[T] — or, inside a Pallas
    kernel, the [T, 1] columns with ``rows=(u, v, valid)`` their [1, T]
    rows (Mosaic cannot relayout a 1-D vector into either). Returns
    bool[T, T]. This is the JIT-conflict matrix of DESIGN.md §2 level 0;
    build it once per tile — it is free-mask independent and reused by
    every round."""
    if rows is None:
        rows = (u[None, :], v[None, :], valid[None, :])
        u, v, valid = u[:, None], v[:, None], valid[:, None]
    ur, vr, valid_r = rows
    t = u.shape[0]
    share = (u == ur) | (u == vr) | (v == ur) | (v == vr)
    row_id = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col_id = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return share & (col_id < row_id) & valid_r & valid


def blocked_from_matrix(conflict: jax.Array) -> Callable[[jax.Array], jax.Array]:
    """``blocked`` predicate from a precomputed ``share_matrix``: edge i is
    blocked iff some FREE j < i shares an endpoint. O(T^2) VPU compares —
    the Pallas kernel's version (T x T ops are native on the VPU and the
    matrix is built once per tile).

    Returns ``blocked_fn(free bool[T]) -> bool[T]`` for
    :func:`first_claim_commit` / :func:`run_first_claim_rounds`. Invariant
    (shared by all three builders, DESIGN.md §3 "Blocked-predicate
    implementations"): ``blocked_fn(free)[i]`` is True iff ``free[i]`` and
    some free ``j < i`` shares an endpoint with edge i — so the returned
    mask is always a subset of ``free``."""

    def blocked_fn(free):
        return jnp.any(conflict & free[None, :], axis=1) & free

    return blocked_fn


def blocked_by_claim_sort(
    u: jax.Array, v: jax.Array, valid: jax.Array, n: int
) -> Callable[[jax.Array], jax.Array]:
    """The same ``blocked`` function, via per-vertex minimum free claimant.

    For each vertex w let ``claimant(w) = min{ j : free_j and w is an
    endpoint of edge j }``; then ``exists free j < i sharing an endpoint``
    is exactly ``min(claimant(u_i), claimant(v_i)) < i`` (edge i itself
    claims at index i, which the strict ``<`` excludes). Computed with one
    sort of the tile's 2T (vertex, edge) slots on a composite int32 key —
    O(T log T) instead of O(T^2), ~2.5x end-to-end on the CPU/XLA matchers.

    The sort happens ONCE per tile (the (vertex, edge) order never changes);
    each round is then O(T): gather the free mask into slot order and
    scatter-min candidate claimants into the per-vertex runs. That keeps
    extra rounds (fallback iterations under vmap pay the batch-max) cheap.

    Requires ``(n + 1) * (T + 1) < 2^31`` (int32 composite key; e.g. n <=
    8M vertices at T = 256) — checked at trace time (a hard raise, not an
    assert: overflow would silently decode wrong claimants under ``-O``).

    Args: u/v int32[T], valid bool[T], n = number of vertices. Returns the
    same ``blocked_fn`` contract as :func:`blocked_from_matrix` (DESIGN.md
    §3 "Blocked-predicate implementations").
    """
    t = u.shape[0]
    if (n + 1) * (t + 1) >= 2**31:
        raise ValueError(
            f"claim-sort int32 key overflow: n={n}, tile={t}; use "
            "conflict_method='matrix' (or 'auto', which picks it)"
        )
    idx = jnp.arange(t, dtype=jnp.int32)
    verts = jnp.concatenate(
        [jnp.where(valid, u, n), jnp.where(valid, v, n)]
    ).astype(jnp.int32)
    eid2 = jnp.concatenate([idx, idx])
    last = 2 * t - 1
    # one sort per tile: slots in (vertex, edge) order
    skey = jnp.sort(verts * (t + 1) + eid2)
    sverts = skey // (t + 1)                     # sorted claimed vertex ids
    seid = (skey % (t + 1)).astype(jnp.int32)    # that slot's edge index
    # run starts: segment id of every sorted slot, and each endpoint's run
    segs = jnp.searchsorted(sverts, sverts)
    pu = jnp.minimum(jnp.searchsorted(sverts, u), last)
    pv = jnp.minimum(jnp.searchsorted(sverts, v), last)
    u_found = sverts[pu] == u
    v_found = sverts[pv] == v

    def blocked_fn(free):
        cand = jnp.where(free[seid], seid, t)    # free slots claim, others inert
        claim = jnp.full((2 * t,), t, jnp.int32).at[segs].min(cand)
        cu = jnp.where(u_found, claim[pu], t)    # min free claimant of u_i
        cv = jnp.where(v_found, claim[pv], t)
        return free & (jnp.minimum(cu, cv) < idx)

    return blocked_fn


def blocked_by_claim_scatter(
    u: jax.Array, v: jax.Array, valid: jax.Array, n: int
) -> Callable[[jax.Array], jax.Array]:
    """Same claimant function as :func:`blocked_by_claim_sort`, via a direct
    scatter-min into a vertex-indexed [n] claim array — no sort, no
    searchsorted. Each round costs one n-element init plus O(T) scatter/
    gather, so it wins when ``n`` is small relative to the tile (the
    window-local tier: ids < window); the sort version wins for
    full-graph-state tiles where the per-round init would dominate.

    Args and contract as :func:`blocked_by_claim_sort` (DESIGN.md §3
    "Blocked-predicate implementations").
    """
    t = u.shape[0]
    idx = jnp.arange(t, dtype=jnp.int32)
    ug = jnp.where(valid, u, 0)
    vg = jnp.where(valid, v, 0)

    def blocked_fn(free):
        cand = jnp.where(free, idx, t)           # only free edges claim
        claim = jnp.full((n,), t, jnp.int32)
        claim = claim.at[ug].min(cand)           # invalid rows write t: inert
        claim = claim.at[vg].min(cand)
        return free & (jnp.minimum(claim[ug], claim[vg]) < idx)

    return blocked_fn


def first_claim_commit(
    su: jax.Array,
    sv: jax.Array,
    valid: jax.Array,
    matched: jax.Array,
    blocked_fn: Callable[[jax.Array], jax.Array],
) -> Tuple[jax.Array, jax.Array]:
    """One first-claim round. ``su``/``sv`` are the gathered endpoint states;
    ``blocked_fn`` is one of the two blocked implementations above.

    Returns (commit, blocked): ``commit`` edges are mutually endpoint-disjoint
    by construction (the lowest-index free edge of any conflict chain is never
    blocked, so every round makes progress)."""
    free = valid & (~matched) & (su == ACC) & (sv == ACC)
    blocked = blocked_fn(free)
    commit = free & ~blocked
    return commit, blocked


# ---------------------------------------------------------------------------
# Capacitated generalization: first-K-claim rounds (DESIGN.md §9).
#
# The unit-capacity invariant above is the special case cap = 1 of a
# *capacitated* claim rule over two independent id spaces (u-side / v-side,
# e.g. MoE tokens / experts) with per-side budgets:
#
#     room_s(w)  = cap_s - used_s[w]                      (remaining slots)
#     free_i     = valid, undecided, room > 0 on BOTH sides
#     rank_s(i)  = #{ free j < i : side-s id of j == side-s id of i }
#     blocked_i  = rank_u(i) >= room_u(u_i)  or  rank_v(i) >= room_v(v_i)
#     commit_i   = free_i and not blocked_i
#
# rank counts ALL free earlier claimants — including ones that are
# themselves blocked on their other side — so claims cascade exactly as in
# the unit-capacity blocked predicate and the fixpoint of iterated rounds is
# the sequential index-order greedy (greedy_fallback_rounds' proof carries
# over verbatim). With cap_u = cap_v = 1 and disjoint id spaces,
# rank >= room degenerates to "some free j < i claims my endpoint" — the
# paper's reservation step — and the round is bit-identical to
# first_claim_commit (test-pinned, tests/test_bipartite.py).
#
# Like the unit predicate, rank has three interchangeable implementations
# (identical function, picked per side by cost): the triangular same-id
# matrix (O(T^2) VPU/MXU — the TPU-native form), the per-side claim sort
# (one sort per tile, O(T) per round), and the vertex-indexed one-hot prefix
# (O(T*n) per round — wins when the side's id space is tiny, e.g. experts).
# ---------------------------------------------------------------------------


def first_k_claim_commit(
    used_u: jax.Array,
    used_v: jax.Array,
    valid: jax.Array,
    matched: jax.Array,
    rank_fn: Callable[[jax.Array], Tuple[jax.Array, jax.Array]],
    cap_u: int,
    cap_v: int,
) -> Tuple[jax.Array, jax.Array]:
    """One capacitated first-claim round (DESIGN.md §9).

    Args:
        used_u, used_v: int32[T] *gathered per-edge* used counts —
            ``used_u_state[u]``, ``used_v_state[v]``.
        valid, matched: bool[T] as in :func:`first_claim_commit`.
        rank_fn: per-side free-claimant ranks, from
            :func:`capacitated_rank_fn` or one of the ``ranks_*`` builders.
        cap_u, cap_v: static per-side budgets (e.g. ``token_budget``,
            ``expert_capacity``).

    Returns:
        ``(commit, blocked)``. Committed edges never oversubscribe a vertex:
        within one round the commits on any vertex are exactly the free
        claimants with rank < room, so at most ``room`` many. An edge with a
        full endpoint is not free and simply stays unmatched (dead) — no
        explicit kill list is needed.
    """
    room_u = cap_u - used_u.astype(jnp.int32)  # state-dtype: ok (widen at gather)
    room_v = cap_v - used_v.astype(jnp.int32)  # state-dtype: ok (widen at gather)
    free = valid & (~matched) & (room_u > 0) & (room_v > 0)
    rank_u, rank_v = rank_fn(free)
    blocked = free & ((rank_u >= room_u) | (rank_v >= room_v))
    commit = free & ~blocked
    return commit, blocked


def _side_rank_matrix(ids: jax.Array, valid: jax.Array):
    """rank(free)[i] = #{free j < i with ids[j] == ids[i]} via the strictly
    lower-triangular same-id matrix — the per-side analogue of
    :func:`share_matrix` (O(T^2) VPU compares, 2-D iota so it traces inside
    Pallas TPU kernels unchanged)."""
    t = ids.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    mat = (
        (ids[:, None] == ids[None, :])
        & (cols < rows)
        & valid[None, :]
        & valid[:, None]
    )

    def rank(free):
        return jnp.sum((mat & free[None, :]).astype(jnp.int32), axis=1)

    return rank


def _side_rank_sort(ids: jax.Array, valid: jax.Array, n: int):
    """Same rank function via one per-tile sort — the per-side analogue of
    :func:`blocked_by_claim_sort`. Slots sorted once by (id, edge index);
    each round is then a gather + cumsum: rank = exclusive prefix of the
    free mask within the edge's id run. Same int32 composite-key bound."""
    t = ids.shape[0]
    if (n + 1) * (t + 1) >= 2**31:
        raise ValueError(
            f"claim-sort int32 key overflow: n={n}, tile={t}; use "
            "conflict_method='matrix' (or 'auto', which picks it)"
        )
    idx = jnp.arange(t, dtype=jnp.int32)
    masked = jnp.where(valid, ids, n).astype(jnp.int32)
    order = jnp.argsort(masked * (t + 1) + idx)   # unique keys: a total order
    sids = masked[order]
    starts = jnp.searchsorted(sids, sids)          # run start per sorted slot
    pos = jnp.zeros((t,), jnp.int32).at[order].set(idx)  # edge -> sorted slot

    def rank(free):
        fs = free[order].astype(jnp.int32)
        excl = jnp.cumsum(fs) - fs                 # exclusive prefix, global
        return (excl - excl[starts])[pos]          # minus the run's base

    return rank


def _side_rank_scatter(ids: jax.Array, valid: jax.Array, n: int):
    """Same rank function via a vertex-indexed [T, n] one-hot running prefix
    — the capacitated analogue of :func:`blocked_by_claim_scatter`'s dense
    [n] claim array (a min no longer suffices: room > 1 needs the claimant
    *count*). O(T*n) per round, so it wins only when the side's id space is
    tiny relative to the tile — exactly the MoE expert side, where the
    cumsum-of-one-hot is the MXU-friendly form."""
    t = ids.shape[0]
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (t, n), 1)
        == jnp.where(valid, ids, n)[:, None]
    )
    col = jnp.minimum(jnp.where(valid, ids, 0), n - 1).astype(jnp.int32)

    def rank(free):
        claims = (onehot & free[:, None]).astype(jnp.int32)
        pref = jnp.cumsum(claims, axis=0) - claims  # exclusive column prefix
        return jnp.take_along_axis(pref, col[:, None], axis=1)[:, 0]

    return rank


_SIDE_RANKS = {
    "matrix": lambda ids, valid, n: _side_rank_matrix(ids, valid),
    "sort": _side_rank_sort,
    "scatter": _side_rank_scatter,
}


def ranks_from_matrix(u: jax.Array, v: jax.Array, valid: jax.Array):
    """Capacitated twin of :func:`blocked_from_matrix`: per-side triangular
    same-id matrices. ``rank_fn(free) -> (rank_u, rank_v)``."""
    ru, rv = _side_rank_matrix(u, valid), _side_rank_matrix(v, valid)
    return lambda free: (ru(free), rv(free))


def ranks_by_claim_sort(
    u: jax.Array, v: jax.Array, valid: jax.Array, n_u: int, n_v: int
):
    """Capacitated twin of :func:`blocked_by_claim_sort`: one sort per side
    per tile, O(T) gathers + a cumsum per round."""
    ru = _side_rank_sort(u, valid, n_u)
    rv = _side_rank_sort(v, valid, n_v)
    return lambda free: (ru(free), rv(free))


def ranks_by_claim_scatter(
    u: jax.Array, v: jax.Array, valid: jax.Array, n_u: int, n_v: int
):
    """Capacitated twin of :func:`blocked_by_claim_scatter`: vertex-indexed
    one-hot prefix per side (use when both id spaces are small)."""
    ru = _side_rank_scatter(u, valid, n_u)
    rv = _side_rank_scatter(v, valid, n_v)
    return lambda free: (ru(free), rv(free))


def capacitated_rank_fn(
    u: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    n_u: int,
    n_v: int,
    method: str = "auto",
):
    """Build the per-side rank function for :func:`first_k_claim_commit`.

    ``method="auto"`` picks *per side* (the sides' id spaces differ wildly in
    the MoE case: thousands of tokens vs a handful of experts): the one-hot
    prefix when the space is tiny, claim-sort while its int32 key fits, the
    T^2 matrix beyond. All three compute the identical function, so the
    choice never changes output (test-pinned, like the unit-capacity trio).
    Explicit ``"matrix"`` / ``"sort"`` / ``"scatter"`` force one
    implementation on both sides."""
    t = u.shape[0]

    def pick(n):
        if n <= max(64, t // 8):
            return "scatter"
        if (n + 1) * (t + 1) < 2**31:
            return "sort"
        return "matrix"

    if method == "auto":
        mu, mv = pick(n_u), pick(n_v)
    elif method in _SIDE_RANKS:
        mu = mv = method
    else:
        raise ValueError(f"unknown conflict_method {method!r}")
    ru = _SIDE_RANKS[mu](u, valid, n_u)
    rv = _SIDE_RANKS[mv](v, valid, n_v)
    return lambda free: (ru(free), rv(free))


def run_first_claim_rounds(
    u: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    read_state: Callable[[], Tuple[jax.Array, jax.Array]],
    apply_commits: Callable[[jax.Array], None],
    vector_rounds: int,
    blocked_fn: Callable[[jax.Array], jax.Array] = None,
    capacities: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run the unrolled round loop over one tile (DESIGN.md §3 / §9).

    Args:
        u, v: int32[T] endpoint ids of the tile's edges (one shared vertex
            space in the unit-capacity case; two independent id spaces —
            e.g. tokens and experts — in the capacitated case).
        valid: bool[T] — padding / self-loop mask; invalid edges never
            commit, never block, never count.
        read_state: ``() -> (a, b)`` gathers the per-edge endpoint values —
            ``(state[u], state[v])`` for unit capacity, the per-edge *used
            counts* ``(used_u[u], used_v[v])`` when ``capacities`` is given.
            Closes over the caller's state container (a VMEM ref in the
            Pallas kernel, an array cell in jnp callers).
        apply_commits: ``commit -> None`` scatters this round's commits back
            into that container (MCHD to both endpoints / +1 to both used
            counters). Committed edges are mutually claim-disjoint within
            remaining room by construction, so the scatter is conflict-free.
        vector_rounds: number of unrolled rounds. Pure unroll tuning: the
            exact fallback (:func:`greedy_fallback_rounds`) reaches the same
            fixpoint from any unroll depth, so this never changes the output
            — only the conflicts counter and how much work stays out of the
            ``while_loop`` (test-pinned; see DESIGN.md §3 and, for why the
            capacitated default differs, §9).
        blocked_fn: unit capacity — one of the three ``blocked_*`` builders
            (defaults to share-matrix); capacitated — a *rank_fn* from
            :func:`capacitated_rank_fn` / the three ``ranks_*`` builders
            (required: there is no per-side default without the id-space
            sizes).
        capacities: ``None`` (unit capacity — the paper's reservation step)
            or ``(cap_u, cap_v)`` per-side budgets; see
            :func:`first_k_claim_commit`.

    Returns:
        ``(matched bool, conflicts int32)``, shaped like ``valid`` ([T], or
        the Pallas kernels' [T, 1] columns) — commits accumulated over
        the rounds and the per-edge blocked-round count (Table II
        instrumentation).

    Invariant (per round): every committed edge was free, and for each of
    its endpoints fewer free lower-index edges claimed that endpoint than it
    had remaining room. The lowest-index free edge always commits, so every
    round makes progress.
    """
    if capacities is None:
        if blocked_fn is None:
            blocked_fn = blocked_from_matrix(share_matrix(u, v, valid))

        def commit_round(a, b, matched):
            return first_claim_commit(a, b, valid, matched, blocked_fn)
    else:
        if blocked_fn is None:
            raise ValueError(
                "capacitated rounds need a rank_fn (capacitated_rank_fn)"
            )
        cap_u, cap_v = capacities

        def commit_round(a, b, matched):
            return first_k_claim_commit(
                a, b, valid, matched, blocked_fn, cap_u, cap_v
            )

    matched = jnp.zeros(valid.shape, jnp.bool_)
    conflicts = jnp.zeros(valid.shape, jnp.int32)
    for _ in range(vector_rounds):
        a, b = read_state()
        commit, blocked = commit_round(a, b, matched)
        apply_commits(commit)
        matched = matched | commit
        conflicts = conflicts + blocked.astype(jnp.int32)
    return matched, conflicts


def greedy_fallback_rounds(
    state,
    u: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    matched: jax.Array,
    blocked_fn: Callable[[jax.Array], jax.Array],
    *,
    gather,
    scatter,
    capacities: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact vectorized cleanup: iterate first-claim rounds until the tile has
    no free edge left. Returns (state, matched, fallback_taken).

    The fixpoint equals the sequential index-order greedy over the tile's
    remaining edges — the invariant the old scalar-scan fallback enforced.
    Sketch (induction on edge index): after each round every undecided valid
    edge is either free or dead-on-arrival next round (an endpoint out of
    room), so every undecided free edge reserves its claim against all
    higher-index edges; the lowest-index free edge is never blocked, so it
    commits the round it first appears free, and a higher-index edge commits
    only once enough smaller conflicting edges are decided that room remains
    for it — which is exactly the sequential scan's accounting. Every
    iteration commits at least one edge while any is free, so the loop
    terminates in at most T rounds — in practice the depth of the worst
    surviving conflict chain. This holds for unit capacity (room is 0/1,
    MCHD endpoints come only from committed edges) and verbatim for the
    capacitated rule of :func:`first_k_claim_commit` (DESIGN.md §9).

    ``state`` is whatever the caller's gather/scatter understand — the
    vertex-state array for unit capacity, the ``(used_u, used_v)`` counter
    pair (any pytree) when ``capacities=(cap_u, cap_v)`` is given.
    ``gather``/``scatter`` are *pure value* functions (state in, state out) so
    the state threads through the ``while_loop`` carry explicitly — closures
    that mutate a cell would leak tracers across the loop boundary. (The
    Pallas kernels pass ``state=()`` and close over their VMEM refs: a ref
    write is an effect, not a tracer, and Mosaic cannot carry packed uint8
    state through a loop.) The
    gathered per-edge values ride the carry too: one gather per iteration (in
    the kernel a gather is two [T, W] MXU matmuls — don't pay it twice).
    """
    if capacities is None:

        def free_mask(a, b, matched):
            return valid & (~matched) & (a == ACC) & (b == ACC)

        def commit_round(a, b, matched):
            return first_claim_commit(a, b, valid, matched, blocked_fn)
    else:
        cap_u, cap_v = capacities

        def free_mask(a, b, matched):
            return valid & (~matched) & (a < cap_u) & (b < cap_v)

        def commit_round(a, b, matched):
            return first_k_claim_commit(
                a, b, valid, matched, blocked_fn, cap_u, cap_v
            )

    def cond(carry):
        return carry[2]

    def body(carry):
        state, m, _, a, b = carry
        matched = m > 0
        commit, _blocked = commit_round(a, b, matched)
        state = scatter(state, commit)
        matched = matched | commit
        a, b = gather(state)
        go = jnp.any(free_mask(a, b, matched))
        m = matched.astype(jnp.int32)
        return state, m, go, a, b

    a, b = gather(state)
    taken = jnp.any(free_mask(a, b, matched))
    # matched rides the carry as int32: Mosaic cannot carry a bool vector
    # through a loop
    m = matched.astype(jnp.int32)
    state, m, _, _, _ = jax.lax.while_loop(cond, body, (state, m, taken, a, b))
    return state, m > 0, taken


def tile_pass(
    state: jax.Array,
    u: jax.Array,
    v: jax.Array,
    *,
    n: int,
    vector_rounds: int,
    fallback: bool = True,
    conflict_method: str = "auto",
    spec: Optional[StateSpec] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Process one edge tile (first-claim vector rounds + exact vectorized
    fallback, unless ``fallback=False``) against a full ``state`` array of
    ``n`` vertices. Shared by the single-device matcher, the distributed
    local pass / replay, and the device-resident pipeline's boundary
    epilogue (DESIGN.md §1, §3).

    Args:
        state: spec-width[n] vertex states (ACC/MCHD): the pass is
            width-polymorphic — the state keeps the caller's (spec's)
            dtype through gather/scatter, comparisons use plain ints.
        u, v: int32[T] endpoint ids; invalid edges are ``u < 0`` or
            ``u == v`` (pad convention of ``graphs/windows.py``).
        n: static vertex count (shape of ``state``).
        vector_rounds: unrolled rounds before the fallback; pure tuning —
            never changes the output (DESIGN.md §3, test-pinned).
        fallback: run :func:`greedy_fallback_rounds` to the exact greedy
            fixpoint (``False`` only for instrumentation).
        conflict_method: picks the blocked implementation — ``"auto"``
            (default: vertex-indexed claim scatter-min when the state is
            small relative to the tile, claim-sort while its int32 key
            fits, share matrix beyond), ``"scatter"``, ``"sort"``, or
            ``"matrix"`` (the compiled Pallas boundary kernel forces matrix
            because Mosaic has no sort/scatter). All compute the identical
            function, so the choice never changes output.
        spec: optional :class:`StateSpec`. When given, the per-edge
            ``conflicts`` output is narrowed to ``spec.counter`` (exact:
            conflicts <= vector_rounds, validated at trace time). When
            ``None`` conflicts stay in the i32 accumulator width — callers
            that sum conflicts (distributed stats, replay) rely on that.

    Returns:
        ``(state, matched, conflicts_per_edge, fallback_taken)``; every
        valid edge is decided — matched, or dead on an MCHD endpoint (the
        paper's single-pass invariant).

    The capacitated twin (per-side used counts + budgets) is
    :func:`tile_pass_capacitated` (DESIGN.md §9)."""
    valid = (u != v) & (u >= 0)
    t = u.shape[0]
    if conflict_method == "auto":
        if n <= 16 * t:          # per-round claim init is O(n)
            conflict_method = "scatter"
        elif (n + 1) * (t + 1) < 2**31:
            conflict_method = "sort"
        else:                    # beyond the sort key's int32 range
            conflict_method = "matrix"
    if conflict_method == "scatter":
        blocked_fn = blocked_by_claim_scatter(u, v, valid, n)
    elif conflict_method == "sort":
        blocked_fn = blocked_by_claim_sort(u, v, valid, n)
    elif conflict_method == "matrix":
        blocked_fn = blocked_from_matrix(share_matrix(u, v, valid))
    else:
        raise ValueError(f"unknown conflict_method {conflict_method!r}")

    def gather(st):
        return st[jnp.where(valid, u, 0)], st[jnp.where(valid, v, 0)]

    def scatter(st, commit):
        st = st.at[jnp.where(commit, u, n)].set(MCHD, mode="drop")
        return st.at[jnp.where(commit, v, n)].set(MCHD, mode="drop")

    cell = StateCell(state)

    def read_state():
        return gather(cell[...])

    def apply_commits(commit):
        cell[...] = scatter(cell[...], commit)

    matched, conflicts = run_first_claim_rounds(
        u, v, valid, read_state, apply_commits, vector_rounds, blocked_fn
    )
    state = cell[...]
    if spec is not None:
        spec.validate_rounds(vector_rounds)
        conflicts = conflicts.astype(spec.counter_dtype)

    if not fallback:
        return state, matched, conflicts, jnp.zeros((), jnp.bool_)

    state, matched, taken = greedy_fallback_rounds(
        state, u, v, valid, matched, blocked_fn, gather=gather, scatter=scatter
    )
    return state, matched, conflicts, taken


def stream_pass(
    state: jax.Array,
    u: jax.Array,
    v: jax.Array,
    *,
    n: int,
    vector_rounds: int,
    tile_size: int,
    conflict_method: str = "auto",
    spec: Optional[StateSpec] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Greedy first-claim pass over an [L]-sized edge slab in stream order,
    tiled by ``tile_size`` (``L % tile_size == 0``; -1 marks padding):
    a ``lax.scan`` of :func:`tile_pass` with the state as carry, i.e. the
    sequential single pass over the slab's edges at tile granularity.

    The one slab driver shared by the distributed matcher's LOCAL PASS /
    REPLAY steps (``core/distributed.py``) and the fault-recovery residual
    replay (``core/faults.py``) — the recovery path cannot drift from the
    protocol it recovers.

    Returns ``(state, matched bool[L], conflicts[L])`` — conflicts in the
    i32 accumulator width, or ``spec.counter`` when a spec is passed (see
    :func:`tile_pass`); the state keeps its input (spec) dtype.
    """
    l = u.shape[0]
    num_tiles = l // tile_size
    ut = u.reshape(num_tiles, tile_size)
    vt = v.reshape(num_tiles, tile_size)

    def step(st, uv):
        uu, vv = uv
        st, matched, conflicts, _ = tile_pass(
            st, uu, vv, n=n, vector_rounds=vector_rounds,
            conflict_method=conflict_method, spec=spec,
        )
        return st, (matched, conflicts)

    state, (matched, conflicts) = jax.lax.scan(step, state, (ut, vt))
    return state, matched.reshape(-1), conflicts.reshape(-1)


def tile_pass_pair(
    state_rows: jax.Array,
    u_loc: jax.Array,
    v_loc: jax.Array,
    blk_u: jax.Array,
    blk_v: jax.Array,
    *,
    window: int,
    vector_rounds: int,
    fallback: bool = True,
    conflict_method: str = "auto",
    spec: Optional[StateSpec] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Two-block variant of :func:`tile_pass` — the block-pair boundary
    epilogue's decision step (DESIGN.md §10).

    Processes one tile of T global-tier edges whose endpoints all live in
    (at most) two vertex-state blocks against ``state_rows`` of shape
    ``[num_windows, window]``: slice out rows ``blk_u`` and ``blk_v``, run
    the standard :func:`tile_pass` on their 2W-element concatenation, write
    the halves back. The endpoint ids are the schedule's *offset-local*
    encoding (``graphs/windows.py``): ``u_loc`` in ``[0, window)`` relative
    to block ``blk_u``; ``v_loc`` relative to block ``blk_v`` **plus
    ``window``** when ``blk_v != blk_u`` and un-offset when the pair is
    same-block — so within the concatenated pair, two slots alias the same
    global vertex iff their local ids are equal, and the pair tile is
    *literally* a ``tile_pass`` over a 2W-vertex state. That is what makes
    the Pallas pair kernel and this jnp form bit-identical by construction:
    both run the identical first-claim rounds + exact fallback on the
    identical local-id tile; only the block load/store differs (DMA +
    one-hot matmuls there, dynamic row slicing here).

    Write-back order is v-half first, u-half second: for a same-block pair
    (``blk_u == blk_v``) every local id is < ``window``, the v-half of the
    concatenation is never read nor written, and the u-half update must win
    the row — with distinct blocks the two updates touch disjoint rows and
    the order is irrelevant.

    Args:
        state_rows: spec-width[num_windows, window] blocked vertex states
            (the pass keeps the caller's dtype).
        u_loc, v_loc: int32[T] offset-local endpoint ids (-1 padding).
        blk_u, blk_v: scalar int32 state-block (window) ids of the pair.
        window / vector_rounds / fallback / conflict_method / spec: as in
            :func:`tile_pass` (``n`` is implied: 2 * window).

    Returns:
        ``(state_rows, matched, conflicts_per_edge, fallback_taken)``.
    """
    row_u = jax.lax.dynamic_index_in_dim(state_rows, blk_u, 0, keepdims=False)
    row_v = jax.lax.dynamic_index_in_dim(state_rows, blk_v, 0, keepdims=False)
    pair = jnp.concatenate([row_u, row_v])
    pair, matched, conflicts, taken = tile_pass(
        pair, u_loc, v_loc, n=2 * window, vector_rounds=vector_rounds,
        fallback=fallback, conflict_method=conflict_method, spec=spec,
    )
    state_rows = jax.lax.dynamic_update_index_in_dim(
        state_rows, pair[window:], blk_v, 0
    )
    state_rows = jax.lax.dynamic_update_index_in_dim(
        state_rows, pair[:window], blk_u, 0
    )
    return state_rows, matched, conflicts, taken


def tile_pass_capacitated(
    used_u: jax.Array,
    used_v: jax.Array,
    u: jax.Array,
    v: jax.Array,
    *,
    cap_u: int,
    cap_v: int,
    vector_rounds: int,
    fallback: bool = True,
    conflict_method: str = "auto",
    spec: Optional[StateSpec] = None,
) -> Tuple[Tuple[jax.Array, jax.Array], jax.Array, jax.Array, jax.Array]:
    """Capacitated twin of :func:`tile_pass` (DESIGN.md §9): process one edge
    tile against per-side used-count states with per-side budgets.

    Args:
        used_u: [n_u] used counts of the u side (e.g. per-token) — the
            used counts are this problem's vertex state, so callers may
            allocate them at the spec's at-rest width when the static
            budgets fit (``StateSpec.validate_capacity``); the rank/room
            comparisons widen to i32 at the gather like everywhere else.
        used_v: [n_v] used counts of the v side (e.g. per-expert).
        u, v: int32[T] per-edge side ids; ``-1`` marks padding (validity is
            ``(u >= 0) & (v >= 0)`` — no ``u != v`` check: the sides are
            independent id spaces, unlike the unipartite :func:`tile_pass`).
        cap_u, cap_v: static per-side budgets.
        vector_rounds / fallback / conflict_method: as in :func:`tile_pass`;
            ``conflict_method`` picks per side when ``"auto"``
            (:func:`capacitated_rank_fn`).

    Returns:
        ``((used_u, used_v), matched, conflicts_per_edge, fallback_taken)``.
        The fixpoint (rounds + fallback) is exactly the sequential
        index-order greedy b-matching over the tile's edges, so scanning
        tiles with the used counts as carry yields the sequential greedy
        over the whole stream (test-pinned against a numpy oracle).
    """
    valid = (u >= 0) & (v >= 0)
    n_u, n_v = used_u.shape[0], used_v.shape[0]
    rank_fn = capacitated_rank_fn(u, v, valid, n_u, n_v, conflict_method)
    ug = jnp.where(valid, u, 0)
    vg = jnp.where(valid, v, 0)

    def gather(st):
        return st[0][ug], st[1][vg]

    def scatter(st, commit):
        uu = st[0].at[jnp.where(commit, u, n_u)].add(1, mode="drop")
        uv = st[1].at[jnp.where(commit, v, n_v)].add(1, mode="drop")
        return uu, uv

    cell = StateCell((used_u, used_v))

    def read_state():
        return gather(cell[...])

    def apply_commits(commit):
        cell[...] = scatter(cell[...], commit)

    matched, conflicts = run_first_claim_rounds(
        u, v, valid, read_state, apply_commits, vector_rounds,
        rank_fn, capacities=(cap_u, cap_v),
    )
    state = cell[...]
    if spec is not None:
        spec.validate_rounds(vector_rounds)
        conflicts = conflicts.astype(spec.counter_dtype)

    if not fallback:
        return state, matched, conflicts, jnp.zeros((), jnp.bool_)

    state, matched, taken = greedy_fallback_rounds(
        state, u, v, valid, matched, rank_fn,
        gather=gather, scatter=scatter, capacities=(cap_u, cap_v),
    )
    return state, matched, conflicts, taken


def window_tier_pass(
    u_rows: jax.Array,   # int32[num_rows, tiles_per_window * tile_size]
    v_rows: jax.Array,   # window-LOCAL ids, -1 padding
    *,
    window: int,
    tiles_per_window: int,
    tile_size: int,
    vector_rounds: int,
    backend: str,
    interpret: bool = True,
    spec: Optional[StateSpec] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run the window tier of a two-tier schedule: each row is one window's
    dispersed tile stream, matched from an all-ACC window-local state
    (DESIGN.md §3; the distributed consumer is §8 step 1).

    This is the single entry point the device-resident pipeline
    (``kernels/skipper_match/ops.skipper_match``) and the distributed
    matcher's per-device LOCAL PASS share — the two matchers cannot drift.
    ``backend="pallas"`` launches the 2-D-grid revolving-VMEM kernel
    (``build_pipeline_matcher``); ``backend="xla"`` runs the bit-identical
    jnp twin (``ref.make_ref_pipeline`` — a flat scan in the exact grid
    order). Imports are deferred: the kernel modules themselves import this
    module.

    Args:
        u_rows, v_rows: int32[num_rows, tiles_per_window * tile_size]
            window-LOCAL endpoint ids, -1 padding (rows are the dense tier
            of ``graphs/windows.build_window_schedule``).
        window / tiles_per_window / tile_size: the schedule's static shape.
        vector_rounds: forwarded to the per-tile rounds (pure tuning).
        backend: ``"pallas"`` or ``"xla"``.
        interpret: Pallas interpreter flag (ignored by the xla twin).
        spec: optional :class:`StateSpec` (None -> the default). Both
            backends allocate state in ``spec.vmem`` and emit
            matched/conflicts in ``spec.counter``, so the two compiled
            graphs stay dtype-identical, not just value-identical.

    Returns:
        ``(states, matched, conflicts, fallback_tiles)`` with ``states`` of
        shape ``spec.vmem[num_rows, window]``, ``matched``/``conflicts``
        ``spec.counter`` of ``u_rows``'s shape, and the int32 count of
        tiles that took the exact fallback (values identical across
        backends and specs, test-pinned).

    Invariant: each row's result depends only on that row's tiles (windows
    are disjoint vertex ranges), which is what lets the distributed matcher
    deal rows to devices with zero communication.
    """
    spec = resolve_spec(spec)
    num_rows = u_rows.shape[0]
    if backend == "pallas":
        from repro.kernels.skipper_match.kernel import build_pipeline_matcher

        call = build_pipeline_matcher(
            num_rows, tiles_per_window, tile_size, window,
            vector_rounds, True, interpret, spec,
        )
        state0 = jnp.zeros((num_rows, window), spec.vmem_dtype)
        states, matched, conflicts, taken = call(u_rows, v_rows, state0)
    elif backend == "xla":
        from repro.kernels.skipper_match.ref import make_ref_pipeline

        run = make_ref_pipeline(window, vector_rounds, spec=spec)
        states, matched, conflicts, taken = run(
            u_rows.reshape(num_rows, tiles_per_window, tile_size),
            v_rows.reshape(num_rows, tiles_per_window, tile_size),
        )
        matched = matched.reshape(u_rows.shape)
        conflicts = conflicts.reshape(u_rows.shape)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return states, matched, conflicts, taken
