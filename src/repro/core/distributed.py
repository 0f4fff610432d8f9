"""Multi-device Skipper via shard_map — devices play the paper's threads.

Two schedules share one protocol core (``_make_round_fn``):

**Dispersed path** (``reorder="none"``, the paper's §IV-C deal): every edge
block goes through the four-step round below, exactly like a paper thread
scanning its blocks.

**Locality-sharded path** (``reorder=``/``window=``): the edge stream is
renumbered (`graphs/reorder.py`), bucketed into a two-tier
``WindowSchedule`` and partitioned by `graphs/partition.partition_schedule`.
Windows are disjoint vertex-id ranges, so each device resolves its dealt
windows ENTIRELY locally through the device-resident pipeline
(``engine.window_tier_pass`` — the same Pallas kernel / jnp twin
``skipper_match`` runs), with zero proposals and zero replay; ONE O(V)
collective over the per-window states (no topology) then rebuilds the
committed full state everywhere — a width-honest combine in the active
``StateSpec``'s wire dtype (rows are device-disjoint, so ``pmax`` is exact
at any width; the legacy i32 spec keeps the historical ``psum``) — and only
the global tier (cross-window + coalesced sparse-window edges — the
minority after reordering) runs the four-step protocol. Masks come back in original stream order and states in original
vertex ids through the schedule's ``stream_src``/``perm`` round-trip.

Protocol per round (DESIGN.md §2 level 1; paper Alg. 1 adapted to SPMD):

  1. LOCAL PASS — each device greedily matches its next dispersed edge block
     (plus its retry buffer) against its replica of the vertex-state array,
     exactly like a paper thread scanning its blocks. Local commits are
     *proposals* — the analogue of holding RSVD on both endpoints.
  2. GATHER — one all_gather moves the per-device proposal blocks (tiny:
     O(block) ints, no topology) to every device.
  3. REPLAY — every device applies the gathered proposals in the same
     deterministic position-major order with the same first-claim tile pass.
     Winners become MCHD everywhere (the committed state stays replicated-
     consistent); a proposal loses only if an endpoint was taken by an
     earlier-priority winner — i.e. the edge is *dead by MCHD endpoint*,
     Skipper's invariant.
  4. REQUEUE — edges the local pass killed via a *provisional* claim whose
     claimant then lost, and are still free post-replay, enter the retry
     buffer for the next round (the analogue of spinning on RSVD). Θ(λ²)-rare.

Each edge is decided exactly once except the rare requeues: total expected
work O(|E|/D + conflicts) per device, O(|E| + conflicts) aggregate — the
paper's single-pass property at block granularity.

Cross-pod: the all_gather composes over ("pod", "data") axes; proposal bytes
per round are independent of |E| (the paper's "conflict resolution touches no
topology").

Output is deterministic given the schedule — (D, block_size) on the
dispersed path, (window, tile_size, reorder, D, block_size) on the
locality-sharded one; at D=1 the latter is bit-identical to
``skipper_match`` on the same schedule (test-pinned). See DESIGN.md §8.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.core.types import ACC, MCHD, Counters, MatchResult
from repro.core.engine import stream_pass, window_tier_pass
from repro.core.statespec import DEFAULT, StateSpec, resolve as resolve_spec
from repro.core.faults import (
    CORRUPT,
    FaultPlan,
    corruption_mask,
    detect_residual,
    proposal_drop_mask,
    residual_replay,
)
from repro.core.validate import check_matching
from repro.graphs.types import EdgeList
from repro.graphs.partition import (
    DeviceSchedule,
    dispersed_blocks,
    locality_device_schedule,
    partition_schedule,
)
from repro.graphs.windows import WindowSchedule

# bounded in-protocol escalation: at most this many re-runs with regrown
# knobs before the ladder drops to the residual replay (DESIGN.md §11)
_MAX_ESCALATIONS = 2


@dataclasses.dataclass(frozen=True)
class DistStats:
    """Per-run distributed accounting (aggregated over devices).

    The last four fields are the degradation ledger (DESIGN.md §11) —
    always zero on a healthy ``on_fault="raise"`` run; filled by
    ``on_fault="report"`` (detection only), ``on_fault="recover"`` (what the
    ladder did), and ``verify=True``.
    """

    proposals: jax.Array        # total proposals sent
    lost_proposals: jax.Array   # proposals that lost replay (cross-device JIT conflicts)
    requeued: jax.Array         # edges requeued (spin-wait analogue)
    retry_overflow: jax.Array   # edges dropped by a full retry buffer (must be 0)
    undrained: jax.Array        # retry entries alive after drain rounds (must be 0)
    gathered_bytes: jax.Array   # collective payload BYTES over the run:
    #   int32 proposal-index gathers + the O(V) state assembly in the
    #   active StateSpec's wire width (was `gathered_ints`, an i32 count)
    recovery_attempts: jax.Array | int = 0  # ladder steps that did real work
    residual_edges: jax.Array | int = 0     # valid edges left undecided
    recovered_matches: jax.Array | int = 0  # matches added by the replay
    corrupted_cells: jax.Array | int = 0    # out-of-domain state bytes seen

    @property
    def gathered_ints(self):
        """Deprecated alias (one release): the old i32-word count. The
        payload is no longer all-i32 — prefer :attr:`gathered_bytes`."""
        import warnings

        warnings.warn(
            "DistStats.gathered_ints is deprecated; use gathered_bytes "
            "(the wire payload is no longer uniformly int32)",
            DeprecationWarning, stacklevel=2,
        )
        return self.gathered_bytes // 4

    @property
    def ok(self) -> bool:
        """True iff the must-be-zero invariants actually held: no retry
        overflow (a dropped edge can silently break maximality) and nothing
        left undrained. ``distributed_skipper(on_fault="raise")`` raises on
        the spot; callers running ``on_fault="report"`` must test this flag.

        NOTE: reading the flag synchronizes — it blocks on the device
        computation via one ``jax.device_get`` of both counters (one
        transfer, not one blocking ``int()`` per field)."""
        ovf, und = jax.device_get(  # host-sync: ok (the ONE fetch)
            (self.retry_overflow, self.undrained)
        )
        return int(ovf) == 0 and int(und) == 0

    def raise_if_bad(self) -> None:
        """Raise ``RuntimeError`` if a must-be-zero invariant tripped.
        Synchronizes, like :attr:`ok` (single ``device_get``)."""
        ovf, und = jax.device_get(  # host-sync: ok (the ONE fetch)
            (self.retry_overflow, self.undrained)
        )
        if int(ovf) != 0 or int(und) != 0:
            raise RuntimeError(
                "distributed matching violated its must-be-zero invariants: "
                f"retry_overflow={int(ovf)} (edges dropped by "
                f"a full retry buffer), undrained={int(und)} "
                "(retry entries alive after the drain rounds) — the matching "
                "may be non-maximal. Increase block_size and/or drain_rounds, "
                "or run on_fault='recover' to complete the matching."
            )


def _make_round_fn(
    *,
    n: int,
    mask_len: int,
    axis_name: str,
    num_devices: int,
    vector_rounds: int,
    tile_size: int,
    block: int,
    edge_lookup=None,
    faults: Optional[FaultPlan] = None,
):
    """Build the four-step round body shared by both distributed schedules.

    The carry is ``(state, mask, ru, rv, ri, stats)`` where ``mask`` is a
    bool[mask_len] of replay winners indexed by the per-edge stream index
    carried in ``ri``/the block index arrays, and ``stats`` is the 9-tuple
    ``(props, req, ovf, gbytes, reads, loads_local, loads_replay,
    stores_replay, winners)`` (``gbytes`` counts wire BYTES — proposal
    slots are int32 stream indices/endpoints, 4 B each). Stats marked *local* count only this device's
    REAL edge work — padded sentinel slots (-1) scanned during padding and
    drain rounds contribute nothing — and get psum'd at the end; the replay
    terms are identical on every device (the replay is replicated) and are
    counted once.

    ``edge_lookup``: optional ``(lu, lv)`` replicated int32 arrays mapping a
    stream index to its endpoints. When the dealt stream is STATIC schedule
    data replicated on every device (the locality-sharded global tier: the
    block-pair grouped ``WindowSchedule.boundary_u``/``boundary_v``), a
    proposal is fully identified by its stream index alone — the GATHER
    moves one int per slot instead of three (u, v, idx) and receivers
    reconstruct the endpoints locally. The dispersed path keeps the 3-int
    proposals (its raw stream is sharded, not replicated).

    ``faults``: optional :class:`FaultPlan`, trace-time gated — ``None``
    (the default) adds zero ops. ``drop_proposals`` drops gather slots the
    local pass believes it sent (the silent-loss failure mode: the edge is
    neither replayed nor requeued); ``lose_shard`` swallows one device's
    proposals wholesale; ``truncate_retry`` shrinks the retry buffer's
    effective capacity so requeues overflow.
    """
    cap = block  # retry buffer capacity
    cap_eff = cap
    if faults is not None and faults.truncate_retry is not None:
        cap_eff = min(cap, faults.truncate_retry)
    slab = block + cap
    slab_pad = (-slab) % tile_size
    slab_t = slab + slab_pad
    dmask = None
    if faults is not None and faults.drop_proposals > 0.0:
        dmask = proposal_drop_mask(faults, mask_len)

    def one_round(carry, blk):
        state, mask, ru, rv, ri, stats = carry
        bu, bv, bi = blk

        # 1. LOCAL PASS on [retry ++ block]
        u = jnp.concatenate([ru, bu, jnp.full((slab_pad,), -1, jnp.int32)])
        v = jnp.concatenate([rv, bv, jnp.full((slab_pad,), -1, jnp.int32)])
        idx = jnp.concatenate([ri, bi, jnp.full((slab_pad,), -1, jnp.int32)])
        local_state, proposed, local_conf = stream_pass(
            state, u, v, n=n, vector_rounds=vector_rounds, tile_size=tile_size
        )
        valid = (u >= 0) & (u != v)
        # dead w.r.t. the committed (pre-round) state — permanent
        sgu = state[jnp.clip(u, 0, n - 1)]
        sgv = state[jnp.clip(v, 0, n - 1)]
        dead_global = valid & (~proposed) & ((sgu == MCHD) | (sgv == MCHD))
        dead_prov = valid & (~proposed) & (~dead_global)

        # 2. GATHER proposals; position-major (round-robin across devices)
        # deterministic order. With a replicated stream lookup, a proposal
        # is just its stream index (1 int); otherwise (u, v, idx).
        sent = proposed
        if dmask is not None:
            # FAULT: drop the slot on the wire — this device still believes
            # it proposed (dead_prov stays False), so the edge is lost
            sent = sent & ~dmask[jnp.clip(idx, 0, mask_len - 1)]
        if faults is not None and faults.lose_shard is not None:
            lost = jax.lax.axis_index(axis_name) == (
                faults.lose_shard % num_devices
            )
            sent = sent & ~lost
        pi = jnp.where(sent, idx, -1)
        gi = jax.lax.all_gather(pi, axis_name).T.reshape(-1)  # [D * slab_t]
        if edge_lookup is not None:
            lu, lv = edge_lookup
            live = gi >= 0
            gj = jnp.clip(gi, 0, lu.shape[0] - 1)
            gu = jnp.where(live, lu[gj], -1)
            gv = jnp.where(live, lv[gj], -1)
            round_gbytes = 4 * slab_t * num_devices  # 1 i32 index per slot
        else:
            pu = jnp.where(sent, u, -1)
            pv = jnp.where(sent, v, -1)
            gu = jax.lax.all_gather(pu, axis_name).T.reshape(-1)
            gv = jax.lax.all_gather(pv, axis_name).T.reshape(-1)
            round_gbytes = 3 * 4 * slab_t * num_devices  # (u, v, idx) i32s

        # 3. REPLAY on the committed state (deterministic first-claim order)
        new_state, winners, _ = stream_pass(
            state, gu, gv, n=n, vector_rounds=vector_rounds, tile_size=tile_size
        )
        mask = mask.at[jnp.where(winners, gi, mask_len)].set(True, mode="drop")

        # 4. REQUEUE provisional-dead edges that are still free post-replay
        snu = new_state[jnp.clip(u, 0, n - 1)]
        snv = new_state[jnp.clip(v, 0, n - 1)]
        requeue = dead_prov & (snu == ACC) & (snv == ACC)
        # compact requeued edges to the front of the retry buffer
        order = jnp.argsort(~requeue)  # True (=0 after ~) first
        ru_n = jnp.where(requeue[order], u[order], -1)[:cap]
        rv_n = jnp.where(requeue[order], v[order], -1)[:cap]
        ri_n = jnp.where(requeue[order], idx[order], -1)[:cap]
        if cap_eff < cap:
            # FAULT: truncated retry buffer — entries past the effective
            # capacity are dropped on the floor and counted as overflow
            keep = jnp.arange(cap, dtype=jnp.int32) < cap_eff
            ru_n = jnp.where(keep, ru_n, -1)
            rv_n = jnp.where(keep, rv_n, -1)
            ri_n = jnp.where(keep, ri_n, -1)
        nreq = jnp.sum(requeue)
        overflow = jnp.maximum(nreq - cap_eff, 0)

        # real-work accounting: only valid slots count (padding/sentinel
        # slots scanned during padded slabs and drain rounds are free);
        # requeued edges count again on re-scan, like the single-device
        # matcher's blocked-edge re-reads.
        nvalid = jnp.sum(valid).astype(jnp.int32)
        nconf = jnp.sum(jnp.where(valid, local_conf, 0)).astype(jnp.int32)
        n_props = jnp.sum(proposed).astype(jnp.int32)
        nwin = jnp.sum(winners).astype(jnp.int32)
        # all devices' proposals, read once each by the (replicated) replay
        n_replayed = jnp.sum((gu >= 0) & (gu != gv)).astype(jnp.int32)

        props, req, ovf, gbytes, reads, l_loc, l_rep, s_rep, wins = stats
        stats = (
            props + n_props,
            req + nreq,
            ovf + overflow,
            gbytes + round_gbytes,
            reads + nvalid,
            l_loc + 2 * nvalid + 2 * nconf,
            l_rep + 2 * n_replayed,
            s_rep + 2 * nwin,
            wins + nwin,
        )
        return (new_state, mask, ru_n, rv_n, ri_n, stats), nwin

    return one_round, slab_t


def _zero_stats():
    z = jnp.zeros((), jnp.int32)
    return (z,) * 9


def _drain_blocks(drain_rounds: int, block: int):
    e = jnp.full((drain_rounds, block), -1, jnp.int32)
    return (e, e, e)


def _aggregate_stats(stats, ru, axis_name):
    """Post-drain stats aggregation: psum the per-device entries, count
    undrained retries, pass replicated entries through."""
    props, req, ovf, gbytes, reads, l_loc, l_rep, s_rep, wins = stats
    und = jnp.sum(ru >= 0)
    agg = lambda x: jax.lax.psum(x, axis_name)
    return (
        agg(props),
        agg(req),
        agg(ovf),
        agg(und),
        gbytes,           # identical on every device already
        agg(reads),
        agg(l_loc),
        l_rep,            # replay is replicated: count once
        s_rep,
        wins,
    )


def dispersed_skipper_fn(
    u_blocks: jax.Array,   # [1, R, B] this device's dispersed blocks
    v_blocks: jax.Array,
    i_blocks: jax.Array,   # [1, R, B] global stream indices
    *,
    num_vertices: int,
    num_edges_padded: int,
    axis_name: str,
    num_devices: int,
    vector_rounds: int,
    tile_size: int,
    drain_rounds: int,
    faults: Optional[FaultPlan] = None,
    spec: StateSpec = DEFAULT,
) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, ...]]:
    """Per-device body of the dispersed (raw stream block) schedule. The
    replicated state array lives at ``spec.at_rest`` width (1 B/vertex by
    default — there is no VMEM/wire split on this path: proposals, not
    state, go over the wire)."""
    n = num_vertices
    # shard_map delivers the device-sharded leading axis as size 1: squeeze.
    u_blocks = u_blocks.reshape(u_blocks.shape[-2:])
    v_blocks = v_blocks.reshape(v_blocks.shape[-2:])
    i_blocks = i_blocks.reshape(i_blocks.shape[-2:])
    _, block = u_blocks.shape

    one_round, _ = _make_round_fn(
        n=n,
        mask_len=num_edges_padded,
        axis_name=axis_name,
        num_devices=num_devices,
        vector_rounds=vector_rounds,
        tile_size=tile_size,
        block=block,
        faults=faults,
    )

    state_dt = spec.at_rest_dtype
    state0 = jnp.full((n,), ACC, state_dt)
    if faults is not None and faults.corrupt_state > 0.0:
        # FAULT: out-of-domain bytes in the committed state — the affected
        # vertices look permanently non-free (neither ACC nor MCHD), so
        # every edge on them dies without being decided
        state0 = jnp.where(
            corruption_mask(faults, n), jnp.asarray(CORRUPT, state_dt), state0
        )
    mask0 = jnp.zeros((num_edges_padded,), jnp.bool_)
    empty = jnp.full((block,), -1, jnp.int32)
    carry0 = (state0, mask0, empty, empty, empty, _zero_stats())

    carry, _ = jax.lax.scan(one_round, carry0, (u_blocks, v_blocks, i_blocks))
    # drain: extra rounds with empty blocks until retry buffers settle
    carry, _ = jax.lax.scan(one_round, carry, _drain_blocks(drain_rounds, block))

    state, mask, ru, _, _, stats = carry
    return state, mask, _aggregate_stats(stats, ru, axis_name)


def locality_sharded_fn(
    u_rows: jax.Array,     # [1, rows_per_device, slots] window-local ids
    v_rows: jax.Array,
    row_slot: jax.Array,   # [1, rows_per_device] schedule-row index, -1 pad
    bu_blocks: jax.Array,  # [1, R, B] global-tier deal (renumbered GLOBAL ids)
    bv_blocks: jax.Array,
    bi_blocks: jax.Array,  # [1, R, B] boundary stream positions
    window_ids: jax.Array,  # int32[num_rows] row -> window id (replicated)
    boundary_lu: jax.Array,  # int32[nb_pad] stream-position -> u (replicated)
    boundary_lv: jax.Array,  #   ... -> v: the idx-only proposal lookup
    *,
    window: int,
    tiles_per_window: int,
    tile_size: int,
    num_rows: int,
    num_windows: int,
    num_boundary_padded: int,
    axis_name: str,
    num_devices: int,
    vector_rounds: int,
    drain_rounds: int,
    backend: str,
    interpret: bool,
    faults: Optional[FaultPlan] = None,
    spec: StateSpec = DEFAULT,
):
    """Per-device body of the locality-sharded schedule.

    PHASE A (window tier, zero communication): this device's dealt window
    rows run through the device-resident pipeline — the identical
    ``engine.window_tier_pass`` entry point ``skipper_match`` uses, so each
    window's result is bit-identical to the single-device pipeline no matter
    which device it was dealt to. One ``spec.combine_rows`` collective over
    the per-row states (disjoint row slots; O(num_rows * window) *
    ``spec.wire_bytes`` bytes, no topology) rebuilds the committed full
    state on every device — max-combine is exact because each row has at
    most one non-zero contributor, and ``lose_shard`` zeroing composes
    (zeros lose to real values).

    PHASE B (global tier): the boundary blocks run the four-step
    propose/gather/replay protocol against that committed state — same
    rounds, seeded with the window-tier commits instead of all-ACC. The
    dealt stream is the replicated block-pair grouped schedule data, so
    proposals gather as bare stream indices (``edge_lookup``): 1 gathered
    int per slot instead of 3.

    Returns (flat committed state [replicated], this device's window-tier
    matched slab [sharded], boundary winners mask [replicated], stats).
    """
    u_rows = u_rows.reshape(u_rows.shape[-2:])
    v_rows = v_rows.reshape(v_rows.shape[-2:])
    row_slot = row_slot.reshape(row_slot.shape[-1:])
    bu_blocks = bu_blocks.reshape(bu_blocks.shape[-2:])
    bv_blocks = bv_blocks.reshape(bv_blocks.shape[-2:])
    bi_blocks = bi_blocks.reshape(bi_blocks.shape[-2:])
    n_flat = num_windows * window

    # ---- PHASE A: device-resident window tier (no collectives) ----------
    states, matched_w, conf_w, _ = window_tier_pass(
        u_rows, v_rows,
        window=window,
        tiles_per_window=tiles_per_window,
        tile_size=tile_size,
        vector_rounds=vector_rounds,
        backend=backend,
        interpret=interpret,
        spec=spec,
    )
    w_valid = u_rows >= 0
    if faults is not None and faults.lose_shard is not None:
        # FAULT: lost shard — this device's whole window-tier contribution
        # (state rows AND matched bits, kept consistent) vanishes before the
        # psum; its global-tier proposals are swallowed in _make_round_fn
        lost = jax.lax.axis_index(axis_name) == (
            faults.lose_shard % num_devices
        )
        states = jnp.where(lost, jnp.zeros_like(states), states)
        matched_w = jnp.where(lost, jnp.zeros_like(matched_w), matched_w)
    # assemble the committed full state: scatter this device's rows into
    # schedule-row order (disjoint across devices), combine at the spec's
    # wire width, then place rows at their window ids (two-tier compaction;
    # coalesced windows stay all-ACC — their edges are global-tier).
    wire_dt = spec.wire_dtype
    slot = jnp.where(row_slot >= 0, row_slot, num_rows)
    rows_state = (
        jnp.zeros((num_rows, window), wire_dt)
        .at[slot].set(states.astype(wire_dt), mode="drop")
    )
    rows_state = spec.combine_rows(rows_state, axis_name)
    flat = (
        jnp.zeros((num_windows, window), wire_dt)
        .at[window_ids].set(rows_state)
        .reshape(n_flat)
        .astype(spec.at_rest_dtype)
    )
    if faults is not None and faults.corrupt_state > 0.0:
        # FAULT: corrupt the assembled committed state (renumbered-flat id
        # space) before the global tier reads it — identical injection site
        # to the single-device pipeline's
        flat = jnp.where(
            corruption_mask(faults, n_flat),
            jnp.asarray(CORRUPT, spec.at_rest_dtype),
            flat,
        )

    # ---- PHASE B: global tier via propose/gather/replay -----------------
    num_rounds, block = bu_blocks.shape
    nvalid_w = jnp.sum(w_valid).astype(jnp.int32)
    # counters may be spec-narrowed (uint8): widen BEFORE summing so a
    # window tier with >255 conflicts/matches can't wrap the stats
    nconf_w = jnp.sum(
        jnp.where(w_valid, conf_w.astype(jnp.int32), 0)
    ).astype(jnp.int32)
    # stores of the window tier happen per device; the stores slot of the
    # stats tuple is a count-once (replicated) entry, so pre-psum here.
    nmatch_w = jax.lax.psum(
        jnp.sum(
            jnp.where(w_valid, matched_w.astype(jnp.int32), 0)
        ).astype(jnp.int32),
        axis_name,
    )
    z = jnp.zeros((), jnp.int32)
    state_wire_bytes = jnp.asarray(
        num_devices * num_rows * window * spec.wire_bytes, jnp.int32
    )  # the PHASE A combine payload — O(V) at wire width, no topology
    stats0 = (z, z, z, state_wire_bytes, nvalid_w,
              2 * nvalid_w + 2 * nconf_w, z, 2 * nmatch_w, z)

    if num_rounds > 0:
        one_round, _ = _make_round_fn(
            n=n_flat,
            mask_len=num_boundary_padded,
            axis_name=axis_name,
            num_devices=num_devices,
            vector_rounds=vector_rounds,
            tile_size=tile_size,
            block=block,
            edge_lookup=(boundary_lu, boundary_lv),
            faults=faults,
        )
        mask0 = jnp.zeros((num_boundary_padded,), jnp.bool_)
        empty = jnp.full((block,), -1, jnp.int32)
        carry0 = (flat, mask0, empty, empty, empty, stats0)
        carry, _ = jax.lax.scan(
            one_round, carry0, (bu_blocks, bv_blocks, bi_blocks)
        )
        carry, _ = jax.lax.scan(
            one_round, carry, _drain_blocks(drain_rounds, block)
        )
        flat, bmask, ru, _, _, stats = carry
    else:
        bmask = jnp.zeros((num_boundary_padded,), jnp.bool_)
        ru = jnp.full((1,), -1, jnp.int32)
        stats = stats0

    stats_out = _aggregate_stats(stats, ru, axis_name)
    matched_out = jnp.where(w_valid, matched_w.astype(jnp.int32), 0)
    return (
        flat,
        matched_out.reshape((1,) + matched_out.shape),
        bmask,
        stats_out,
    )


@lru_cache(maxsize=32)
def _compiled_dispersed(
    mesh, axis_name, num_devices, num_vertices, num_edges_padded,
    vector_rounds, tile_size, drain_rounds, faults=None, spec=DEFAULT,
):
    """One compiled shard_map per static config — rebuilding shard_map+jit
    per call would retrace/recompile every time (~100x the actual run time
    on the bench graphs). Mesh is hashable and participates in the key, as
    do the (frozen, default-None) fault plan and the (frozen) state spec."""
    fn = partial(
        dispersed_skipper_fn,
        num_vertices=num_vertices,
        num_edges_padded=num_edges_padded,
        axis_name=axis_name,
        num_devices=num_devices,
        vector_rounds=vector_rounds,
        tile_size=tile_size,
        drain_rounds=drain_rounds,
        faults=faults,
        spec=spec,
    )
    shard = compat.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(None), P(None), (P(),) * 10),
        check_vma=False,
    )
    return jax.jit(shard)


@lru_cache(maxsize=32)
def _compiled_sharded(
    mesh, axis_name, num_devices, window, tiles_per_window, tile_size,
    num_rows, num_windows, num_boundary_padded, vector_rounds, drain_rounds,
    backend, interpret, faults=None, spec=DEFAULT,
):
    """Compiled locality-sharded body per static schedule shape (the
    schedule ARRAYS are runtime inputs, including window_ids); the frozen
    fault plan (default None) and the frozen state spec are part of the
    static key."""
    fn = partial(
        locality_sharded_fn,
        window=window,
        tiles_per_window=tiles_per_window,
        tile_size=tile_size,
        num_rows=num_rows,
        num_windows=num_windows,
        num_boundary_padded=num_boundary_padded,
        axis_name=axis_name,
        num_devices=num_devices,
        vector_rounds=vector_rounds,
        drain_rounds=drain_rounds,
        backend=backend,
        interpret=interpret,
        faults=faults,
        spec=spec,
    )
    shard = compat.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis_name),) * 6 + (P(None), P(None), P(None)),
        out_specs=(P(None), P(axis_name), P(None), (P(),) * 10),
        check_vma=False,
    )
    return jax.jit(shard)


def _mesh_and_devices(mesh: Optional[Mesh], axis_name: str):
    if mesh is None:
        devs = jax.devices()
        mesh = compat.make_mesh((len(devs),), (axis_name,))
    if isinstance(mesh.shape, dict):
        num_devices = mesh.shape[axis_name]
    else:  # pragma: no cover
        num_devices = dict(zip(mesh.axis_names, mesh.shape))[axis_name]
    return mesh, num_devices


def _finalize(mask, state, stats):
    """Shared host-level epilogue: counters + stats assembly (no policy —
    ``_apply_policy`` owns raising / recovering / reporting)."""
    props, req, ovf, und, gbytes, reads, l_loc, l_rep, s_rep, wins = stats
    lost = props - wins  # proposals that did not win the replay
    counters = Counters(
        edge_reads=reads.astype(jnp.int32),
        state_loads=(l_loc + l_rep).astype(jnp.int32),
        state_stores=s_rep.astype(jnp.int32),
        rounds=jnp.asarray(1, jnp.int32),
    )
    result = MatchResult(match_mask=mask, state=state, counters=counters)
    dstats = DistStats(
        proposals=props,
        lost_proposals=lost,
        requeued=req,
        retry_overflow=ovf,
        undrained=und,
        gathered_bytes=gbytes,
    )
    return result, dstats


def _effective_knobs(block_size, drain_rounds, faults):
    """The (retry capacity, drain rounds) a run ACTUALLY gets once the fault
    plan has had its say — the ladder stops escalating a knob the plan pins
    (regrowing a buffer the plan truncates right back is wasted work)."""
    cap = block_size
    if faults is not None and faults.truncate_retry is not None:
        cap = min(cap, faults.truncate_retry)
    dr = 0 if (faults is not None and faults.skip_drain) else drain_rounds
    return cap, dr


def _apply_policy(
    run,
    edges: Optional[EdgeList],
    *,
    on_fault: str,
    verify: bool,
    faults: Optional[FaultPlan],
    block_size: int,
    drain_rounds: int,
    tile_size: int,
    vector_rounds: int,
    spec: StateSpec = DEFAULT,
) -> Tuple[MatchResult, DistStats]:
    """The recovery ladder (DESIGN.md §11), shared by both schedules.

    ``run(block_size, drain_rounds) -> (MatchResult, DistStats)`` re-executes
    the protocol under escalated knobs (the sharded closure repartitions the
    global-tier deal, the dispersed one re-deals the stream).

    Policy:
      * ``"raise"``  — the historical hard-fail: ``raise_if_bad()``.
      * ``"report"`` — never raise; fill ``residual_edges`` /
        ``corrupted_cells`` so the caller sees the damage (synchronizes).
      * ``"recover"`` — rung 1: up to ``_MAX_ESCALATIONS`` re-runs,
        geometrically regrowing whichever knob tripped (retry capacity on
        ``retry_overflow``, drain rounds on ``undrained``), skipped when the
        fault plan pins the knob; rung 2: ``faults.residual_replay`` —
        rebuild state from the (always-valid) match mask and complete the
        matching over the residual edges. Provably valid+maximal.

    ``verify=True`` additionally runs ``check_matching`` on the final mask
    (raises on failure under every policy — after ``"recover"`` a failure
    is a bug in the ladder itself, and the error says so).
    """
    if on_fault not in ("raise", "recover", "report"):
        raise ValueError(
            f"on_fault must be 'raise', 'recover' or 'report', got {on_fault!r}"
        )
    if (verify or on_fault in ("recover", "report")) and edges is None:
        raise ValueError(
            "on_fault='recover'/'report' and verify=True need the original "
            "edge list — pass edges even when a prebuilt schedule is given"
        )

    bs, dr = block_size, drain_rounds
    result, dstats = run(bs, dr)
    if on_fault == "raise":
        if not verify:
            dstats.raise_if_bad()
        # with verify the check below subsumes raise_if_bad and reports the
        # actual damage, not just the tripwire
    elif on_fault == "recover":
        attempts = 0
        for _ in range(_MAX_ESCALATIONS):
            ovf, und = jax.device_get(  # host-sync: ok (ladder gate)
                (dstats.retry_overflow, dstats.undrained)
            )
            if int(ovf) == 0 and int(und) == 0:
                break
            nbs = bs * 2 if int(ovf) > 0 else bs
            ndr = max(1, dr) * 2 if int(und) > 0 else dr
            if _effective_knobs(nbs, ndr, faults) == _effective_knobs(
                bs, dr, faults
            ):
                break  # the fault pins the knob — go straight to the replay
            bs, dr = nbs, ndr
            attempts += 1
            result, dstats = run(bs, dr)
        mask, state, residual, recovered, corrupted = residual_replay(
            edges, result.match_mask, result.state,
            tile_size=tile_size, vector_rounds=vector_rounds, spec=spec,
        )
        res_i, cor_i = jax.device_get((residual, corrupted))  # host-sync: ok (ladder gate)
        if int(res_i) > 0 or int(cor_i) > 0:
            attempts += 1  # the replay rung did real work
        result = MatchResult(
            match_mask=mask, state=state, counters=result.counters
        )
        dstats = dataclasses.replace(
            dstats,
            recovery_attempts=jnp.asarray(attempts, jnp.int32),
            residual_edges=residual,
            recovered_matches=recovered,
            corrupted_cells=corrupted,
        )

    if on_fault == "report" or (verify and on_fault == "raise"):
        residual, corrupted = detect_residual(
            edges, result.match_mask, result.state
        )
        dstats = dataclasses.replace(
            dstats, residual_edges=residual, corrupted_cells=corrupted
        )

    if verify:
        chk = check_matching(edges, result.match_mask)
        ok_v, ok_m, res_i, cor_i = (
            int(x) for x in jax.device_get(  # host-sync: ok (verify path)
                (chk["valid"], chk["maximal"],
                 dstats.residual_edges, dstats.corrupted_cells)
            )
        )
        if on_fault == "recover" and not (ok_v and ok_m):
            raise RuntimeError(
                "verify=True after on_fault='recover': recovered matching "
                f"failed validation (valid={bool(ok_v)}, maximal={bool(ok_m)})"
                " — this is a bug in the recovery ladder, please report it"
            )
        if on_fault == "raise" and not (ok_v and ok_m and res_i == 0
                                        and cor_i == 0):
            raise RuntimeError(
                "verify=True: matching failed validation "
                f"(valid={bool(ok_v)}, maximal={bool(ok_m)}, "
                f"residual_edges={res_i}, corrupted_cells={cor_i}) — run "
                "on_fault='recover' to complete it or 'report' to inspect"
            )
    return result, dstats


def distributed_skipper(
    edges: Optional[EdgeList] = None,
    mesh: Optional[Mesh] = None,
    axis_name: str = "data",
    block_size: int = 512,
    vector_rounds: int = 1,
    tile_size: int = 256,
    drain_rounds: int = 4,
    reorder: str = "none",
    window: Optional[int] = None,
    schedule: Optional[WindowSchedule] = None,
    device_schedule: Optional[DeviceSchedule] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    on_fault: str = "raise",
    verify: bool = False,
    faults: Optional[FaultPlan] = None,
    spec: Optional[StateSpec] = None,
) -> Tuple[MatchResult, DistStats]:
    """Run Skipper across the devices of ``mesh`` along ``axis_name``.

    Works for any device count >= 1. With the default ``reorder="none"`` /
    ``window=None`` the raw stream is dealt in dispersed blocks (paper
    §IV-C); passing ``reorder=`` (a ``graphs/reorder.py`` policy) and/or
    ``window=`` switches to the locality-sharded schedule: each device's
    intra-window edges run through the device-resident pipeline
    (``engine.window_tier_pass`` — Pallas on TPU, its jnp twin under
    ``backend="xla"``) with zero communication, and only the global tier
    pays the propose/gather/replay protocol. A prebuilt ``schedule`` /
    ``device_schedule`` skips the host precompute (benchmarks).

    Results are always in the ORIGINAL edge-stream order and vertex ids; at
    D=1 the locality-sharded output is bit-identical to
    ``skipper_match(schedule=..., backend=...)`` (test-pinned).

    Failure handling (DESIGN.md §11): ``on_fault`` replaces the old boolean
    ``check=``.

    * ``"raise"`` (default, == the old ``check=True``): ``RuntimeError`` if
      a must-be-zero invariant tripped (``retry_overflow``/``undrained`` —
      a dropped or undecided edge can break maximality).
    * ``"report"`` (== the old ``check=False``, plus detection): never
      raise; the returned :class:`DistStats` carries ``residual_edges`` /
      ``corrupted_cells`` for inspection. Needs ``edges``. Synchronizes.
    * ``"recover"``: bounded in-protocol escalation (regrow the retry
      buffer / drain rounds, at most ``_MAX_ESCALATIONS`` re-runs), then a
      host-side residual replay that provably completes the matching —
      the result is always valid+maximal on the uncorrupted graph, though
      possibly a *different* maximal matching than a fault-free run's.
      Needs ``edges``.

    ``verify=True`` runs ``core/validate.check_matching`` on the final mask
    (and fills the DistStats degradation fields); ``faults=`` threads a
    :class:`FaultPlan` into the compiled bodies for chaos testing —
    ``None`` (default) compiles to exactly the pre-fault-harness graph.

    ``spec=`` (a ``core/statespec.StateSpec``, default the package-wide
    uint8 default) sets the per-tier state widths: the at-rest/replicated
    arrays, the window tier's VMEM carry, and the PHASE A state-assembly
    wire payload. ``StateSpec.legacy_i32()`` reproduces the pre-spec
    int32+psum graph bit-for-bit (test-pinned).
    """
    mesh, num_devices = _mesh_and_devices(mesh, axis_name)
    spec = resolve_spec(spec)
    if faults is not None and not faults.active:
        faults = None  # all sites off: share the clean compiled body
    drain_eff = 0 if (faults is not None and faults.skip_drain) else None

    sharded = (
        reorder != "none"
        or window is not None
        or schedule is not None
        or device_schedule is not None
    )
    if not sharded:
        if edges is None:
            raise ValueError("the dispersed schedule needs an edge list")

        def run_dispersed(bs, dr):
            return _dispersed_skipper(
                edges, mesh, axis_name, num_devices, bs, vector_rounds,
                tile_size, dr if drain_eff is None else drain_eff, faults,
                spec,
            )

        return _apply_policy(
            run_dispersed, edges,
            on_fault=on_fault, verify=verify, faults=faults,
            block_size=block_size, drain_rounds=drain_rounds,
            tile_size=tile_size, vector_rounds=vector_rounds, spec=spec,
        )

    if device_schedule is None:
        if schedule is None and edges is None:
            raise ValueError("need edges or a prebuilt (device) schedule")
        device_schedule = locality_device_schedule(
            edges, num_devices, block_size,
            window=window, tile_size=tile_size, reorder=reorder,
            schedule=schedule,
        )
    schedule = device_schedule.schedule
    if device_schedule.num_devices != num_devices:
        raise ValueError(
            f"device_schedule was partitioned for {device_schedule.num_devices} "
            f"devices, mesh has {num_devices}"
        )
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    ds0, bs0 = device_schedule, device_schedule.block_size

    def run_sharded(bs, dr):
        # escalated retry capacity == escalated global-tier block size:
        # repartition the SAME WindowSchedule (host-cheap — the window tier
        # deal is unchanged in content, only the boundary blocks re-deal)
        ds = ds0 if bs == bs0 else partition_schedule(
            schedule, num_devices, bs
        )
        return _sharded_run(
            ds, mesh, axis_name, num_devices, vector_rounds,
            dr if drain_eff is None else drain_eff, backend,
            bool(interpret), faults, spec,
        )

    return _apply_policy(
        run_sharded, edges,
        on_fault=on_fault, verify=verify, faults=faults,
        block_size=bs0, drain_rounds=drain_rounds,
        tile_size=tile_size, vector_rounds=vector_rounds, spec=spec,
    )


def _sharded_run(
    device_schedule, mesh, axis_name, num_devices, vector_rounds,
    drain_rounds, backend, interpret, faults, spec=DEFAULT,
):
    """One locality-sharded execution + host epilogue (no policy)."""
    schedule = device_schedule.schedule
    slots = schedule.tiles_per_window * schedule.tile_size
    num_rows = schedule.num_rows
    run = _compiled_sharded(
        mesh, axis_name, num_devices, schedule.window,
        schedule.tiles_per_window, schedule.tile_size, num_rows,
        schedule.num_windows, schedule.num_boundary_padded, vector_rounds,
        drain_rounds, backend, interpret, faults, spec,
    )
    flat, matched_w, bmask, stats = run(
        jnp.asarray(device_schedule.u_rows),
        jnp.asarray(device_schedule.v_rows),
        jnp.asarray(device_schedule.row_slot),
        jnp.asarray(device_schedule.boundary_ub),
        jnp.asarray(device_schedule.boundary_vb),
        jnp.asarray(device_schedule.boundary_ib),
        jnp.asarray(schedule.window_ids),
        jnp.asarray(schedule.boundary_u),
        jnp.asarray(schedule.boundary_v),
    )

    # ---- host epilogue: decisions -> stream order, state -> original ids
    # (the same [windowed ++ global ++ pad] slot layout and stream_src
    # gather skipper_match uses)
    slot_flat = np.where(
        device_schedule.row_slot.reshape(-1) >= 0,
        device_schedule.row_slot.reshape(-1),
        num_rows,
    )
    dec_w = (
        jnp.zeros((num_rows, slots), jnp.int32)
        .at[jnp.asarray(slot_flat)]
        .set(matched_w.reshape(-1, slots), mode="drop")
    )
    decisions = jnp.concatenate(
        [dec_w.reshape(-1), bmask.astype(jnp.int32), jnp.zeros((1,), jnp.int32)]
    )
    mask = decisions[jnp.asarray(schedule.stream_src)] > 0
    perm = schedule.perm
    if perm is None:
        perm = np.arange(schedule.num_vertices, dtype=np.int32)
    state = flat[jnp.asarray(perm)].astype(spec.at_rest_dtype)
    return _finalize(mask, state, stats)


def _dispersed_skipper(
    edges, mesh, axis_name, num_devices, block_size, vector_rounds,
    tile_size, drain_rounds, faults, spec=DEFAULT,
):
    """One raw dispersed-block execution (paper §IV-C), D >= 1 (no policy)."""
    n = edges.num_vertices
    m = edges.num_edges
    e = edges.canonical()
    ub, vb = dispersed_blocks(e, num_devices, block_size)  # [D, R, B]
    num_rounds = ub.shape[1]
    num_edges_padded = num_devices * num_rounds * block_size
    # global stream index of (d, r, b) = ((r * D) + d) * B + b
    d_ids = jnp.arange(num_devices, dtype=jnp.int32)[:, None, None]
    r_ids = jnp.arange(num_rounds, dtype=jnp.int32)[None, :, None]
    b_ids = jnp.arange(block_size, dtype=jnp.int32)[None, None, :]
    ib = (r_ids * num_devices + d_ids) * block_size + b_ids

    run = _compiled_dispersed(
        mesh, axis_name, num_devices, n, num_edges_padded, vector_rounds,
        tile_size, drain_rounds, faults, spec,
    )
    state, mask_padded, stats = run(ub, vb, ib)

    # map padded-stream mask back to the original edge order:
    # stream position of original edge k is k (dispersed_blocks keeps stream
    # order: block index = k // B, position = k % B)
    mask = mask_padded[:m]
    return _finalize(mask, state, stats)
