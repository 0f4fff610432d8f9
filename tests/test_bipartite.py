"""The capacitated first-K-claim engine (DESIGN.md §9) and the b-matching
token-to-expert assignment built on it (``bmatch_assign``).

Pins the three contracts the PR-4 unification relies on:
  * bmatch_assign == the sequential greedy over the score-sorted stream
    (exact, not just valid-and-maximal);
  * the three per-side rank implementations compute the identical function;
  * at unit capacity the capacitated path is bit-identical to the engine's
    unit-capacity first-claim rounds (the paper's reservation step).
"""
import numpy as np
import jax.numpy as jnp

# property tests need hypothesis (a [dev] dep); the deterministic pins don't
from strategies import given, settings, st  # noqa: E402

from repro.core import engine
from repro.core.bipartite import BMATCH_VECTOR_ROUNDS, bmatch_assign


def greedy_oracle(tok, exp, n_tok, n_exp, budget, cap):
    """Sequential greedy b-matching in stream order — the fixpoint the
    engine's capacitated rounds + exact fallback must reproduce."""
    used_t = np.zeros(n_tok, np.int64)
    used_e = np.zeros(n_exp, np.int64)
    out = np.zeros(len(tok), bool)
    for i, (t, e) in enumerate(zip(tok, exp)):
        if t < 0:
            continue
        if used_t[t] < budget and used_e[e] < cap:
            out[i] = True
            used_t[t] += 1
            used_e[e] += 1
    return out


@settings(max_examples=25, deadline=None)
@given(
    n_tok=st.integers(1, 80),
    n_exp=st.integers(1, 16),
    budget=st.integers(1, 4),
    cap=st.integers(1, 32),
    m=st.integers(1, 300),
    vector_rounds=st.sampled_from([1, 2, 3]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_bmatch_equals_sequential_greedy(
    n_tok, n_exp, budget, cap, m, vector_rounds, seed
):
    """EXACT equality with the stream-order greedy — implies maximality,
    capacity-respect, and priority order all at once."""
    rng = np.random.default_rng(seed)
    tok = rng.integers(-1, n_tok, m).astype(np.int32)  # -1 = invalid slots
    exp = rng.integers(0, n_exp, m).astype(np.int32)
    accept = np.asarray(
        bmatch_assign(
            jnp.asarray(tok), jnp.asarray(exp),
            num_tokens=n_tok, num_experts=n_exp,
            token_budget=budget, expert_capacity=cap,
            tile_size=64, vector_rounds=vector_rounds,
        )
    )
    want = greedy_oracle(tok, exp, n_tok, n_exp, budget, cap)
    assert np.array_equal(accept, want)
    # capacity constraints never violated (implied, asserted explicitly)
    ok = accept & (tok >= 0)
    assert np.bincount(tok[ok], minlength=n_tok).max(initial=0) <= budget
    assert np.bincount(exp[ok], minlength=n_exp).max(initial=0) <= cap


@settings(max_examples=20, deadline=None)
@given(
    n_tok=st.integers(1, 60),
    n_exp=st.integers(1, 10),
    m=st.integers(1, 200),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_rank_impls_bit_equal(n_tok, n_exp, m, seed):
    """matrix / sort / scatter rank builders compute the identical per-side
    rank function (the capacitated analogue of the unit blocked-impl pin)."""
    rng = np.random.default_rng(seed)
    valid = jnp.asarray(rng.random(m) > 0.1)
    u = jnp.asarray(rng.integers(0, n_tok, m), jnp.int32)
    v = jnp.asarray(rng.integers(0, n_exp, m), jnp.int32)
    free = jnp.asarray(rng.random(m) > 0.4) & valid
    fns = {
        "matrix": engine.ranks_from_matrix(u, v, valid),
        "sort": engine.ranks_by_claim_sort(u, v, valid, n_tok, n_exp),
        "scatter": engine.ranks_by_claim_scatter(u, v, valid, n_tok, n_exp),
    }
    got = {k: fn(free) for k, fn in fns.items()}
    ref_u, ref_v = got["matrix"]
    ref_u = np.where(np.asarray(free), np.asarray(ref_u), 0)
    ref_v = np.where(np.asarray(free), np.asarray(ref_v), 0)
    for name, (ru, rv) in got.items():
        # ranks are only consumed under the free mask; compare there
        assert np.array_equal(np.where(np.asarray(free), np.asarray(ru), 0),
                              ref_u), name
        assert np.array_equal(np.where(np.asarray(free), np.asarray(rv), 0),
                              ref_v), name


def test_bmatch_equals_sequential_greedy_seeded():
    """Hypothesis-free twin of the oracle property (fixed shapes — one
    compile, many data draws) so minimal containers still pin it."""
    rng = np.random.default_rng(0)
    for _ in range(20):
        tok = rng.integers(-1, 40, 256).astype(np.int32)
        exp = rng.integers(0, 8, 256).astype(np.int32)
        accept = np.asarray(
            bmatch_assign(
                jnp.asarray(tok), jnp.asarray(exp),
                num_tokens=40, num_experts=8,
                token_budget=2, expert_capacity=10, tile_size=64,
            )
        )
        assert np.array_equal(accept, greedy_oracle(tok, exp, 40, 8, 2, 10))


def test_conflict_methods_identical_output():
    """End-to-end: forcing each rank implementation through bmatch_assign
    never changes the accept mask."""
    rng = np.random.default_rng(7)
    m = 512
    tok = jnp.asarray(rng.integers(0, 100, m), jnp.int32)
    exp = jnp.asarray(rng.integers(0, 8, m), jnp.int32)
    outs = {}
    for method in ("auto", "matrix", "sort", "scatter"):
        outs[method] = np.asarray(
            bmatch_assign(
                tok, exp, num_tokens=100, num_experts=8,
                token_budget=2, expert_capacity=20, tile_size=128,
                conflict_method=method,
            )
        )
    for method, out in outs.items():
        assert np.array_equal(out, outs["auto"]), method


@settings(max_examples=20, deadline=None)
@given(
    n_tok=st.integers(2, 60),
    n_exp=st.integers(1, 30),
    m=st.integers(1, 250),
    vector_rounds=st.sampled_from([1, 2]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_unit_capacity_bit_identical_to_unit_engine(
    n_tok, n_exp, m, vector_rounds, seed
):
    """caps (1, 1) degenerate case: tile_pass_capacitated must match the
    unit-capacity engine (run_first_claim_rounds + greedy_fallback_rounds
    via tile_pass) bit for bit — matched mask, conflicts counter, AND the
    fallback decision — on the experts-offset unipartite encoding."""
    rng = np.random.default_rng(seed)
    tok = rng.integers(-1, n_tok, m).astype(np.int32)
    exp = rng.integers(0, n_exp, m).astype(np.int32)
    valid = tok >= 0

    used_u = jnp.zeros((n_tok,), jnp.int32)
    used_v = jnp.zeros((n_exp,), jnp.int32)
    (uu, uv), matched_c, conf_c, fb_c = engine.tile_pass_capacitated(
        used_u, used_v, jnp.asarray(tok), jnp.asarray(exp),
        cap_u=1, cap_v=1, vector_rounds=vector_rounds,
    )

    # unit engine on the same tile: experts offset into a shared id space
    n = n_tok + n_exp
    u1 = jnp.asarray(np.where(valid, tok, -1), jnp.int32)
    v1 = jnp.asarray(np.where(valid, exp + n_tok, 0), jnp.int32)
    state0 = jnp.zeros((n,), jnp.uint8)
    state, matched_1, conf_1, fb_1 = engine.tile_pass(
        state0, u1, v1, n=n, vector_rounds=vector_rounds
    )

    assert np.array_equal(np.asarray(matched_c), np.asarray(matched_1))
    assert np.array_equal(np.asarray(conf_c), np.asarray(conf_1))
    assert bool(fb_c) == bool(fb_1)
    # states agree: used == 1 exactly where the unit state is MCHD
    su = np.asarray(state)
    assert np.array_equal(np.asarray(uu) >= 1, su[:n_tok] == engine.MCHD)
    assert np.array_equal(np.asarray(uv) >= 1, su[n_tok:] == engine.MCHD)


def test_rounds_sensitivity():
    """vector_rounds is pure tuning (rounds-invariant output) and the
    documented default of 2 is what retires the common cross-side chains
    without entering the vmap-hostile while_loop fallback.

    Chain instance (single tile): A=(t1,e1), B=(t1,e2), C=(t2,e2), all
    budgets/capacities 1. Round 1: A commits, B is token-blocked by A, C is
    expert-blocked by the still-free B. Round 2: B is dead (t1 full), which
    unblocks C. So one round needs the fallback; two rounds don't."""
    tok = jnp.asarray([1, 1, 2], jnp.int32)
    exp = jnp.asarray([1, 2, 2], jnp.int32)
    kw = dict(num_tokens=3, num_experts=3, token_budget=1,
              expert_capacity=1, tile_size=64, with_stats=True)
    results = {}
    for vr in (1, 2, 3, 5):
        accept, stats = bmatch_assign(tok, exp, vector_rounds=vr, **kw)
        results[vr] = (np.asarray(accept),
                       int(stats["fallback_tiles"]), int(stats["conflicts"]))
    for vr, (accept, _, _) in results.items():
        assert accept.tolist() == [True, False, True], vr  # rounds-invariant
    assert results[1][1] == 1   # vr=1: chain survives into the fallback
    assert results[2][1] == 0   # vr=2: decided in the unrolled rounds
    assert results[BMATCH_VECTOR_ROUNDS][1] == 0  # the default stays safe


def test_first_k_single_round():
    """Why the old private router needed vector_rounds ~= budget and the
    engine does not: a token's budget-k in-tile candidates commit in ONE
    round under the first-K rule (rank < room), not one per round."""
    tok = jnp.asarray([0, 0, 0], jnp.int32)
    exp = jnp.asarray([0, 1, 2], jnp.int32)
    accept, stats = bmatch_assign(
        tok, exp, num_tokens=1, num_experts=3, token_budget=3,
        expert_capacity=1, tile_size=64, vector_rounds=1, with_stats=True,
    )
    assert np.asarray(accept).all()
    assert int(stats["fallback_tiles"]) == 0
    assert int(stats["conflicts"]) == 0


def test_oversubscribed_expert_dies_without_fallback():
    """Structural oversubscription (hot expert) resolves in the unrolled
    rounds: round 1 commits the first `capacity` claims, the rest observe a
    full expert and die — no free edge remains for the fallback."""
    m = 64
    tok = jnp.arange(m, dtype=jnp.int32)
    exp = jnp.zeros((m,), jnp.int32)
    accept, stats = bmatch_assign(
        tok, exp, num_tokens=m, num_experts=1, token_budget=1,
        expert_capacity=5, tile_size=64, vector_rounds=1, with_stats=True,
    )
    assert np.asarray(accept).tolist() == [True] * 5 + [False] * (m - 5)
    assert int(stats["fallback_tiles"]) == 0


def test_used_counts_cross_tiles():
    """The scan carry makes the stream-order greedy global: capacity
    consumed in tile 0 is visible to tile 1."""
    tok = jnp.asarray([0, 1, 2, 3], jnp.int32)
    exp = jnp.asarray([0, 0, 0, 1], jnp.int32)
    accept = bmatch_assign(
        tok, exp, num_tokens=4, num_experts=2, token_budget=1,
        expert_capacity=2, tile_size=2,   # two tiles of two edges
    )
    assert np.asarray(accept).tolist() == [True, True, False, True]


@settings(max_examples=25, deadline=None)
@given(
    n_tok=st.integers(1, 200),
    n_exp=st.integers(1, 16),
    budget=st.integers(1, 4),
    cap=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_bmatch_capacities(n_tok, n_exp, budget, cap, seed):
    """Invariants: per-token budget and per-expert capacity are never
    violated; the assignment is maximal (no acceptable edge remains)."""
    rng = np.random.default_rng(seed)
    m = n_tok * min(n_exp, budget + 2)
    tok = rng.integers(0, n_tok, m).astype(np.int32)
    exp = rng.integers(0, n_exp, m).astype(np.int32)
    accept = np.asarray(
        bmatch_assign(
            jnp.asarray(tok), jnp.asarray(exp),
            num_tokens=n_tok, num_experts=n_exp,
            token_budget=budget, expert_capacity=cap, tile_size=64,
        )
    )
    tok_used = np.bincount(tok[accept], minlength=n_tok)
    exp_used = np.bincount(exp[accept], minlength=n_exp)
    assert tok_used.max(initial=0) <= budget
    assert exp_used.max(initial=0) <= cap
    # maximality: every rejected edge was blocked by a full token or expert
    # *at its decision point*; at the end, any edge with BOTH sides free would
    # violate maximality.
    for t, e, a in zip(tok, exp, accept):
        if not a:
            assert tok_used[t] >= budget or exp_used[e] >= cap


def test_bmatch_respects_priority_order():
    """Earlier (higher-score) edges win contested capacity."""
    tok = jnp.asarray([0, 1, 2], jnp.int32)
    exp = jnp.asarray([0, 0, 0], jnp.int32)
    accept = bmatch_assign(
        tok, exp, num_tokens=3, num_experts=1,
        token_budget=1, expert_capacity=2, tile_size=64,
    )
    assert accept.tolist() == [True, True, False]
