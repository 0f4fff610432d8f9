"""Block-pair boundary epilogue (DESIGN.md §10): schedule grouping
invariants, pinned bit-identity of the scalar-prefetch Pallas kernel against
the jnp ``tile_pass_pair`` twin across the shapes the grouping must survive
(odd V, V not divisible by window, all-boundary streams, same-block pairs,
empty global tiers), a hypothesis sweep over random graphs, the single-trace
proof that the new epilogue still joins the one compilation unit, and the
lru_cache'd builder identity."""
import numpy as np
import pytest
import jax.numpy as jnp

from strategies import given, random_edge_list, settings, st  # noqa: E402

from repro.core import assert_matching, engine
from repro.graphs import erdos_renyi_graph
from repro.graphs.types import EdgeList
from repro.graphs.windows import build_window_schedule
from repro.kernels.skipper_match import skipper_match, pipeline_trace_count
from repro.kernels.skipper_match.kernel import (
    build_boundary_matcher,
    build_pipeline_matcher,
    build_window_matcher,
)


def _graph(rng, n, m):
    return random_edge_list(rng, n, m, canonical=True)


def _check_grouping(s):
    """The schedule invariants the kernel's aliasing contract relies on:
    every boundary tile holds edges of exactly ONE (blk_u, blk_v) pair,
    pairs are contiguous in lexicographic order, offset-local ids
    reconstruct the global ids, and the stream stays a single pass (stable
    stream order within each pair)."""
    W, T = s.window, s.tile_size
    nbt = s.num_boundary_tiles
    assert s.boundary_blk_u.shape == (nbt,)
    assert s.boundary_blk_v.shape == (nbt,)
    assert s.num_boundary_padded == nbt * T
    key_prev = -1
    for k in range(nbt):
        bu, bv = int(s.boundary_blk_u[k]), int(s.boundary_blk_v[k])
        assert 0 <= bu <= bv < s.num_windows  # canonical u <= v
        sl = slice(k * T, (k + 1) * T)
        real = s.boundary_index[sl] >= 0
        gu, gv = s.boundary_u[sl][real], s.boundary_v[sl][real]
        # every real edge of the tile lives in THIS tile's pair
        np.testing.assert_array_equal(gu // W, bu)
        np.testing.assert_array_equal(gv // W, bv)
        # offset-local ids reconstruct the global ids
        ul = s.boundary_ulocal[sl][real]
        vl = s.boundary_vlocal[sl][real]
        off = W if bv != bu else 0
        np.testing.assert_array_equal(bu * W + ul, gu)
        np.testing.assert_array_equal(bv * W + vl - off, gv)
        assert ((ul >= 0) & (ul < W)).all()
        assert ((vl >= off) & (vl < off + W)).all()
        # pairs are grouped: tile keys never decrease (no interleaving)
        key = bu * s.num_windows + bv
        assert key >= key_prev
        key_prev = key
    # stable within pair: stream order preserved among the real slots
    real = s.boundary_index >= 0
    keys = (s.boundary_u[real] // W) * s.num_windows + s.boundary_v[real] // W
    idx = s.boundary_index[real]
    for kk in np.unique(keys):
        grp = idx[keys == kk]
        assert (np.diff(grp) > 0).all()


def _assert_twins(edges, schedule, label):
    """Pallas block-pair epilogue bit-identical to the jnp twin (mask, state
    AND conflicts), and the result is a valid maximal matching."""
    rp, cp = skipper_match(
        edges, schedule=schedule, backend="pallas", with_conflicts=True
    )
    rx, cx = skipper_match(
        edges, schedule=schedule, backend="xla", with_conflicts=True
    )
    np.testing.assert_array_equal(
        np.asarray(rp.match_mask), np.asarray(rx.match_mask)
    )
    np.testing.assert_array_equal(np.asarray(rp.state), np.asarray(rx.state))
    np.testing.assert_array_equal(np.asarray(cp), np.asarray(cx))
    assert_matching(edges, rp.match_mask, label)
    return rp


@pytest.mark.parametrize("n,window,tile", [
    (701, 128, 64),    # odd V
    (700, 256, 64),    # V not divisible by window
    (901, 128, 32),    # both
])
def test_pair_epilogue_pinned_shapes(n, window, tile):
    rng = np.random.default_rng(n)
    edges = _graph(rng, n, 4 * n)
    s = build_window_schedule(edges, window, tile)
    assert s.num_boundary_padded > 0  # the epilogue actually runs
    _check_grouping(s)
    _assert_twins(edges, s, f"pair/{n}")


def test_pair_epilogue_all_boundary_stream():
    """intra == 0: every edge crosses a window boundary, so the entire graph
    is decided by the block-pair epilogue."""
    rng = np.random.default_rng(3)
    u = rng.integers(0, 128, 1500).astype(np.int32)
    v = rng.integers(128, 640, 1500).astype(np.int32)
    edges = EdgeList(jnp.asarray(u), jnp.asarray(v), 640)
    s = build_window_schedule(edges, window=128, tile_size=64)
    assert s.num_intra == 0
    assert s.num_boundary_padded > 0
    _check_grouping(s)
    _assert_twins(edges, s, "pair/all-boundary")


def test_pair_epilogue_same_block_pairs():
    """Coalesced sparse windows put SAME-block pairs (blk_u == blk_v) in the
    global tier; the kernel degenerates them to one block load and the u-row
    write-back wins (tile_pass_pair's v-then-u order)."""
    rng = np.random.default_rng(4)
    # window 0 dense (stays in the window tier), window 2 sparse (coalesced)
    u0 = rng.integers(0, 128, 600).astype(np.int32)
    v0 = rng.integers(0, 128, 600).astype(np.int32)
    u2 = rng.integers(256, 384, 5).astype(np.int32)
    v2 = rng.integers(256, 384, 5).astype(np.int32)
    u = np.concatenate([u0, u2])
    v = np.concatenate([v0, v2])
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    edges = EdgeList(jnp.asarray(lo), jnp.asarray(hi), 384)
    s = build_window_schedule(edges, window=128, tile_size=64)
    assert (s.boundary_blk_u == s.boundary_blk_v).any()
    _check_grouping(s)
    _assert_twins(edges, s, "pair/same-block")


def test_pair_epilogue_empty_global_tier():
    """V <= window: everything is intra, the epilogue is skipped and the
    grouped arrays are empty."""
    g = erdos_renyi_graph(120, 400, seed=5)
    s = build_window_schedule(g, window=128, tile_size=64)
    assert s.num_boundary_padded == 0
    assert s.num_boundary_tiles == 0
    assert s.num_boundary_pairs == 0
    assert s.boundary_blk_u.size == 0
    _assert_twins(g, s, "pair/empty-global")


def test_pair_epilogue_chunked_prefetch(monkeypatch):
    """A global tier longer than one pallas_call's scalar-prefetch capacity
    (the block ids live in SMEM) runs as a scan of ``PREFETCH_TILES``-tile
    calls plus a tail call over the aliased state: still bit-identical."""
    from repro.kernels.skipper_match import kernel

    monkeypatch.setattr(kernel, "PREFETCH_TILES", 4)
    rng = np.random.default_rng(7)
    edges = _graph(rng, 600, 3000)
    # unique (window, tile): the cached builders are built under the patch
    s = build_window_schedule(edges, window=112, tile_size=40)
    assert s.num_boundary_tiles > 2 * 4 and s.num_boundary_tiles % 4
    _assert_twins(edges, s, "pair/chunked")


def test_pair_epilogue_single_trace():
    """The block-pair epilogue still joins the ONE compilation unit: first
    call traces the pipeline once, a repeat with the same schedule shape
    reuses it (zero host round-trips per window or per pair)."""
    rng = np.random.default_rng(6)
    edges = _graph(rng, 555, 2500)
    # unique (window, tile) so no earlier test pre-populated the cache
    s = build_window_schedule(edges, window=96, tile_size=48)
    assert s.num_boundary_padded > 0
    before = pipeline_trace_count()
    skipper_match(edges, schedule=s, backend="pallas")
    assert pipeline_trace_count() == before + 1
    skipper_match(edges, schedule=s, backend="pallas")
    assert pipeline_trace_count() == before + 1, "retraced on same shapes"


def test_builders_are_cached():
    """lru_cache satellite: repeated builder calls with the same static args
    return the SAME pallas_call object (the single-device driver used to
    rebuild per call)."""
    assert build_boundary_matcher(4, 64, 8, 128) is build_boundary_matcher(
        4, 64, 8, 128
    )
    assert build_window_matcher(4, 64, 128) is build_window_matcher(4, 64, 128)
    assert build_pipeline_matcher(2, 4, 64, 128) is build_pipeline_matcher(
        2, 4, 64, 128
    )
    assert build_boundary_matcher(4, 64, 8, 128) is not build_boundary_matcher(
        8, 64, 8, 128
    )


def test_tile_pass_pair_is_concat_tile_pass():
    """tile_pass_pair == tile_pass on the concatenated rows (the kernel's
    bit-identity-by-construction contract), including the same-block
    degenerate case where the u-row write-back must win."""
    rng = np.random.default_rng(7)
    W = 16
    rows = (rng.integers(0, 2, (4, W)) * 2).astype(np.int32)  # ACC/MCHD
    u = rng.integers(0, W, 8).astype(np.int32)
    v = (rng.integers(0, W, 8) + W).astype(np.int32)
    out, mt, cf, tk = engine.tile_pass_pair(
        jnp.asarray(rows), jnp.asarray(u), jnp.asarray(v), 1, 3,
        window=W, vector_rounds=1,
    )
    pair = np.concatenate([rows[1], rows[3]])
    ref_pair, ref_mt, ref_cf, ref_tk = engine.tile_pass(
        jnp.asarray(pair), jnp.asarray(u), jnp.asarray(v),
        n=2 * W, vector_rounds=1,
    )
    exp = rows.copy()
    exp[1] = np.asarray(ref_pair)[:W]
    exp[3] = np.asarray(ref_pair)[W:]
    np.testing.assert_array_equal(np.asarray(out), exp)
    np.testing.assert_array_equal(np.asarray(mt), np.asarray(ref_mt))
    np.testing.assert_array_equal(np.asarray(cf), np.asarray(ref_cf))

    # same-block pair: v ids stay in [0, W), row 2 = both halves' home
    vs = rng.integers(0, W, 8).astype(np.int32)
    out2, mt2, _, _ = engine.tile_pass_pair(
        jnp.asarray(rows), jnp.asarray(u), jnp.asarray(vs), 2, 2,
        window=W, vector_rounds=1,
    )
    pair2 = np.concatenate([rows[2], rows[2]])
    ref2, ref_mt2, _, _ = engine.tile_pass(
        jnp.asarray(pair2), jnp.asarray(u), jnp.asarray(vs),
        n=2 * W, vector_rounds=1,
    )
    exp2 = rows.copy()
    exp2[2] = np.asarray(ref2)[:W]  # u half wins; v half was never touched
    np.testing.assert_array_equal(np.asarray(out2), exp2)
    np.testing.assert_array_equal(np.asarray(mt2), np.asarray(ref_mt2))


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(min_value=3, max_value=900),
    mult=st.integers(min_value=1, max_value=6),
    window=st.sampled_from([64, 128, 256]),
    tile=st.sampled_from([32, 64]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    dispersed=st.booleans(),
)
def test_pair_epilogue_property(n, mult, window, tile, seed, dispersed):
    """Random graphs x random shapes: grouping invariants hold and the two
    backends stay bit-identical (the hypothesis half of the pinned suite —
    the deterministic pins above run even without hypothesis installed)."""
    rng = np.random.default_rng(seed)
    edges = _graph(rng, n, mult * n)
    s = build_window_schedule(edges, window, tile, dispersed)
    _check_grouping(s)
    _assert_twins(edges, s, "pair/prop")
