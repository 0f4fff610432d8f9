"""Pallas kernel validation (interpret mode): shape/dtype sweeps against the
pure-jnp oracles, per the kernel contract (kernel.py + ops.py + ref.py)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.graphs import erdos_renyi_graph, grid_graph, rmat_graph
from repro.core import assert_matching, sgmm
from repro.kernels.skipper_match import (
    skipper_match, skipper_match_window, ref_match_window,
)


# ------------------------------------------------------------ skipper ------
@pytest.mark.parametrize("window", [128, 512])
@pytest.mark.parametrize("tile", [64, 128])
@pytest.mark.parametrize("m", [37, 300, 1000])
def test_skipper_kernel_matches_ref_exactly(window, tile, m):
    rng = np.random.default_rng(window * 1000 + tile + m)
    u = rng.integers(-1, window, size=m).astype(np.int32)
    v = rng.integers(0, window, size=m).astype(np.int32)
    st0 = jnp.zeros((window,), jnp.int32)
    s1, m1, c1, f1 = skipper_match_window(
        jnp.asarray(u), jnp.asarray(v), st0, tile_size=tile
    )
    pad = (-m) % tile
    up = np.concatenate([u, np.full(pad, -1, np.int32)]).reshape(-1, tile)
    vp = np.concatenate([v, np.full(pad, -1, np.int32)]).reshape(-1, tile)
    s2, m2, c2, f2 = ref_match_window(jnp.asarray(up), jnp.asarray(vp), st0)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2)[:m])
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2)[:m])
    assert int(f1) == int(f2)


@pytest.mark.parametrize("gname,g", [
    ("grid", grid_graph(30, 30)),
    ("er", erdos_renyi_graph(3000, 9000, seed=7)),
    ("rmat", rmat_graph(11, 8, seed=8)),
])
def test_skipper_kernel_full_graph(gname, g):
    res = skipper_match(g, window=1024, tile_size=128)
    out = assert_matching(g, res.match_mask, f"kernel/{gname}")
    # maximal matching size within the 2x bound of another maximal matching
    ms = int(sgmm(g).num_matches)
    assert out["num_matches"] >= ms / 2


def test_skipper_kernel_matches_ref_without_fallback():
    """Oracle honors fallback=False exactly like the kernel (a dependency
    chain that only the sequential fallback would finish stays unmatched)."""
    u = np.array([0, 1, 2, -1], np.int32)
    v = np.array([1, 2, 3, -1], np.int32)
    st0 = jnp.zeros((8,), jnp.int32)
    s1, m1, c1, f1 = skipper_match_window(
        jnp.asarray(u), jnp.asarray(v), st0, tile_size=4,
        vector_rounds=1, fallback=False,
    )
    s2, m2, c2, f2 = ref_match_window(
        jnp.asarray(u).reshape(1, 4), jnp.asarray(v).reshape(1, 4), st0,
        vector_rounds=1, fallback=False,
    )
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    assert int(f1) == int(f2) == 0


CHAIN_U = np.array([0, 1, 2, -1], np.int32)
CHAIN_V = np.array([1, 2, 3, -1], np.int32)


@pytest.mark.parametrize("fallback", [True, False])
def test_window_kernel_counts_the_chain_tile_that_takes_the_fallback(
        fallback):
    """One round commits (0, 1) and kills (1, 2), but (2, 3) was blocked by
    the then-free (1, 2): it is still free after the round, so the tile
    takes the fallback — counted once, and never without it."""
    st0 = jnp.zeros((8,), jnp.int32)
    tiles = [(CHAIN_U, CHAIN_V), (np.full(4, -1, np.int32),) * 2,
             (CHAIN_U, CHAIN_V)]
    u = np.concatenate([t[0] for t in tiles])
    v = np.concatenate([t[1] for t in tiles])
    *_, f1 = skipper_match_window(jnp.asarray(u), jnp.asarray(v), st0,
                                  tile_size=4, fallback=fallback)
    *_, f2 = ref_match_window(jnp.asarray(u).reshape(3, 4),
                              jnp.asarray(v).reshape(3, 4), st0,
                              fallback=fallback)
    # the second chain meets MCHD vertices 0..3: nothing left free
    assert int(f1) == int(f2) == (1 if fallback else 0)
    assert f1.dtype == jnp.int32 and f1.shape == ()


@pytest.mark.parametrize("fallback", [True, False])
def test_pipeline_kernel_counts_fallback_tiles_like_its_twin(fallback):
    """Each row restarts from all-ACC state, so the chain in every row's
    first tile takes the fallback there; the padding tile does not."""
    from repro.kernels.skipper_match import make_ref_pipeline
    from repro.kernels.skipper_match.kernel import build_pipeline_matcher

    pad = np.full(4, -1, np.int32)
    u = np.stack([np.concatenate([CHAIN_U, pad])] * 3)
    v = np.stack([np.concatenate([CHAIN_V, pad])] * 3)
    call = build_pipeline_matcher(3, 2, 4, 8, 1, fallback, True)
    st, m, _, taken = call(jnp.asarray(u), jnp.asarray(v),
                           jnp.zeros((3, 8), jnp.uint8))
    if fallback:
        st2, m2, _, taken2 = make_ref_pipeline(8)(
            jnp.asarray(u).reshape(3, 2, 4), jnp.asarray(v).reshape(3, 2, 4))
        np.testing.assert_array_equal(np.asarray(st), np.asarray(st2))
        np.testing.assert_array_equal(np.asarray(m), np.asarray(m2))
        assert int(taken2) == 3
    assert int(taken) == (3 if fallback else 0)


def test_skipper_kernel_empty_and_selfloops():
    import jax.numpy as jnp
    from repro.graphs.types import EdgeList
    g = EdgeList(jnp.asarray([3, 5, -1], jnp.int32),
                 jnp.asarray([3, 5, -1], jnp.int32), 10)
    res = skipper_match(g, window=16, tile_size=64)
    assert int(res.match_mask.sum()) == 0

