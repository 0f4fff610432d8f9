"""Differential matrix over the public entry point: every graph shape the
matcher must survive, under every reorder policy, through both backends.

Each case builds ``build_window_schedule(g, window=256, tile_size=128,
reorder=r)`` and runs ``skipper_match(schedule=..., backend=b)`` (Pallas in
interpret mode on the CPU). The result must be a valid maximal matching of
the original graph, the state must hold only ACC/MCHD, and the state must
mark matched exactly the endpoints of the matched edges, each covered once.
The Pallas result must be bit-identical to the XLA twin's on the same
schedule.
"""
import functools

import numpy as np
import pytest
import jax.numpy as jnp

from strategies import adversarial_edge_list, random_edge_list

from repro.core.validate import check_matching, check_state_domain
from repro.core.engine import MCHD
from repro.graphs import (
    EdgeList, bipartite_graph, build_window_schedule, erdos_renyi_graph,
    grid_graph, path_graph, ring_graph, rmat_graph, star_graph,
)
from repro.graphs.reorder import POLICIES
from repro.kernels.skipper_match import skipper_match

WINDOW = 256
TILE = 128


def _complete_graph(n: int) -> EdgeList:
    u, v = np.triu_indices(n, k=1)
    return EdgeList(jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32), n)


GRAPHS = {
    "empty": lambda: EdgeList(jnp.zeros((0,), jnp.int32),
                              jnp.zeros((0,), jnp.int32), 300),
    "hazards": lambda: random_edge_list(
        11, 500, 2000, self_loops=0.1, duplicates=0.1, invalid=0.05),
    "path": lambda: path_graph(1000),
    "star": lambda: star_graph(600),
    "ring": lambda: ring_graph(777),
    "grid": lambda: grid_graph(40, 40),
    "erdos_renyi": lambda: erdos_renyi_graph(2000, 6000, seed=5),
    "rmat10": lambda: rmat_graph(10, 16, seed=2),
    "bipartite": lambda: bipartite_graph(300, 200, 1500, seed=4),
    "adversarial": lambda: adversarial_edge_list(0),
    "complete24": lambda: _complete_graph(24),
    "rmat13": lambda: rmat_graph(13, 4, seed=7),
}


@functools.lru_cache(maxsize=None)
def _graph(gname):
    return GRAPHS[gname]()


@functools.lru_cache(maxsize=None)
def _schedule(gname, reorder):
    return build_window_schedule(
        _graph(gname), window=WINDOW, tile_size=TILE, reorder=reorder
    )


@functools.lru_cache(maxsize=None)
def _match(gname, reorder, backend):
    res = skipper_match(schedule=_schedule(gname, reorder), backend=backend)
    return np.asarray(res.match_mask), np.asarray(res.state)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("reorder", POLICIES)
@pytest.mark.parametrize("gname", list(GRAPHS))
def test_main_path_matrix(gname, reorder, backend):
    g = _graph(gname)
    mask, state = _match(gname, reorder, backend)
    assert mask.shape == (g.num_edges,)
    assert state.shape == (g.num_vertices,)

    chk = check_matching(g, jnp.asarray(mask))
    assert bool(chk["valid"]) and bool(chk["maximal"]), (
        gname, reorder, backend)
    dom = check_state_domain(jnp.asarray(state))
    assert bool(dom["clean"]), {k: int(v) for k, v in dom.items()}

    # the state marks matched exactly the endpoints of matched edges, each
    # an endpoint of one matched edge
    u, v = np.asarray(g.u)[mask], np.asarray(g.v)[mask]
    cover = (np.bincount(u, minlength=g.num_vertices)
             + np.bincount(v, minlength=g.num_vertices))
    matched = state == MCHD
    assert np.all(cover[matched] == 1)
    np.testing.assert_array_equal(matched, cover > 0)

    if backend == "pallas":
        x_mask, x_state = _match(gname, reorder, "xla")
        np.testing.assert_array_equal(mask, x_mask)
        np.testing.assert_array_equal(state, x_state)
