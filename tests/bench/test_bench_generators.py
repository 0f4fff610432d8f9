"""The benchmark's seeded graph generators (run here on the CPU)."""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import generators  # noqa: E402
from bench.generators import kronecker, rgg  # noqa: E402

G500 = {"scale": 10, "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "graph_seed": 1}


def _host(uv):
    return tuple(np.asarray(x) for x in uv)


def test_kronecker_quadrant_frequencies():
    """At scale 1 every edge is one quadrant draw: A, B, C, D = 0.57, 0.19,
    0.19, 0.05 within five standard deviations."""
    p = dict(G500, scale=1, edgefactor=1 << 17)
    u, v = _host(jax.jit(lambda: kronecker.unlabelled_edges(p))())
    m = u.shape[0]
    for (ub, vb), prob in {(0, 0): 0.57, (0, 1): 0.19, (1, 0): 0.19,
                           (1, 1): 0.05}.items():
        freq = np.count_nonzero((u == ub) & (v == vb)) / m
        assert abs(freq - prob) < 5 * np.sqrt(prob * (1 - prob) / m)


def test_kronecker_range_and_seeds():
    make, n = generators.stream_maker({"generator": "kronecker", "graph": G500})
    assert n == 1024
    u, v = _host(make(7, 0))
    assert u.shape == v.shape == (16 * 1024,)
    assert u.dtype == v.dtype == np.int32
    assert 0 <= min(u.min(), v.min()) and max(u.max(), v.max()) < n
    # the same seed gives the same stream; another seed the same graph in
    # another order (a seed wider than 32 bits is a seed of its own)
    assert all(np.array_equal(a, b) for a, b in
               zip((u, v), _host(make(7, 0))))
    u2, v2 = _host(make(7 + (1 << 32), 0))
    assert not np.array_equal(u, u2)
    key = lambda a, b: np.sort(a.astype(np.int64) * n + b)  # noqa: E731
    assert np.array_equal(key(u, v), key(u2, v2))


def test_kronecker_degrees_are_skewed():
    make, n = generators.stream_maker({"generator": "kronecker", "graph": G500})
    u, v = _host(make(3, 0))
    deg = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    assert deg.max() > 20 * deg.mean()


def test_morton_numbering():
    r, c = np.meshgrid(np.arange(8), np.arange(8), indexing="ij")
    ids = np.asarray(rgg.morton(r, c))
    assert ids[0, :4].tolist() == [0, 1, 4, 5]
    assert ids[1, :2].tolist() == [2, 3]
    assert sorted(ids.ravel().tolist()) == list(range(64))


RGG10 = {"scale": 10, "graph_seed": 3}


def test_rgg_edges_are_the_pairs_within_the_radius():
    """Every pair of points closer than 0.55 * sqrt(ln n / n), each once,
    by brute force; ids follow the points' Morton order."""
    pts = rgg.points(RGG10)
    n = pts.shape[0]
    q = np.minimum((pts * 65536).astype(np.int64), 65535)
    codes = rgg.morton(q[:, 1], q[:, 0]).astype(np.int64)
    assert (np.diff(codes) >= 0).all()
    d = np.linalg.norm(pts[:, None] - pts[None], axis=-1)
    iu, ju = np.nonzero(np.triu(d < rgg.radius(n), 1))
    u, v = rgg.host_edges(RGG10)
    assert u.dtype == v.dtype == np.int32 and (u < v).all()
    assert sorted(zip(u.tolist(), v.tolist())) == list(zip(iu.tolist(),
                                                          ju.tolist()))


def test_rgg_edge_count_follows_the_rule():
    """At 2**16 points the count is within 1% of n(n-1)/2 times the chance
    that two uniform points of the unit square lie within r."""
    p = {"scale": 16, "graph_seed": 0}
    n = rgg.num_vertices(p)
    r = rgg.radius(n)
    chance = np.pi * r ** 2 - 8 * r ** 3 / 3 + r ** 4 / 2
    u, _ = rgg.host_edges(p)
    assert u.shape[0] == pytest.approx(n * (n - 1) / 2 * chance, rel=0.01)


def test_host_stream_orders_share_one_graph():
    make, n = generators.stream_maker({"generator": "rgg", "graph": RGG10})
    seed = 7 + (1 << 40)
    u, v = make(seed, 0)
    assert n == 1024 and u.dtype == np.int32
    assert all(np.array_equal(a, b) for a, b in zip((u, v), make(seed, 0)))
    u2, v2 = make(seed, 1)
    assert not np.array_equal(u, u2)
    key = lambda a, b: np.sort(a.astype(np.int64) * n + b)  # noqa: E731
    assert np.array_equal(key(u, v), key(u2, v2))
    with pytest.raises(ValueError):
        make(-1, 0)


def test_rgg_n_2_21_shape_and_slots_per_edge():
    """The cold cell's own graph: 14,487,168 edges (the published file has
    14,487,995); the Morton numbering keeps 95.2% of them inside a 2048-id
    window, at 1.1175 scheduled slots per edge (W=2048, T=256, no
    reorder)."""
    import jax.numpy as jnp

    from bench.harness import Run, read_metric
    from bench.loops import schedule_stats
    from repro.graphs import EdgeList, build_window_schedule

    u, v = rgg.host_edges({"scale": 21, "graph_seed": 0})
    assert u.shape == (14_487_168,)
    s = build_window_schedule(EdgeList(jnp.asarray(u), jnp.asarray(v), 1 << 21),
                              window=2048, tile_size=256, reorder="none")
    stats = schedule_stats(s)
    assert stats["num_windowed"] == 13_794_757
    run = Run(setup_s=0.0, calls=[], medges_per_s=0.0,
              peak_bytes=0, schedule=stats, trace=None, peaks=None)
    assert read_metric("slots_per_edge", run) == pytest.approx(1.1175, abs=5e-4)


def test_seed_key_rejects_negative_seeds():
    with pytest.raises(ValueError):
        generators.seed_key(-1)
