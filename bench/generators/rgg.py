"""Random geometric graph of the 10th DIMACS Implementation Challenge
(graph partitioning and graph clustering), class ``rgg_n_2_<scale>_s0``:
``n = 2**scale`` points drawn uniformly in the unit square, and an edge
between every two points closer than ``0.55 * sqrt(ln n / n)``, the radius
that makes the graph almost connected (Holtgrewe, Sanders and Schulz,
"Engineering a scalable high quality graph partitioner", IPDPS 2010).

The points come from ``graph_seed``, so the graph is the configuration's;
``--seed`` draws only the stream order (``generators.stream_maker``).
Vertices are numbered along a Morton (Z-order) curve of the points'
16-bit coordinates, so that ids close in number lie close in the square.
Each pair is one edge, listed once, with no self-loop.

The graph is made in bulk on the host: the pairs within the radius come
from scipy's k-d tree over the Morton-sorted points.
"""
from __future__ import annotations

import math

import numpy as np

COORD_BITS = 16


def num_vertices(p: dict) -> int:
    return 1 << p["scale"]


def radius(n: int) -> float:
    return 0.55 * math.sqrt(math.log(n) / n)


def _spread(x):
    """Bits 0..15 of x to the even positions 0..30."""
    x = x.astype(np.uint32)
    x = (x | (x << 8)) & 0x00FF00FF
    x = (x | (x << 4)) & 0x0F0F0F0F
    x = (x | (x << 2)) & 0x33333333
    return (x | (x << 1)) & 0x55555555


def morton(row, col):
    """Z-order code of a cell: ``col``'s bits at the even positions,
    ``row``'s at the odd ones."""
    return _spread(col) | (_spread(row) << 1)


def points(p: dict) -> np.ndarray:
    """The ``n`` points, in vertex-id order: ``[n, 2]`` float64."""
    n = num_vertices(p)
    pts = np.random.default_rng(p["graph_seed"]).random((n, 2))
    top = (1 << COORD_BITS) - 1
    q = np.minimum((pts * (1 << COORD_BITS)).astype(np.uint32), top)
    return pts[np.argsort(morton(q[:, 1], q[:, 0]), kind="stable")]


def host_edges(p: dict):
    """Every pair of points closer than the radius: two int32 arrays."""
    from scipy.spatial import cKDTree

    pts = points(p)
    pairs = cKDTree(pts, balanced_tree=False, compact_nodes=False).query_pairs(
        radius(pts.shape[0]), output_type="ndarray")
    return pairs[:, 0].astype(np.int32), pairs[:, 1].astype(np.int32)
