"""Seeded graph generators.

A configuration names its generator (``"generator"``) and gives it its
parameters (``"graph"``). Each generator module defines
``num_vertices(params)`` and either ``base_edges(params)``, the graph's
edges as two int32 arrays made on the device (traced inside one jitted
call), or ``host_edges(params)``, the same made in bulk on the host once
a run: a function of the parameters alone. ``--seed`` then draws the
order of the edge stream, so every seed of a cell hands the program the
same graph, the same schedule shapes and the same programs to compile, in
another stream order and so with another matching.
"""
from __future__ import annotations

import importlib

import jax
import numpy as np


def load(name: str):
    """The generator module ``bench/generators/<name>.py``."""
    return importlib.import_module(f"bench.generators.{name}")


def seed_key(seed: int, *path: int) -> jax.Array:
    """A PRNG key from a whole ``seed`` in [0, 2**64) — ``jax.random.key``
    alone keeps only the low 32 bits — folded with ``path`` to name
    independent streams."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def _shuffle(key, u, v):
    order = jax.random.permutation(key, u.shape[0])
    return u[order], v[order]


def stream_maker(cfg: dict):
    """``(make, num_vertices)``: ``make(seed, index)`` hands over the
    configuration's edges as two host int32 arrays, in the stream order
    that ``seed`` and ``index`` draw (``index`` names one of a run's
    stream orders). A device generator makes them in one jitted call; a
    host generator's edges are made once and shuffled on the host."""
    gen = load(cfg["generator"])
    params = cfg["graph"]
    if hasattr(gen, "host_edges"):
        base = gen.host_edges(params)

        def make(seed: int, index: int):
            if seed < 0:
                raise ValueError(f"seed must be >= 0, got {seed}")
            order = np.random.default_rng([seed, index]).permutation(
                base[0].shape[0])
            return base[0][order], base[1][order]
    else:
        device = jax.jit(lambda key: _shuffle(key, *gen.base_edges(params)))

        def make(seed: int, index: int):
            u, v = device(seed_key(seed, index))
            host = jax.device_get((u, v))
            u.delete()
            v.delete()
            return host
    return make, gen.num_vertices(params)
