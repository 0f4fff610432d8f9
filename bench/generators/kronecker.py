"""Graph500 Kronecker graph (Graph500 specification, section 3 "Graph
generation", and its reference ``kronecker_generator.m``).

Each of the ``edgefactor * 2**scale`` edges descends ``scale`` levels of
the adjacency matrix; at each level it takes one quadrant, with initiator
probabilities A, B, C and D = 1 - A - B - C, which sets one bit of each
endpoint. Vertex labels are then randomly permuted. The specification's
last step, a shuffle of the edge list, is the stream order that the seed
draws (``generators.stream_maker``); the quadrant draws and the label
permutation come from ``graph_seed``, so the graph is the configuration's.

Self-loops and repeated edges are kept, as the specification keeps them:
the matcher skips self-loops and decides each repeat on its own. One
32-bit draw per edge and level gives both of its coins, as two 16-bit
uniforms: each probability is met to within 2**-17.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def num_vertices(p: dict) -> int:
    return 1 << p["scale"]


def _threshold(prob: float) -> int:
    """A 16-bit uniform is >= the result with probability 1 - prob."""
    return round(prob * (1 << 16))


def base_edges(p: dict):
    u, v = unlabelled_edges(p)
    perm = jax.random.permutation(_keys(p)[1], num_vertices(p))
    return perm[u].astype(jnp.int32), perm[v].astype(jnp.int32)


def _keys(p: dict):
    return jax.random.split(jax.random.key(p["graph_seed"]))


def unlabelled_edges(p: dict):
    """The quadrant descent alone, before the labels are permuted."""
    scale = p["scale"]
    m = p["edgefactor"] << scale
    a, b, c = p["a"], p["b"], p["c"]
    t_ab = _threshold(a + b)               # u's bit is 1: quadrant C or D
    t_c = _threshold(c / (1.0 - a - b))    # then v's bit is 1: D
    t_a = _threshold(a / (a + b))          # else v's bit is 1: B
    draws = _keys(p)[0]

    def level(i, uv):
        u, v = uv
        bits = jax.random.bits(jax.random.fold_in(draws, i), (m,), jnp.uint32)
        u_bit = (bits & 0xFFFF) >= t_ab
        v_bit = (bits >> 16) >= jnp.where(u_bit, t_c, t_a)
        return (u | (u_bit.astype(jnp.int32) << i),
                v | (v_bit.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))
