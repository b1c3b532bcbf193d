"""Graph500 Kronecker generator (the reference ``kronecker_generator.m``
in numpy): ``edge_factor * 2^scale`` edges, each placed bit by bit in
the quadrants of the initiator ``[[a, b], [c, 1-a-b-c]]``, then a random
permutation of the vertex labels.  The benchmark symmetrizes the edge
list, drops duplicates and self edges, and adds every self loop.

The edges are drawn once from the configuration's ``generator_seed``
(and kept for the next call); ``seed`` draws the vertex permutation.
So every seed gives the same degree sequence and the same layout shapes,
in another labelling, and with it another MIS-2 answer.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

from .csr import closed_symmetric


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, rng):
    """(i, j) int64 endpoints of ``edge_factor * 2^scale`` edges."""
    m = edge_factor << scale
    i = np.zeros(m, dtype=np.int64)
    j = np.zeros(m, dtype=np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for bit in range(scale):
        i_bit = rng.random(m) > ab
        j_bit = rng.random(m) > np.where(i_bit, c_norm, a_norm)
        i += i_bit.astype(np.int64) << bit
        j += j_bit.astype(np.int64) << bit
    return i, j


@lru_cache(maxsize=1)
def _edges(scale, edge_factor, a, b, c, generator_seed):
    return kronecker_edges(scale, edge_factor, a, b, c,
                           np.random.default_rng(generator_seed))


def generate(cfg: dict, seed):
    v = 1 << cfg["scale"]
    i, j = _edges(cfg["scale"], cfg["edge_factor"], cfg["a"], cfg["b"],
                  cfg["c"], cfg["generator_seed"])
    perm = np.arange(v, dtype=np.int64) if seed is None \
        else np.random.default_rng(seed).permutation(v)
    return closed_symmetric(perm[i], perm[j], v)
