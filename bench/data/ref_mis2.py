"""Plain host replay of paper Alg. 1 (deterministic MIS-2) with the §V-B
worklists, over CSR with closed neighborhoods (every row holds its own
self loop).

State per vertex is one packed uint32 tuple (§V-C): ``IN = 0``,
``OUT = 2^32 - 1``, undecided ``((h >> b) << b) | (v + 1)`` with
``b = ceil(log2(V + 2))`` and ``h`` the high word of the §V-A hash
``f(f(round + 1) ^ f(v + 1))``, ``f`` = xorshift64*.  Each round:

1. every undecided vertex (worklist 1) draws its round priority;
2. every live vertex (worklist 2, all vertices in round 0) takes
   ``M = min`` of the tuples of its closed neighborhood, IN turned OUT;
3. an undecided vertex goes OUT if an active neighbor has ``M = OUT``,
   else IN if every active neighbor's ``M`` equals its own tuple.

Worklist 1 then holds the undecided vertices and worklist 2 those with
``M != OUT``.  The replay also counts the least bytes any engine of this
schedule moves (see :func:`mis2_reference`).
"""
from __future__ import annotations

import math

import numpy as np

IN = np.uint32(0)
OUT = np.uint32(0xFFFFFFFF)
MAX_ROUNDS = 128
WORD = 4     # bytes of a neighbor id, a packed tuple or a result


def _xorshift64_star(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint64(12))
    x = x ^ (x << np.uint64(25))
    x = x ^ (x >> np.uint64(27))
    return x * np.uint64(0x2545F4914F6CDD1D)


def priority_hash(rnd: int, ids: np.ndarray) -> np.ndarray:
    """High 32 bits of ``f(f(rnd + 1) ^ f(ids + 1))`` (uint32)."""
    with np.errstate(over="ignore"):
        r = _xorshift64_star(np.array([rnd + 1], dtype=np.uint64))
        h = _xorshift64_star(r ^ _xorshift64_star(ids.astype(np.uint64)
                                                  + np.uint64(1)))
    return (h >> np.uint64(32)).astype(np.uint32)


def id_bits(v: int) -> int:
    return max(1, math.ceil(math.log2(v + 2)))


def packed_tuples(rnd: int, ids: np.ndarray, b: int) -> np.ndarray:
    hi = (priority_hash(rnd, ids) >> np.uint32(b)) << np.uint32(b)
    return hi | (ids.astype(np.uint32) + np.uint32(1))


def _row_entries(indptr: np.ndarray, rows: np.ndarray):
    """Entry positions of ``rows`` (ascending) and each row's offset
    into that list."""
    deg = (indptr[rows + 1] - indptr[rows]).astype(np.int64)
    starts = np.zeros(len(rows), dtype=np.int64)
    np.cumsum(deg[:-1], out=starts[1:])
    pos = np.arange(int(deg.sum()), dtype=np.int64) \
        - np.repeat(starts - indptr[rows].astype(np.int64), deg)
    return pos, starts


def _distinct(cols: np.ndarray, v: int) -> int:
    seen = np.zeros(v, dtype=bool)
    seen[cols] = True
    return int(seen.sum())


def mis2_reference(indptr: np.ndarray, indices: np.ndarray, active=None,
                   max_rounds: int = MAX_ROUNDS) -> dict:
    """Replay Alg. 1; returns ``in_set``, ``rounds``, ``undecided`` (left
    when ``max_rounds`` cut the replay) and ``least_bytes``.

    Least bytes, summed over rounds, for each pass over a worklist W:
    4 B per neighbor id of the rows of W, 4 B per distinct tuple those
    rows read, and 4 B per result written (one per row of W).  No engine
    of this schedule can read less of the adjacency or of the state, or
    write fewer results, so a time measured for the fixed point can
    never put this count above the chip's bandwidth.
    """
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices, dtype=np.int64)
    v = len(indptr) - 1
    if (np.diff(indptr) < 1).any():
        raise ValueError("every row needs its self loop")
    act = np.ones(v, dtype=bool) if active is None \
        else np.asarray(active, dtype=bool)
    b = id_bits(v)
    t = np.where(act, np.uint32(1), OUT)
    m = np.full(v, OUT, dtype=np.uint32)
    wl1 = np.flatnonzero(act)
    wl2 = np.arange(v)
    least = 0
    rnd = 0
    while len(wl1) and rnd < max_rounds:
        t[wl1] = packed_tuples(rnd, wl1, b)
        # M over worklist 2: poisoned closed-neighborhood minimum
        pos, starts = _row_entries(indptr, wl2)
        cols = indices[pos]
        mv = np.minimum.reduceat(t[cols], starts)
        m[wl2] = np.where(mv == IN, OUT, mv)
        least += WORD * (len(cols) + _distinct(cols, v) + len(wl2))
        # decide over worklist 1
        pos, starts = _row_entries(indptr, wl1)
        cols = indices[pos]
        mn = m[cols]
        an = act[cols]
        tv = np.repeat(t[wl1], np.diff(np.append(starts, len(cols))))
        any_out = np.logical_or.reduceat(an & (mn == OUT), starts)
        all_eq = np.logical_and.reduceat(~an | (mn == tv), starts)
        t[wl1] = np.where(any_out, OUT, np.where(all_eq, IN, t[wl1]))
        least += WORD * (len(cols) + _distinct(cols, v) + len(wl1))
        wl1 = np.flatnonzero((t != IN) & (t != OUT))
        wl2 = np.flatnonzero(m != OUT)
        rnd += 1
    return {"in_set": t == IN, "rounds": rnd, "undecided": len(wl1),
            "least_bytes": least}


def closed_adjacency(indptr, indices):
    import scipy.sparse as sp

    v = len(indptr) - 1
    return sp.csr_matrix((np.ones(len(indices), np.int32), indices, indptr),
                         shape=(v, v))


def mis2_violations(adj, in_set: np.ndarray) -> tuple[int, int]:
    """(vertices whose closed neighborhood holds two or more set members,
    vertices with no set member within distance 2): both 0 for a
    distance-2 maximal independent set."""
    y = adj @ in_set.astype(np.int32)
    return int((y > 1).sum()), int(((adj @ y) == 0).sum())
