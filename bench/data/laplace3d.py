"""Galeri ``Laplace3D`` pattern: the 7-point stencil on an
``nx x ny x nz`` grid, with the diagonal.

Grid point ``(x, y, z)`` has id ``x*ny*nz + y*nz + z``.  The seed draws
a permutation of the points inside each plane of the slowest axis (one
contiguous block of ``ny*nz`` ids) and applies it to rows and columns:
the stencil, the degrees and the locality at plane scale stay as they
are, while the MIS-2 answer, which hashes vertex ids, changes with the
seed.  ``seed=None`` keeps the grid order.
"""
from __future__ import annotations

import numpy as np

from .csr import csr_from_pairs

OFFSETS_7PT = ((-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0), (0, 0, -1),
               (0, 0, 1))


def stencil_pairs(nx: int, ny: int, nz: int, offsets=OFFSETS_7PT):
    """(row, col) id pairs of every off-diagonal stencil entry."""
    ids = np.arange(nx * ny * nz, dtype=np.int64).reshape(nx, ny, nz)
    rows, cols = [], []
    for dx, dy, dz in offsets:
        src = tuple(slice(max(0, -d), n - max(0, d))
                    for d, n in zip((dx, dy, dz), (nx, ny, nz)))
        dst = tuple(slice(max(0, d), n - max(0, -d))
                    for d, n in zip((dx, dy, dz), (nx, ny, nz)))
        rows.append(ids[src].ravel())
        cols.append(ids[dst].ravel())
    return np.concatenate(rows), np.concatenate(cols)


def plane_permutation(nx: int, plane: int, seed):
    """``perm[old] = new``: points shuffled within each plane."""
    local = np.tile(np.arange(plane, dtype=np.int64), (nx, 1))
    if seed is not None:
        local = np.random.default_rng(seed).permuted(local, axis=1)
    return (local + plane * np.arange(nx, dtype=np.int64)[:, None]).ravel()


def generate(cfg: dict, seed):
    nx, ny, nz = cfg["nx"], cfg["ny"], cfg["nz"]
    v = nx * ny * nz
    rows, cols = stencil_pairs(nx, ny, nz)
    diag = np.arange(v, dtype=np.int64)
    perm = plane_permutation(nx, ny * nz, seed)
    return csr_from_pairs(perm[np.concatenate([rows, diag])],
                          perm[np.concatenate([cols, diag])], v)
