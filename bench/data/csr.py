"""COO -> CSR for the generators: rows sorted, columns sorted within a
row, duplicates removed (numpy only)."""
from __future__ import annotations

import numpy as np


def csr_from_pairs(rows: np.ndarray, cols: np.ndarray, v: int):
    """Deduplicated CSR ``(indptr, indices)`` as int32 arrays."""
    key = np.unique(rows.astype(np.int64) * v + cols.astype(np.int64))
    r = key // v
    indices = (key - r * v).astype(np.int32)
    indptr = np.zeros(v + 1, dtype=np.int64)
    np.cumsum(np.bincount(r, minlength=v), out=indptr[1:])
    return indptr.astype(np.int32), indices


def closed_symmetric(rows: np.ndarray, cols: np.ndarray, v: int):
    """Symmetric CSR of an edge list with every self loop present
    (closed neighborhoods, as the MIS-2 semantics read them)."""
    keep = rows != cols
    rows, cols = rows[keep], cols[keep]
    diag = np.arange(v, dtype=np.int64)
    return csr_from_pairs(np.concatenate([rows, cols, diag]),
                          np.concatenate([cols, rows, diag]), v)
