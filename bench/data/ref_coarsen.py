"""Plain host reference of paper Alg. 3 (two-phase MIS-2 coarsening)
over CSR with closed neighborhoods.

1. Roots of a MIS-2 of the graph form aggregates with their neighbors
   (ids in root order).
2. A second MIS-2 runs on the vertices left over; its roots with at
   least ``min_secondary`` left-over neighbors (self excluded) form
   further aggregates with their left-over neighbors.
3. Up to four rounds, each against the labels at the start of the
   round: a left-over vertex joins the adjacent aggregate it has most
   neighbors in, ties to the smaller aggregate, then to the smaller id.
4. Vertices still left over become aggregates of their own, in id order.
"""
from __future__ import annotations

import numpy as np

from .ref_mis2 import MAX_ROUNDS, mis2_reference

NONE = np.iinfo(np.int32).max


def _closed_min(indptr, indices, values):
    """Per row: min of ``values`` over the closed neighborhood."""
    return np.minimum.reduceat(values[indices], indptr[:-1])


def _root_join(indptr, indices, roots, first_id):
    ids = first_id + np.cumsum(roots) - 1
    lab = _closed_min(indptr, indices, np.where(roots, ids, NONE))
    return np.where(lab == NONE, -1, lab)


def _coupling_join(indptr, indices, labels):
    """One phase-3 round against the frozen ``labels``."""
    v = len(labels)
    rows = np.repeat(np.arange(v), np.diff(indptr))
    nl = labels[indices]
    sel = (labels[rows] < 0) & (indices != rows) & (nl >= 0)
    r, lab = rows[sel], nl[sel]
    if len(r) == 0:
        return labels
    key = r.astype(np.int64) * (v + 1) + lab
    pair, coupling = np.unique(key, return_counts=True)
    pr, pl = pair // (v + 1), pair % (v + 1)
    size = np.bincount(labels[labels >= 0], minlength=v + 1)[pl]
    order = np.lexsort((pl, size, -coupling, pr))
    pr, pl = pr[order], pl[order]
    head = np.ones(len(pr), dtype=bool)
    head[1:] = pr[1:] != pr[:-1]
    out = labels.copy()
    out[pr[head]] = pl[head]
    return out


def coarsen_reference(indptr, indices, min_secondary: int = 2,
                      max_rounds: int = MAX_ROUNDS) -> dict:
    """Labels, aggregate count and total MIS-2 rounds of Alg. 3."""
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices, dtype=np.int64)
    v = len(indptr) - 1
    r1 = mis2_reference(indptr, indices, max_rounds=max_rounds)
    labels = _root_join(indptr, indices, r1["in_set"], 0)
    nagg = int(r1["in_set"].sum())
    rounds = r1["rounds"]
    left = labels < 0
    if left.any():
        r2 = mis2_reference(indptr, indices, active=left,
                            max_rounds=max_rounds)
        rounds += r2["rounds"]
        rows = np.repeat(np.arange(v), np.diff(indptr))
        n_left = np.bincount(rows, weights=(left[indices]
                                            & (indices != rows)),
                             minlength=v)
        roots2 = r2["in_set"] & (n_left >= min_secondary)
        adj = _root_join(indptr, indices, roots2, nagg)
        labels = np.where(left & (adj >= 0), adj, labels)
        nagg += int(roots2.sum())
    for _ in range(4):
        if not (labels < 0).any():
            break
        labels = _coupling_join(indptr, indices, labels)
    left = np.flatnonzero(labels < 0)
    labels[left] = nagg + np.arange(len(left))
    nagg += len(left)
    return {"labels": labels.astype(np.int32), "num_aggregates": nagg,
            "rounds": rounds}
