"""Graph and sparse-matrix containers.

Two structural formats:

* ``CSRGraph`` / ``CSRMatrix`` — the paper's native format (compressed sparse
  row).  Used for host-side construction and as the interchange format.
* ``ELLGraph`` / ``ELLMatrix`` — the TPU-native format (padded ELLPACK).  Every
  vertex's adjacency row is padded to a common width ``D`` so that neighbor
  reductions become dense, lane-aligned gathers — the TPU analogue of the
  paper's warp-coalesced CRS row reads (DESIGN.md §3).

Padding convention: padded ``neighbors`` entries point at the row's own vertex
(self), with ``mask == False``.  Because the MIS-2 reductions (min / forall /
exists) are computed over *closed* neighborhoods, self-padding is semantically
inert for them; operations that must not see padding (coupling counts,
SpMV) consult ``mask``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import span as _obs_span

Array = jnp.ndarray


class CSRGraph(NamedTuple):
    """Symmetric graph in CSR form (structure only)."""

    indptr: Array   # int32 [V+1]
    indices: Array  # int32 [E]

    @property
    def num_vertices(self) -> int:
        return int(self.indptr.shape[0]) - 1

    @property
    def num_entries(self) -> int:
        return int(self.indices.shape[0])


class CSRMatrix(NamedTuple):
    """Square sparse matrix in CSR form."""

    indptr: Array   # int32 [V+1]
    indices: Array  # int32 [E]
    values: Array   # float [E]

    @property
    def num_rows(self) -> int:
        return int(self.indptr.shape[0]) - 1

    @property
    def num_entries(self) -> int:
        return int(self.indices.shape[0])

    @property
    def graph(self) -> CSRGraph:
        return CSRGraph(self.indptr, self.indices)


class ELLGraph(NamedTuple):
    """Padded (ELLPACK) graph. ``neighbors[v, j]`` is the j-th neighbor of v;
    padded slots hold ``v`` itself with ``mask`` False."""

    neighbors: Array  # int32 [V, D]
    mask: Array       # bool  [V, D]

    @property
    def num_vertices(self) -> int:
        return int(self.neighbors.shape[0])

    @property
    def width(self) -> int:
        return int(self.neighbors.shape[1])


class ELLMatrix(NamedTuple):
    """Padded (ELLPACK) matrix; padded slots hold column=row, value=0."""

    cols: Array    # int32 [V, D]
    vals: Array    # float [V, D]
    mask: Array    # bool  [V, D]

    @property
    def num_rows(self) -> int:
        return int(self.cols.shape[0])

    @property
    def width(self) -> int:
        return int(self.cols.shape[1])

    @property
    def graph(self) -> ELLGraph:
        return ELLGraph(self.cols, self.mask)


# ---------------------------------------------------------------------------
# Host-side (numpy) conversions.  Format conversion is setup-time work, like
# the CRS assembly the paper inherits from the application.
# ---------------------------------------------------------------------------

def csr_from_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    num_vertices: int,
    vals: np.ndarray | None = None,
    *,
    sum_duplicates: bool = True,
):
    """Build CSR (graph or matrix) from COO triples, deduplicating."""
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    if vals is not None:
        vals = vals[order]
    if len(rows):
        keep = np.ones(len(rows), dtype=bool)
        keep[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])
        if vals is not None and sum_duplicates:
            seg = np.cumsum(keep) - 1
            vals = np.bincount(seg, weights=vals, minlength=int(keep.sum()))
        elif vals is not None:
            vals = vals[keep]
        rows, cols = rows[keep], cols[keep]
    indptr = np.zeros(num_vertices + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    if vals is None:
        return CSRGraph(jnp.asarray(indptr), jnp.asarray(cols.astype(np.int32)))
    return CSRMatrix(
        jnp.asarray(indptr),
        jnp.asarray(cols.astype(np.int32)),
        jnp.asarray(vals.astype(np.float32)),
    )


def _csr_host(indptr, indices):
    return np.asarray(indptr), np.asarray(indices)


def to_device(host):
    """Copy a pytree of host arrays to the default device in one
    ``device_put``, inside a ``graph.to_device`` span that waits for the
    copy to land, so the span covers the transfer."""
    with _obs_span("graph.to_device"):
        out = jax.device_put(host)
        jax.block_until_ready(out)
    return out


def csr_to_ell_graph(g: CSRGraph, width: int | None = None) -> ELLGraph:
    """CSR -> ELL. ``width`` defaults to the max degree (rows longer than
    ``width`` would be truncated; we require width >= max degree)."""
    indptr, indices = _csr_host(g.indptr, g.indices)
    v = len(indptr) - 1
    deg = np.diff(indptr)
    d = int(deg.max()) if width is None else int(width)
    if (deg > d).any():
        raise ValueError(f"ELL width {d} < max degree {int(deg.max())}")
    neighbors = np.repeat(np.arange(v, dtype=np.int32)[:, None], d, axis=1)
    mask = np.zeros((v, d), dtype=bool)
    # slot index of each CSR entry within its row
    slot = np.arange(len(indices)) - np.repeat(indptr[:-1], deg)
    rows = np.repeat(np.arange(v), deg)
    neighbors[rows, slot] = indices
    mask[rows, slot] = True
    return ELLGraph(*to_device((neighbors, mask)))


def csr_to_ell_matrix(m: CSRMatrix, width: int | None = None) -> ELLMatrix:
    indptr, indices = _csr_host(m.indptr, m.indices)
    values = np.asarray(m.values)
    v = len(indptr) - 1
    deg = np.diff(indptr)
    d = int(deg.max()) if width is None else int(width)
    if (deg > d).any():
        raise ValueError(f"ELL width {d} < max degree {int(deg.max())}")
    cols = np.repeat(np.arange(v, dtype=np.int32)[:, None], d, axis=1)
    vals = np.zeros((v, d), dtype=values.dtype)
    mask = np.zeros((v, d), dtype=bool)
    slot = np.arange(len(indices)) - np.repeat(indptr[:-1], deg)
    rows = np.repeat(np.arange(v), deg)
    cols[rows, slot] = indices
    vals[rows, slot] = values
    mask[rows, slot] = True
    return ELLMatrix(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask))


def pad_ell_graph(g: ELLGraph, num_rows: int, width: int) -> ELLGraph:
    """Pad an ELL graph to ``[num_rows, width]`` (both >= current shape).

    Follows the module's padding convention: every padded slot — the new
    width columns of real rows and all slots of the new rows — points at
    the row's own vertex with ``mask == False``, so closed-neighborhood
    reductions (MIS-2 min / forall / exists) are unaffected and mask-aware
    consumers skip the padding.  This is the shape-normalization step that
    lets ``repro.batch`` stack many graphs into one ``[B, rows, width]``
    bucket for a vmapped dispatch.
    """
    v, d = g.neighbors.shape
    if num_rows < v or width < d:
        raise ValueError(
            f"pad_ell_graph target [{num_rows}, {width}] smaller than "
            f"current [{v}, {d}]")
    if num_rows == v and width == d:
        return g
    neighbors = np.repeat(np.arange(num_rows, dtype=np.int32)[:, None],
                          width, axis=1)
    mask = np.zeros((num_rows, width), dtype=bool)
    neighbors[:v, :d] = np.asarray(g.neighbors)
    mask[:v, :d] = np.asarray(g.mask)
    return ELLGraph(jnp.asarray(neighbors), jnp.asarray(mask))


def ell_to_csr_graph(g: ELLGraph) -> CSRGraph:
    neighbors = np.asarray(g.neighbors)
    mask = np.asarray(g.mask)
    v, _ = neighbors.shape
    rows = np.repeat(np.arange(v), mask.sum(axis=1))
    cols = neighbors[mask]
    return csr_from_coo(rows, cols, v)


def ensure_self_loops(g: CSRGraph) -> CSRGraph:
    """Add any missing diagonal entries (closed-neighborhood semantics)."""
    indptr, indices = _csr_host(g.indptr, g.indices)
    v = len(indptr) - 1
    rows = np.repeat(np.arange(v), np.diff(indptr))
    has_self = np.zeros(v, dtype=bool)
    has_self[rows[rows == indices]] = True
    missing = np.flatnonzero(~has_self)
    rows = np.concatenate([rows, missing])
    cols = np.concatenate([indices, missing])
    return csr_from_coo(rows.astype(np.int64), cols.astype(np.int64), v)


def symmetrize(g: CSRGraph) -> CSRGraph:
    indptr, indices = _csr_host(g.indptr, g.indices)
    v = len(indptr) - 1
    rows = np.repeat(np.arange(v), np.diff(indptr))
    all_rows = np.concatenate([rows, indices])
    all_cols = np.concatenate([indices, rows])
    return csr_from_coo(all_rows, all_cols, v)


def degrees(g: CSRGraph) -> np.ndarray:
    indptr, _ = _csr_host(g.indptr, g.indices)
    return np.diff(indptr)


# ---------------------------------------------------------------------------
# Degree-bucketed ELL (DESIGN.md §3): one padded block per degree class, so
# a skewed graph does not pay max-degree padding for every row.  Reductions
# run per bucket and scatter back by the bucket's row permutation.
# ---------------------------------------------------------------------------

class BucketedELL(NamedTuple):
    """rows[i], graphs[i]: vertex ids + ELL block of bucket i."""

    rows: tuple       # tuple of int32 arrays
    graphs: tuple     # tuple of ELLGraph

    @property
    def num_vertices(self) -> int:
        return int(sum(len(r) for r in self.rows))

    @property
    def padding_ratio(self) -> float:
        """Padded slots / real entries (1.0 = no waste)."""
        padded = sum(g.neighbors.shape[0] * g.width for g in self.graphs)
        real = sum(int(np.asarray(g.mask).sum()) for g in self.graphs)
        return padded / max(1, real)


def csr_to_bucketed_ell(g: CSRGraph, boundaries=(8, 32, 128)) -> BucketedELL:
    """Split rows into degree buckets (<=8, <=32, <=128, rest)."""
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    v = len(indptr) - 1
    deg = np.diff(indptr)
    edges = [0] + [b for b in boundaries if b < deg.max()] + [int(deg.max())]
    rows_out, graphs_out = [], []
    for lo, hi in zip(edges[:-1], edges[1:]):
        sel = np.flatnonzero((deg > lo) & (deg <= hi))
        if len(sel) == 0:
            continue
        width = int(deg[sel].max())
        nbrs = np.repeat(sel.astype(np.int32)[:, None], width, axis=1)
        mask = np.zeros((len(sel), width), dtype=bool)
        for j, r in enumerate(sel):
            d = deg[r]
            nbrs[j, :d] = indices[indptr[r]:indptr[r] + d]
            mask[j, :d] = True
        rows_out.append(jnp.asarray(sel.astype(np.int32)))
        graphs_out.append(ELLGraph(jnp.asarray(nbrs), jnp.asarray(mask)))
    return BucketedELL(tuple(rows_out), tuple(graphs_out))
