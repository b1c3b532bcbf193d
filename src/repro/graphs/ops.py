"""Device-side sparse operations used by MIS-2, aggregation and AMG.

Everything here is jit-friendly JAX (static shapes); host-side helpers that
materialize dynamic-size results (SpGEMM output, coarse graphs) return numpy
and are setup-time only — mirroring the paper's setup/solve split.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops
from .csr import CSRGraph, CSRMatrix, ELLGraph, ELLMatrix, csr_from_coo


# ---------------------------------------------------------------------------
# SpMV (ELL): the AMG / Gauss-Seidel hot loop
# ---------------------------------------------------------------------------

def spmv_ell(m: ELLMatrix, x: jnp.ndarray) -> jnp.ndarray:
    """y = A @ x with A in ELL form. Padding has vals == 0 so no mask needed."""
    gathered = xla_ops.gather_rows(x, m.cols)          # [V, D]
    return jnp.sum(m.vals * gathered, axis=1)


def spmv_csr_segment(m: CSRMatrix, x: jnp.ndarray) -> jnp.ndarray:
    """Segment-sum CSR SpMV — the 'no coalescing' baseline layout."""
    v = m.num_rows
    rows = jnp.repeat(
        jnp.arange(v, dtype=jnp.int32), jnp.diff(m.indptr),
        total_repeat_length=m.indices.shape[0],
    )
    contrib = m.values * x[m.indices]
    return jax.ops.segment_sum(contrib, rows, num_segments=v)


# ---------------------------------------------------------------------------
# Neighbor reductions (ELL) — the MIS-2 inner loops
# ---------------------------------------------------------------------------

def neighbor_min(ell: ELLGraph, t: jnp.ndarray) -> jnp.ndarray:
    """min_{w in N[v]} t[w] (closed: self-padding makes min include self)."""
    return jnp.min(xla_ops.gather_rows(t, ell.neighbors), axis=1)


def neighbor_all_eq(ell: ELLGraph, m: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """forall w in N[v]: m[w] == t[v] (closed; padding contributes m[v])."""
    return jnp.all(xla_ops.gather_rows(m, ell.neighbors) == t[:, None], axis=1)


def neighbor_any_eq(ell: ELLGraph, m: jnp.ndarray, value) -> jnp.ndarray:
    """exists w in N[v]: m[w] == value (closed)."""
    return jnp.any(xla_ops.gather_rows(m, ell.neighbors) == value, axis=1)


# ---------------------------------------------------------------------------
# Host-side structural ops (setup time)
# ---------------------------------------------------------------------------

def graph_power2(g: CSRGraph) -> CSRGraph:
    """G^2 (with self loops) via scipy — used only by tests/verification
    (Lemma IV.2: MIS-1(G^2) == MIS-2(G))."""
    import scipy.sparse as sp

    v = g.num_vertices
    indptr = np.asarray(g.indptr, dtype=np.int64)
    indices = np.asarray(g.indices, dtype=np.int64)
    a = sp.csr_matrix(
        (np.ones(len(indices), dtype=np.int8), indices, indptr), shape=(v, v)
    )
    a = a + sp.identity(v, dtype=np.int8, format="csr")
    a2 = (a @ a).tocsr()
    a2.sort_indices()
    return CSRGraph(
        jnp.asarray(a2.indptr.astype(np.int32)),
        jnp.asarray(a2.indices.astype(np.int32)),
    )


def coarse_graph_from_labels(g: CSRGraph, labels: np.ndarray,
                             num_aggregates: int) -> CSRGraph:
    """Coarse graph: aggregate a ~ aggregate b iff a fine edge links them.

    Includes self loops (diagonal), as the coarse matrix would.
    """
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    labels = np.asarray(labels)
    rows = np.repeat(np.arange(g.num_vertices), np.diff(indptr))
    cr, cc = labels[rows], labels[indices]
    keep = (cr >= 0) & (cc >= 0)
    cr, cc = cr[keep], cc[keep]
    diag = np.arange(num_aggregates, dtype=np.int64)
    cr = np.concatenate([cr.astype(np.int64), diag])
    cc = np.concatenate([cc.astype(np.int64), diag])
    return csr_from_coo(cr, cc, num_aggregates)


def galerkin_coarse_matrix(a: CSRMatrix, p_rows: np.ndarray, p_cols: np.ndarray,
                           p_vals: np.ndarray, num_aggregates: int) -> CSRMatrix:
    """A_c = P^T A P with P given in COO (host, scipy; setup-time)."""
    import scipy.sparse as sp

    v = a.num_rows
    indptr = np.asarray(a.indptr, dtype=np.int64)
    indices = np.asarray(a.indices, dtype=np.int64)
    values = np.asarray(a.values, dtype=np.float64)
    asp = sp.csr_matrix((values, indices, indptr), shape=(v, v))
    p = sp.csr_matrix(
        (p_vals.astype(np.float64), (p_rows, p_cols)), shape=(v, num_aggregates)
    )
    ac = (p.T @ asp @ p).tocsr()
    ac.sort_indices()
    ac.eliminate_zeros()
    return CSRMatrix(
        jnp.asarray(ac.indptr.astype(np.int32)),
        jnp.asarray(ac.indices.astype(np.int32)),
        jnp.asarray(ac.data.astype(np.float32)),
    )


def matrix_to_scipy(a: CSRMatrix):
    import scipy.sparse as sp

    v = a.num_rows
    return sp.csr_matrix(
        (np.asarray(a.values, dtype=np.float64),
         np.asarray(a.indices, dtype=np.int64),
         np.asarray(a.indptr, dtype=np.int64)),
        shape=(v, v),
    )


def extract_diagonal(a: CSRMatrix) -> jnp.ndarray:
    indptr = np.asarray(a.indptr)
    indices = np.asarray(a.indices)
    values = np.asarray(a.values)
    v = a.num_rows
    rows = np.repeat(np.arange(v), np.diff(indptr))
    d = np.zeros(v, dtype=values.dtype)
    on_diag = rows == indices
    d[rows[on_diag]] = values[on_diag]
    return jnp.asarray(d)
