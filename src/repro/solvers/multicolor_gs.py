"""Point and cluster multicolor Gauss-Seidel (paper Alg. 4 + §III-C).

Point multicolor GS [Deveci et al. 2016, the paper's baseline]: color the
*fine* matrix graph; rows of one color are independent and update in
parallel; colors sweep sequentially.

Cluster multicolor GS (the paper's contribution): coarsen the graph with
MIS-2 aggregation (Alg. 2 or 3), color the *coarse* graph, then within one
coarse color update all clusters in parallel while rows inside a cluster
update sequentially — locally exact Gauss-Seidel, so fewer Krylov
iterations than point multicolor GS, and the (expensive) greedy coloring
runs on the much smaller coarse graph, cutting setup time (Table VI).

Data layout: per color a padded int32 matrix ``rows[c][n_clusters_c,
max_len_c]`` (sentinel = V, scatter-dropped).  The apply sweeps are a single
jitted function per direction; sequential depth = sum_c max_len_c, exactly
the paper's parallelism structure.  Point GS is the cluster structure with
singleton clusters.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops
from ..graphs.csr import ELLMatrix, csr_to_ell_matrix
from ..graphs.handle import Graph
from ..graphs.ops import extract_diagonal
from ..core.coloring import _color_graph_impl
from ..core.mis2 import Mis2Options
from ..multilevel.packing import pack_clusters_host


@dataclass
class MulticolorGSPreconditioner:
    ell: ELLMatrix
    diag: jnp.ndarray
    color_rows: tuple           # tuple of int32 [n_clusters_c, max_len_c]
    num_colors: int
    num_clusters: int
    setup_seconds: float
    kind: str                   # 'point' | 'cluster'
    timings: dict = field(default_factory=dict)  # setup-phase split
    #                          (aggregate / color / pack seconds)

    def apply(self, b: jnp.ndarray, sweeps: int = 1,
              symmetric: bool = True) -> jnp.ndarray:
        """Approximate A^-1 b by `sweeps` (S)GS sweeps from x0 = 0."""
        return _apply_sweeps(self.ell.cols, self.ell.vals, self.diag,
                             self.color_rows, b, sweeps, symmetric)

    def as_precond(self, sweeps: int = 1, symmetric: bool = True) -> Callable:
        return functools.partial(self.apply, sweeps=sweeps, symmetric=symmetric)


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def _row_update(cols, vals, diag, x, b, rows):
    """GS update of `rows` (parallel across rows; rows are independent)."""
    v = cols.shape[0]
    safe = jnp.clip(rows, 0, v - 1)
    a_cols = cols[safe]                         # [R, D]
    a_vals = vals[safe]
    ax = jnp.sum(a_vals * xla_ops.gather_rows(x, a_cols), axis=1)    # A_i . x
    xi = x[safe]
    new = xi + (b[safe] - ax) / diag[safe]
    return x.at[rows].set(new, mode="drop")


@functools.partial(jax.jit, static_argnames=("sweeps", "symmetric"))
def _apply_sweeps(cols, vals, diag, color_rows, b, sweeps: int,
                  symmetric: bool):
    x = jnp.zeros_like(b)
    for _ in range(sweeps):
        for rows_c in color_rows:               # forward color sweep
            for s in range(rows_c.shape[1]):    # sequential within cluster
                x = _row_update(cols, vals, diag, x, b, rows_c[:, s])
        if symmetric:
            for rows_c in reversed(color_rows):
                for s in reversed(range(rows_c.shape[1])):
                    x = _row_update(cols, vals, diag, x, b, rows_c[:, s])
    return x


# ---------------------------------------------------------------------------
# setup
# ---------------------------------------------------------------------------

# moved to repro.multilevel.packing; kept under its legacy name because
# callers (and tests) import it from here
_pack_clusters = pack_clusters_host


def setup_cluster_gs(a, aggregation: str = "two_phase",
                     options: Mis2Options | None = None,
                     coarsen_levels: int = 1,
                     engine: str = "host") -> MulticolorGSPreconditioner:
    """Cluster multicolor GS setup through the multilevel subsystem.

    ``engine`` picks the multilevel setup path (``host`` | ``resident``;
    see ``repro.api.cluster_gs_setup`` for the auto-selected facade).
    The returned preconditioner carries the structured setup-phase
    timings (``aggregate`` / ``color`` / ``pack`` seconds) in
    ``.timings``.
    """
    import time

    from ..multilevel.hierarchy import _cluster_gs_setup_impl

    if isinstance(a, Graph):
        a = a.csr_matrix
    t0 = time.perf_counter()
    color_rows, num_colors, nagg, _, _, timings = _cluster_gs_setup_impl(
        a, aggregation=aggregation, options=options,
        coarsen_levels=coarsen_levels, engine=engine)
    ell = csr_to_ell_matrix(a)
    diag = extract_diagonal(a)
    return MulticolorGSPreconditioner(
        ell, diag, color_rows, num_colors, nagg,
        time.perf_counter() - t0, "cluster", timings=timings)


def setup_point_gs(a) -> MulticolorGSPreconditioner:
    import time
    if isinstance(a, Graph):
        a = a.csr_matrix
    t0 = time.perf_counter()
    v = a.num_rows
    t_color = time.perf_counter()
    coloring = _color_graph_impl(a.graph)      # colors the FINE graph
    t_color = time.perf_counter() - t_color
    if not coloring.converged:     # a partial coloring is unusable for GS
        raise RuntimeError("fine-graph coloring did not converge")
    t_pack = time.perf_counter()
    labels = np.arange(v, dtype=np.int32)      # singleton clusters
    color_rows = _pack_clusters(labels, coloring.colors, coloring.num_colors, v)
    t_pack = time.perf_counter() - t_pack
    ell = csr_to_ell_matrix(a)
    diag = extract_diagonal(a)
    return MulticolorGSPreconditioner(
        ell, diag, color_rows, coloring.num_colors, v,
        time.perf_counter() - t0, "point",
        timings={"aggregate": 0.0, "color": t_color, "pack": t_pack})
