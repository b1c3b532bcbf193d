"""Smoothed-aggregation algebraic multigrid built on MIS-2 aggregation.

Reproduces the paper's Table V setting: a V-cycle SA preconditioner whose
aggregates come from Algorithm 2 ("MIS2 Basic"), Algorithm 3 ("MIS2 Agg"),
or the host-sequential greedy ("Serial Agg" stand-in), used inside CG with
a damped-Jacobi smoother.

Setup now lives in :mod:`repro.multilevel` (engines ``host`` | ``resident``
dispatched through the api registry; see ``repro.amg_setup``); this module
keeps the **solve phase** (fully jitted per level: damped-Jacobi pre/post
smoothing, ELL SpMV residuals, ELL prolong/restrict) plus the legacy
entry points, which re-export the multilevel containers unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import xla_ops
from .._compat import DeprecatedMapping, warn_deprecated
from ..core.aggregation import (
    _aggregate_basic_impl,
    _aggregate_serial_greedy_impl,
    _aggregate_two_phase_impl,
)
from ..core.mis2 import Mis2Options
from ..graphs.csr import CSRMatrix, ELLMatrix
from ..multilevel.hierarchy import (  # noqa: F401  (compat re-exports)
    AMGHierarchy,
    AMGLevel,
    _build_hierarchy_impl,
)
from ..multilevel.prolongator import rect_ell as _rect_ell  # noqa: F401

# Deprecated: aggregation dispatch moved to the repro.api engine registry
# (register_engine("aggregation", ...)); this mapping warns on access.
AGGREGATORS = DeprecatedMapping(
    {
        "mis2_basic": _aggregate_basic_impl,          # Alg. 2
        "mis2_agg": _aggregate_two_phase_impl,        # Alg. 3
        "serial": lambda graph, options=None, **_:    # Table V "Serial Agg"
            _aggregate_serial_greedy_impl(graph),
    },
    "solvers.amg.AGGREGATORS",
    'repro.api.registry.get_engine("aggregation", name)',
)


def build_hierarchy(a: CSRMatrix, aggregation: str = "mis2_agg",
                    max_levels: int = 10, coarse_size: int = 200,
                    omega: float = 2.0 / 3.0, jacobi_weight: float = 2.0 / 3.0,
                    smoother_sweeps: int = 2,
                    options: Mis2Options | None = None) -> AMGHierarchy:
    """Deprecated entry point — use :func:`repro.api.amg_setup`."""
    warn_deprecated("repro.solvers.amg.build_hierarchy",
                    "repro.api.amg_setup")
    return _build_hierarchy_impl(a, aggregation, max_levels, coarse_size,
                                 omega, jacobi_weight, smoother_sweeps,
                                 options)


# ---------------------------------------------------------------------------
# solve phase (jitted per level)
# ---------------------------------------------------------------------------

def _spmv(ell: ELLMatrix, x):
    return jnp.sum(ell.vals * x[ell.cols], axis=1)


@functools.partial(jax.jit, static_argnames=("sweeps",))
def _jacobi(ell_cols, ell_vals, diag, x, b, weight, sweeps: int):
    for _ in range(sweeps):
        ax = jnp.sum(ell_vals * xla_ops.gather_rows(x, ell_cols), axis=1)
        x = x + weight * (b - ax) / diag
    return x


def v_cycle(h: AMGHierarchy, b: jnp.ndarray, level: int = 0) -> jnp.ndarray:
    lvl = h.levels[level]
    if lvl.p_ell is None:
        return h.coarse_solve(b)
    w = jnp.float32(h.jacobi_weight)
    x = _jacobi(lvl.a_ell.cols, lvl.a_ell.vals, lvl.diag,
                jnp.zeros_like(b), b, w, h.smoother_sweeps)
    r = b - _spmv(lvl.a_ell, x)
    if lvl.r_ell is not None:
        rc = _spmv(lvl.r_ell, r)
    else:
        # matrix-free restriction: R = P^T via the transposed ELL SpMV
        from ..kernels.spmv_ell import ops as spmv_ops

        rc = spmv_ops.spmv_t(lvl.p_ell, r, h.levels[level + 1].n)
    xc = v_cycle(h, rc, level + 1)
    x = x + _spmv(lvl.p_ell, xc)
    x = _jacobi(lvl.a_ell.cols, lvl.a_ell.vals, lvl.diag, x, b, w,
                h.smoother_sweeps)
    return x
