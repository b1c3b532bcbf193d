"""Multilevel setup orchestration: AMG hierarchies and cluster-GS packing.

Two engines, dispatched through the ``repro.api`` registry
(``multilevel: host | resident``), producing **digest-identical**
hierarchies (per-level ``A_l`` ELL digests, aggregation labels, coarse
colors — the PR-3/PR-4 bit-identity discipline):

* ``host``      the legacy orchestration: scipy smoothed prolongator,
  canonical sorted-COO Galerkin on numpy, numpy cluster packing — every
  level round-trips matrix-sized data through host memory (counted in
  ``SETUP_STATS.host_syncs``).
* ``resident``  the whole per-level setup runs jitted on device under
  ``jax.enable_x64``: prolongator assembly from aggregation
  labels via fixed-shape sort/segment-sum, the Galerkin triple product as
  a padded sorted-COO SpGEMM, coarse-level ELL repacking, and cluster/
  color row packing — reusing the PR-4 resident aggregation and coloring
  fixed points.  A full ``build_hierarchy`` is a bounded number of
  dispatches (7 per level + the aggregation's own) with zero matrix-sized
  host syncs; only per-level shape scalars (ELL widths) come back to pick
  the next dispatch's static shapes.

The solve phase (``solvers.amg.v_cycle``) is engine-agnostic: it consumes
the same :class:`AMGHierarchy` either engine builds.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# pow2 padding for traced-count static buckets — the same policy as the
# MIS-2 worklist buckets, imported so the two can never drift
from ..core.mis2 import _bucket as _bucket_pow2
from ..graphs.csr import CSRMatrix, ELLMatrix, csr_to_ell_matrix
from ..obs import metrics as _OBS
from ..obs import span as _obs_span
from ..graphs.handle import Graph, as_graph
from ..graphs.ops import extract_diagonal, matrix_to_scipy
from .galerkin import (
    DENSE_ACCUM_LIMIT,
    _coarse_graph_ell_device,
    _coarse_graph_keys_device,
    _coo_rows_repack_device,
    _coo_to_ell_device,
    _dense_rows_extract_device,
    _dense_to_ell_device,
    _pad_p_rows,
    _spgemm_stage1_dense_device,
    _spgemm_stage1_device,
    _spgemm_stage2_dense_device,
    _spgemm_stage2_device,
    galerkin_coo_host,
)
from .packing import pack_clusters_device, pack_clusters_host
from .prolongator import (
    _prolongator_device,
    _prolongator_pack_device,
    rect_ell,
    smoothed_prolongator_host,
)


def x64_context():
    """Float64 tracing scope for the resident setup path (the host scipy
    reference computes in f64; the device path must match it before the
    final float32 rounding)."""
    return jax.enable_x64(True)


# ---------------------------------------------------------------------------
# setup-phase accounting (HOTLOOP_STATS counterpart for the setup path)
# ---------------------------------------------------------------------------

class SetupStats:
    """Compatibility view over the multilevel-setup registry counters.

    ``host_syncs`` counts matrix-sized device<->host round-trips in the
    *per-level* setup path of a hierarchy/cluster-GS build (the host
    engine pays 3 per level: prolongator, Galerkin product,
    transfer-operator packing); the one-time coarsest-level densify —
    bounded by ``dense_coarse_cap`` and needed only when the dense
    factorization runs on the host — is boundary work and counted by
    neither engine.  ``resident_dispatches`` counts whole-stage jitted
    dispatches of the resident engine (7 per AMG level).

    The numbers live in the process-wide :mod:`repro.obs` registry
    (``multilevel.host_syncs`` / ``multilevel.resident_dispatches``); this
    shim keeps the legacy attribute surface (including ``+=``) working.
    Tests should prefer ``obs.capture()`` over :meth:`reset`.
    """

    _SYNCS = "multilevel.host_syncs"
    _DISPATCHES = "multilevel.resident_dispatches"

    @property
    def host_syncs(self) -> int:
        return int(_OBS.counter(self._SYNCS).value)

    @host_syncs.setter
    def host_syncs(self, v: int) -> None:
        _OBS.counter(self._SYNCS).set_(v)

    @property
    def resident_dispatches(self) -> int:
        return int(_OBS.counter(self._DISPATCHES).value)

    @resident_dispatches.setter
    def resident_dispatches(self, v: int) -> None:
        _OBS.counter(self._DISPATCHES).set_(v)

    def reset(self) -> None:
        _OBS.reset(self._SYNCS)
        _OBS.reset(self._DISPATCHES)


SETUP_STATS = SetupStats()


# ---------------------------------------------------------------------------
# hierarchy containers (the solve phase consumes these; moved here from
# solvers/amg.py, which re-exports them)
# ---------------------------------------------------------------------------

@dataclass
class AMGLevel:
    a_ell: ELLMatrix
    diag: jnp.ndarray
    p_ell: ELLMatrix | None        # prolongator (fine x coarse), None at coarsest
    r_ell: ELLMatrix | None        # restriction = P^T
    n: int
    nnz: int


@dataclass
class AMGHierarchy:
    levels: List[AMGLevel]
    coarse_solve: Callable
    setup_seconds: float
    aggregation_seconds: float
    aggregation: str
    omega: float
    jacobi_weight: float
    smoother_sweeps: int
    level_sizes: list = field(default_factory=list)
    engine: str = "host"
    coarse_dtype: str = "float32"
    coarse_kind: str = "lu"        # 'lu' | 'jacobi' (above dense_coarse_cap)
    timings: dict = field(default_factory=dict)
    dispatches: int = 0            # resident jitted dispatches this build
    _digests: list | None = None

    def as_precond(self) -> Callable:
        from ..solvers.amg import v_cycle   # lazy: solvers imports us

        return functools.partial(v_cycle, self)

    def level_digests(self) -> list[str]:
        """Per-level ``A_l`` ELL digest (cols + vals + mask), lazily
        computed — the build itself never pulls level matrices to host."""
        if self._digests is None:
            self._digests = [ell_matrix_digest(lvl.a_ell)
                             for lvl in self.levels]
        return self._digests


def ell_matrix_digest(ell: ELLMatrix) -> str:
    """One digest string over an ELL matrix's (cols, vals, mask), built
    from the canonical per-array :func:`~repro.api.result.
    determinism_digest` so the two schemes cannot drift."""
    import hashlib

    from ..api.result import determinism_digest

    h = hashlib.sha256()
    for arr in (ell.cols, ell.vals, ell.mask):
        h.update(determinism_digest(arr).encode())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# coarsest-level solver (shared by both engines)
# ---------------------------------------------------------------------------

def resolve_coarse_dtype(coarse_dtype: Optional[str]) -> str:
    """Default coarse factorization dtype: float64 on CPU hosts (free and
    robust), float32 on accelerators (f64 is emulated or absent there)."""
    if coarse_dtype is not None:
        return str(coarse_dtype)
    from ..api.backend import accelerator_present

    return "float32" if accelerator_present() else "float64"


def _coarse_solver(dense, coarse_dtype: str):
    """Cached dense factorization in the requested dtype.

    ``dense`` may be a host or device array: the float32 branch factors
    on device (a device input never round-trips), the float64 branch
    factors on the host (scipy), pulling the capped coarse matrix once.
    """
    if coarse_dtype == "float64":
        import scipy.linalg as sla

        lu_piv = sla.lu_factor(np.asarray(dense, dtype=np.float64))

        def _host_solve(b):
            x = sla.lu_solve(lu_piv, np.asarray(b, dtype=np.float64))
            return x.astype(np.float32)

        def coarse_solve(b):
            # pure_callback keeps the f64 host solve traceable — Krylov
            # drivers apply the preconditioner inside a jitted step
            return jax.pure_callback(
                _host_solve,
                jax.ShapeDtypeStruct(b.shape, jnp.float32), b)

        return coarse_solve
    lu_piv = jax.scipy.linalg.lu_factor(jnp.asarray(dense, dtype=jnp.float32))

    @jax.jit
    def coarse_solve(b):
        return jax.scipy.linalg.lu_solve(lu_piv, b)

    return coarse_solve


def _jacobi_coarse_solver(a_ell: ELLMatrix, diag, weight: float, sweeps: int):
    """Fallback when the coarsest level exceeds ``dense_coarse_cap``:
    weighted-Jacobi sweeps instead of an O(n^2) dense factorization."""
    w = jnp.float32(weight)

    @jax.jit
    def coarse_solve(b):
        x = jnp.zeros_like(b)
        for _ in range(sweeps):
            ax = jnp.sum(a_ell.vals * x[a_ell.cols], axis=1)
            x = x + w * (b - ax) / diag
        return x

    return coarse_solve


@functools.partial(jax.jit, static_argnames=("n",))
def _ell_to_dense_device(cols, vals, mask, *, n: int):
    rid = jnp.arange(n, dtype=jnp.int32)[:, None]
    dense = jnp.zeros((n, n), jnp.float32)
    rows = jnp.where(mask, jnp.broadcast_to(rid, cols.shape), n)
    return dense.at[rows, cols].add(jnp.where(mask, vals, 0.0), mode="drop")


# ---------------------------------------------------------------------------
# per-level builders
# ---------------------------------------------------------------------------

def _host_level(cur: CSRMatrix, labels: np.ndarray, nagg: int, omega: float,
                timings: dict):
    """Host-engine level: scipy prolongator + canonical numpy Galerkin.

    Returns ``(level_without_sizes, a_next)``; three matrix-sized host
    round-trips, counted in ``SETUP_STATS``.
    """
    v = cur.num_rows
    t0 = time.perf_counter()
    pr, pc, pv = smoothed_prolongator_host(cur, labels, nagg, omega)
    _OBS.counter(SetupStats._SYNCS).inc()
    timings["prolongator"] = timings.get("prolongator", 0.0) \
        + time.perf_counter() - t0
    t0 = time.perf_counter()
    a_ell = csr_to_ell_matrix(cur)
    p_pad_cols, p_pad_vals = _pad_p_rows(pr, pc, pv, v)
    cr, cc, cv = galerkin_coo_host(a_ell, p_pad_cols, p_pad_vals, nagg)
    _OBS.counter(SetupStats._SYNCS).inc()
    indptr = np.zeros(nagg + 1, dtype=np.int64)
    np.add.at(indptr, cr + 1, 1)
    a_next = CSRMatrix(jnp.asarray(np.cumsum(indptr).astype(np.int32)),
                       jnp.asarray(cc.astype(np.int32)),
                       jnp.asarray(cv.astype(np.float32)))
    timings["galerkin"] = timings.get("galerkin", 0.0) \
        + time.perf_counter() - t0
    t0 = time.perf_counter()
    p_ell = rect_ell(pr, pc, pv.astype(np.float32), v)
    r_ell = rect_ell(pc, pr, pv.astype(np.float32), nagg)
    _OBS.counter(SetupStats._SYNCS).inc()
    level = AMGLevel(a_ell, extract_diagonal(cur), p_ell, r_ell,
                     v, cur.num_entries)
    timings["pack"] = timings.get("pack", 0.0) + time.perf_counter() - t0
    return level, a_next


def _resident_level(cur_ell: ELLMatrix, cur_nnz: int, labels: np.ndarray,
                    nagg: int, omega: float, timings: dict):
    """Resident-engine level: 7 jitted dispatches, zero matrix-sized host
    syncs (only the ELL width scalars come back to fix static shapes)."""
    v = cur_ell.num_rows
    with x64_context():
        t0 = time.perf_counter()
        labels_j = jnp.asarray(labels.astype(np.int32))
        p_cols, p_vals, p_keep, diag, dp_real, dr = _prolongator_device(
            cur_ell.cols, cur_ell.vals, cur_ell.mask, labels_j, float(omega))
        _OBS.counter(SetupStats._DISPATCHES).inc(2)   # scan + finish (FMA boundary)
        dp_real, dr = int(dp_real), int(dr)       # shape scalars only
        timings["prolongator"] = timings.get("prolongator", 0.0) \
            + time.perf_counter() - t0
        t0 = time.perf_counter()
        a_vals64 = jnp.where(cur_ell.mask, cur_ell.vals.astype(jnp.float64),
                             0.0)
        # key_base = v (shape-derived) so the expensive expansion/sort
        # kernels are compiled once per level shape, not once per
        # aggregate count — the key grouping and order are base-independent
        cpad = _bucket_pow2(nagg)
        if v * cpad <= DENSE_ACCUM_LIMIT:
            # sort-free dense-accumulator SpGEMM (same accumulation order
            # per entry as the sorted path -> bit-identical values)
            dense1, csum1, dq, nnz_q = _spgemm_stage1_dense_device(
                cur_ell.cols, a_vals64, p_cols, p_vals, num_cols=cpad)
            _OBS.counter(SetupStats._DISPATCHES).inc()
            dq, nnz_qi = int(dq), int(nnz_q)
            q_cols, q_vals = _dense_rows_extract_device(
                dense1, csum1, nnz_q, num_cols=cpad,
                width=_bucket_pow2(dq), nnz_bucket=_bucket_pow2(nnz_qi))
            _OBS.counter(SetupStats._DISPATCHES).inc()
            dense2, csum2, width_c, nnz_c = _spgemm_stage2_dense_device(
                p_cols, p_vals, q_cols, q_vals, num_cols=cpad)
            _OBS.counter(SetupStats._DISPATCHES).inc()
            width_c, nnz_c = int(width_c), int(nnz_c)
            ac_cols, ac_vals, ac_mask, _ = _dense_to_ell_device(
                dense2, csum2, jnp.int32(nnz_c), num_cols=cpad,
                num_rows=nagg, width=width_c,
                nnz_bucket=_bucket_pow2(nnz_c))
            _OBS.counter(SetupStats._DISPATCHES).inc()
        else:
            # sorted-COO fallback when the dense accumulator would not
            # fit; key_base = v (shape-derived) so the sort kernels
            # compile once per level shape
            k1, s1, kp1, dq = _spgemm_stage1_device(
                cur_ell.cols, a_vals64, p_cols, p_vals, key_base=v)
            _OBS.counter(SetupStats._DISPATCHES).inc()
            q_cols, q_vals = _coo_rows_repack_device(
                k1, s1, kp1, key_base=v, num_rows=v, width=int(dq))
            _OBS.counter(SetupStats._DISPATCHES).inc()
            keys, sums, keep, nnz_c, width_c = _spgemm_stage2_device(
                p_cols, p_vals, q_cols, q_vals, key_base=v)
            _OBS.counter(SetupStats._DISPATCHES).inc()
            nnz_c, width_c = int(nnz_c), int(width_c)
            ac_cols, ac_vals, ac_mask, _ = _coo_to_ell_device(
                keys, sums, keep, key_base=v, num_rows=nagg, width=width_c)
            _OBS.counter(SetupStats._DISPATCHES).inc()
        timings["galerkin"] = timings.get("galerkin", 0.0) \
            + time.perf_counter() - t0
        t0 = time.perf_counter()
        (pe_cols, pe_vals, pe_mask), (re_cols, re_vals, re_mask) = \
            _prolongator_pack_device(p_cols, p_vals, p_keep,
                                     num_aggregates=nagg, p_width=dp_real,
                                     r_width=dr)
        _OBS.counter(SetupStats._DISPATCHES).inc()
        timings["pack"] = timings.get("pack", 0.0) + time.perf_counter() - t0
    level = AMGLevel(cur_ell, diag,
                     ELLMatrix(pe_cols, pe_vals, pe_mask),
                     ELLMatrix(re_cols, re_vals, re_mask), v, cur_nnz)
    return level, ELLMatrix(ac_cols, ac_vals, ac_mask), nnz_c


# ---------------------------------------------------------------------------
# hierarchy build (both engines)
# ---------------------------------------------------------------------------

def _build_hierarchy_impl(a, aggregation: str = "two_phase",
                          max_levels: int = 10, coarse_size: int = 200,
                          omega: float = 2.0 / 3.0,
                          jacobi_weight: float = 2.0 / 3.0,
                          smoother_sweeps: int = 2,
                          options=None,
                          mis2_engine: Optional[str] = None,
                          interpret=None,
                          engine: str = "host",
                          coarse_dtype: Optional[str] = None,
                          dense_coarse_cap: Optional[int] = None,
                          explicit_restriction: bool = True,
                          first_agg=None) -> AMGHierarchy:
    """Build the SA-AMG hierarchy with the requested multilevel engine.

    ``dense_coarse_cap`` (default: ``coarse_size``) bounds the dense
    coarsest-level factorization: the factor never exceeds what the
    caller asked for, and a coarsening stall or ``max_levels`` cut that
    leaves the coarsest level above the cap falls back to weighted-Jacobi
    sweeps instead of an unrequested O(n^2) densification.

    ``explicit_restriction=False`` drops the stored ``R = P^T`` ELL
    matrices after the build; the V-cycle then restricts matrix-free
    through the transposed ELL SpMV (``kernels.spmv_ell.spmv_t``),
    halving steady-state transfer-operator memory.

    ``first_agg`` optionally injects a precomputed finest-level
    :class:`~repro.core.aggregation.AggregationResult` (the batched
    facade aggregates every finest level in one vmapped dispatch and
    finishes each hierarchy through here).
    """
    from ..api.registry import get_engine

    if engine not in ("host", "resident"):
        raise ValueError(f"unknown multilevel engine {engine!r} "
                         "(host | resident)")
    gh = as_graph(a) if not isinstance(a, Graph) else a
    coarse_dtype = resolve_coarse_dtype(coarse_dtype)
    if dense_coarse_cap is None:
        dense_coarse_cap = coarse_size
    t_setup = time.perf_counter()
    t_agg = 0.0
    timings: dict = {}
    dispatches0 = SETUP_STATS.resident_dispatches
    agg_fn = get_engine("aggregation", aggregation)
    agg_kwargs = dict(options=options, interpret=interpret)
    if mis2_engine is not None:
        agg_kwargs["mis2_engine"] = mis2_engine
    elif engine == "resident":
        # keep the aggregation fixed point device-resident too (labels are
        # bit-identical across mis2 engines, so this is purely execution
        # shape — the host engine keeps its host-driven default)
        agg_kwargs["mis2_engine"] = "compacted_resident"

    levels: List[AMGLevel] = []
    sizes = []
    if engine == "host":
        cur = gh.csr_matrix
        cur_graph, cur_n, cur_nnz = cur.graph, cur.num_rows, cur.num_entries
    else:
        cur_ell = gh.ell_matrix
        cur_graph, cur_n, cur_nnz = gh, gh.num_vertices, gh.num_entries
    while len(levels) < max_levels - 1 and cur_n > coarse_size:
        with _obs_span("multilevel.level", engine=engine,
                       level=len(levels), n=cur_n) as lvl_span:
            t0 = time.perf_counter()
            if first_agg is not None:
                agg, first_agg = first_agg, None
            else:
                agg = agg_fn(cur_graph, **agg_kwargs)
            dt = time.perf_counter() - t0
            t_agg += dt
            timings["aggregate"] = timings.get("aggregate", 0.0) + dt
            if agg.num_aggregates >= cur_n:
                break
            if engine == "host":
                level, cur = _host_level(cur, agg.labels,
                                         agg.num_aggregates, omega, timings)
                sizes.append((level.n, level.nnz))
                cur_graph, cur_n, cur_nnz = cur.graph, cur.num_rows, \
                    cur.num_entries
            else:
                level, cur_ell, cur_nnz = _resident_level(
                    cur_ell, cur_nnz, agg.labels, agg.num_aggregates, omega,
                    timings)
                sizes.append((level.n, level.nnz))
                cur_graph = Graph(cur_ell)
                cur_n = agg.num_aggregates
            lvl_span.annotate(num_aggregates=agg.num_aggregates)
        levels.append(level)

    # coarsest level
    if engine == "host":
        coarsest = AMGLevel(csr_to_ell_matrix(cur), extract_diagonal(cur),
                            None, None, cur.num_rows, cur.num_entries)
    else:
        diag_c = jnp.sum(jnp.where(
            (cur_ell.cols == jnp.arange(cur_n, dtype=jnp.int32)[:, None])
            & cur_ell.mask, cur_ell.vals, jnp.float32(0)), axis=1)
        coarsest = AMGLevel(cur_ell, diag_c, None, None, cur_n, cur_nnz)
    levels.append(coarsest)
    sizes.append((coarsest.n, coarsest.nnz))

    if coarsest.n <= dense_coarse_cap:
        if engine == "host":
            dense = np.asarray(matrix_to_scipy(cur).todense())
        else:
            # stays a device array: the float32 branch of _coarse_solver
            # factors it in place; only the float64/scipy branch pulls it
            dense = _ell_to_dense_device(
                coarsest.a_ell.cols, coarsest.a_ell.vals, coarsest.a_ell.mask,
                n=coarsest.n)
        coarse_solve = _coarse_solver(dense, coarse_dtype)
        coarse_kind = "lu"
    else:
        # the cap guards the O(n^2) densification when max_levels (or a
        # coarsening stall) leaves the coarsest level larger than the
        # caller asked for
        coarse_solve = _jacobi_coarse_solver(
            coarsest.a_ell, coarsest.diag, jacobi_weight,
            sweeps=8 * smoother_sweeps)
        coarse_kind = "jacobi"

    if not explicit_restriction:
        for lvl in levels:
            lvl.r_ell = None      # v_cycle restricts via spmv_t instead

    return AMGHierarchy(
        levels, coarse_solve, time.perf_counter() - t_setup, t_agg,
        aggregation, omega, jacobi_weight, smoother_sweeps, sizes,
        engine=engine, coarse_dtype=coarse_dtype, coarse_kind=coarse_kind,
        timings=timings,
        dispatches=SETUP_STATS.resident_dispatches - dispatches0)


# ---------------------------------------------------------------------------
# cluster-GS setup (both engines)
# ---------------------------------------------------------------------------

def _cluster_gs_setup_impl(a, aggregation: str = "two_phase", options=None,
                           coarsen_levels: int = 1, engine: str = "host",
                           mis2_engine: Optional[str] = None):
    """Aggregate -> color the coarse graph -> pack cluster rows.

    Returns ``(color_rows, num_colors, num_clusters, labels, colors,
    timings)`` with ``timings`` the structured setup-phase split
    ``{aggregate, color, pack}`` in seconds.
    """
    from ..api.registry import get_engine
    from ..core.coloring import _color_graph_impl
    from ..graphs.ops import coarse_graph_from_labels

    if engine not in ("host", "resident"):
        raise ValueError(f"unknown multilevel engine {engine!r} "
                         "(host | resident)")
    gh = as_graph(a)
    v = gh.num_vertices
    timings = {"aggregate": 0.0, "color": 0.0, "pack": 0.0}
    agg_fn = get_engine("aggregation", aggregation)
    agg_kwargs = dict(options=options)
    if mis2_engine is not None:
        agg_kwargs["mis2_engine"] = mis2_engine
    elif engine == "resident":
        agg_kwargs["mis2_engine"] = "compacted_resident"

    def coarse_structure(graph_handle, labels, nagg):
        if engine == "host":
            g = coarse_graph_from_labels(graph_handle.csr, labels, nagg)
            _OBS.counter(SetupStats._SYNCS).inc()
            return Graph(g)
        ell = graph_handle.ell
        with x64_context():     # int64 edge keys (la * V + lb)
            keys, keep, _, width = _coarse_graph_keys_device(
                ell.neighbors, ell.mask, jnp.asarray(labels.astype(np.int32)),
                key_base=ell.num_vertices)
            _OBS.counter(SetupStats._DISPATCHES).inc()
            nbrs, mask = _coarse_graph_ell_device(
                keys, keep, key_base=ell.num_vertices, num_rows=nagg,
                width=int(width))
        _OBS.counter(SetupStats._DISPATCHES).inc()
        from ..graphs.csr import ELLGraph

        return Graph(ELLGraph(nbrs, mask))

    t0 = time.perf_counter()
    agg = agg_fn(gh, **agg_kwargs)
    labels, nagg = agg.labels, agg.num_aggregates
    timings["aggregate"] += time.perf_counter() - t0
    for _ in range(coarsen_levels - 1):        # optional deeper clustering
        t0 = time.perf_counter()
        cg = coarse_structure(gh, labels, nagg)
        agg2 = agg_fn(cg, **agg_kwargs)
        labels = agg2.labels[labels]
        nagg = agg2.num_aggregates
        timings["aggregate"] += time.perf_counter() - t0

    t0 = time.perf_counter()
    coarse = coarse_structure(gh, labels, nagg)
    coloring = _color_graph_impl(coarse)
    timings["color"] += time.perf_counter() - t0
    if not coloring.converged:     # a partial coloring is unusable for GS
        raise RuntimeError("coarse-graph coloring did not converge")

    t0 = time.perf_counter()
    if engine == "host":
        color_rows = pack_clusters_host(labels, coloring.colors,
                                        coloring.num_colors, v)
        _OBS.counter(SetupStats._SYNCS).inc()
    else:
        with x64_context():     # int64 (color, cluster) sort keys
            color_rows = pack_clusters_device(labels, coloring.colors,
                                              coloring.num_colors, v)
        _OBS.counter(SetupStats._DISPATCHES).inc(2)
    timings["pack"] += time.perf_counter() - t0
    return color_rows, coloring.num_colors, nagg, labels, \
        coloring.colors, timings
