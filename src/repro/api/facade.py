"""The public facade: one call per pipeline, engines dispatched by name,
results in the common :class:`~repro.api.result.Result` protocol.

    import repro

    g = repro.Graph(repro.graphs.laplace3d(32))
    r = repro.mis2(g, engine="pallas")          # Mis2Result
    agg = repro.coarsen(g, method="two_phase")  # AggregationResult
    parts = repro.partition(g, num_parts=16)    # PartitionResult

Every function accepts a :class:`Graph` handle (conversions cached across
calls) or any bare structural container (``CSRGraph``/``CSRMatrix``/
``ELLGraph``/``ELLMatrix``), and an optional :class:`Backend` controlling
the Pallas/interpret/device policy.
"""
from __future__ import annotations

import time
from typing import Optional

from ..batch.container import GraphBatch, as_graph_batch
from ..batch.pipeline import (
    _coarsen_batch_impl,
    _color_batch_impl,
    _mis2_batch_impl,
)
from ..core.mis2 import Mis2Options
from ..graphs.handle import Graph, as_graph
from ..obs import Provenance
from ..obs import span as _obs_span
from .backend import Backend, backend_platform, resolve_backend
from .registry import get_engine
from .result import (
    AggregationResult,
    AmgSetup,
    BatchResult,
    ClusterGsSetup,
    ColoringResult,
    Mis2Result,
    PartitionResult,
    determinism_digest,
)


def _prepare(graph, backend: Backend) -> Graph:
    gh = as_graph(graph)
    if backend.device is not None:
        gh.place(backend.device)
    return gh


def _traced(kind: str, engine, be: Backend, call, wrap):
    """Run one facade engine call inside an ``obs`` span and attach the
    serializable provenance record to the wrapped Result.

    ``call()`` invokes the engine; ``wrap(core, dt)`` builds the facade
    Result.  The root span (wall time + metric deltas: dispatches, host
    syncs, compiles, cache and conversion traffic inside the call) plus
    engine/backend/digest become ``result.provenance`` — every facade
    answer can explain its own cost.
    """
    platform = backend_platform(be)
    with _obs_span(f"api.{kind}", engine=str(engine),
                   backend=platform) as sp:
        t0 = time.perf_counter()
        core = call()
        dt = time.perf_counter() - t0
        result = wrap(core, dt)
    result.provenance = Provenance(kind, str(engine), platform,
                                   getattr(result, "digest", ""),
                                   sp.to_dict())
    return result


def mis2(graph, *, active=None, options: Optional[Mis2Options] = None,
         engine: Optional[str] = None,
         backend: Optional[Backend] = None) -> Mis2Result:
    """Distance-2 maximal independent set (paper Alg. 1), deterministic
    across engines: ``dense`` | ``compacted`` | ``compacted_resident`` |
    ``pallas`` | ``pallas_resident`` | ``pallas_hybrid`` |
    ``distributed`` | ``distributed_single_gather`` return bit-identical
    sets (equal ``digest``) for equal options.

    ``engine=None`` auto-selects: the device-resident engines (one jitted
    dispatch per solve, no host round-trip inside the fixed point) on
    accelerators, the host-driven ``compacted`` driver on CPU hosts;
    ``Backend(pallas=True)`` upgrades either to its Pallas variant.  When
    the graph's padded-ELL bytes estimate exceeds
    ``repro.graphs.hybrid.HYBRID_AUTO_BYTES`` (skewed degree distribution
    at scale), auto-selection routes to ``pallas_hybrid`` — the
    sliced-ELL + COO-spill layout that needs O(E) memory instead of
    O(V x max_degree).  The distributed engines shard vertices over
    ``Backend(mesh=..., axis=...)`` and report their collective-byte
    accounting in ``result.collectives``."""
    from .backend import default_mis2_engine

    be = resolve_backend(backend)
    gh = _prepare(graph, be)
    if engine is None:
        engine = default_mis2_engine(be, options, gh)
    elif be.pallas and engine == "compacted":
        engine = "pallas"       # legacy: Backend(pallas=True) upgrade
    fn = get_engine("mis2", engine)
    return _traced(
        "mis2", engine, be,
        lambda: fn(gh, active, options, be),
        lambda r, dt: Mis2Result(
            r.in_set, r.iterations, r.converged, dt, engine=engine,
            collectives=getattr(r, "collectives", None),
            num_compiles=getattr(r, "num_compiles", None)))


def misk(graph, k: int = 2, *, priority: str = "xorshift_star",
         max_iters: int = 256, engine: Optional[str] = None,
         backend: Optional[Backend] = None) -> Mis2Result:
    """Distance-k maximal independent set (k-fold min-propagation),
    deterministic across engines: ``dense`` (masked jitted fixed point)
    and ``resident`` (§V-B worklist compaction on the row refresh)
    return bit-identical sets.

    ``engine=None`` selects ``dense`` — the distance-k fixed point is
    already one jitted dispatch with zero in-loop host syncs, so there
    is no host-driven default to escape; ``resident`` is the worklist
    ablation shape."""
    from .backend import default_misk_engine

    be = resolve_backend(backend)
    gh = _prepare(graph, be)
    if engine is None:
        engine = default_misk_engine(be)
    fn = get_engine("misk", engine)
    return _traced(
        "misk", engine, be,
        lambda: fn(gh, k, priority, max_iters, be),
        lambda r, dt: Mis2Result(
            r.in_set, r.iterations, r.converged, dt,
            engine=f"misk{k}_{engine}",
            num_compiles=getattr(r, "num_compiles", None)))


def color(graph, *, max_rounds: int = 256, engine: str = "luby",
          backend: Optional[Backend] = None) -> ColoringResult:
    """Deterministic parallel greedy distance-1 coloring.  If the round
    limit is hit before every vertex is colored the result comes back with
    ``converged=False`` (uncolored vertices hold ``-1``) instead of
    raising."""
    be = resolve_backend(backend)
    gh = _prepare(graph, be)
    fn = get_engine("coloring", engine)
    return _traced(
        "color", engine, be,
        lambda: fn(gh, max_rounds, be),
        lambda r, dt: ColoringResult(r.colors, r.rounds, r.converged, dt,
                                     num_colors=r.num_colors))


def coarsen(graph, *, method: str = "two_phase",
            options: Optional[Mis2Options] = None,
            mis2_engine: Optional[str] = None,
            min_secondary_neighbors: int = 2,
            backend: Optional[Backend] = None) -> AggregationResult:
    """MIS-2 graph coarsening: ``method`` is ``two_phase`` (paper Alg. 3),
    ``basic`` (Alg. 2), ``serial`` (host-sequential reference) or
    ``two_phase_distributed`` (Alg. 3 sharded over ``Backend(mesh=...)``;
    pass ``mis2_engine="distributed_single_gather"`` for the half-traffic
    gather schedule).  ``mis2_engine=None`` means the method's default
    inner fixed point (``compacted`` for the single-device methods,
    ``distributed`` for the sharded one); an explicit engine a method
    cannot honor raises.

    ``backend`` is forwarded only to engines that declare it, so
    externally registered aggregation engines using the pre-backend call
    convention keep working."""
    import inspect

    be = resolve_backend(backend)
    gh = _prepare(graph, be)
    fn = get_engine("aggregation", method)
    kwargs = dict(options=options, interpret=be.resolve_interpret(),
                  min_secondary_neighbors=min_secondary_neighbors)
    if mis2_engine is not None:
        # None = "engine's own default": omit the kwarg entirely so engines
        # registered with any default spelling (old convention:
        # mis2_engine="compacted") keep applying their own
        kwargs["mis2_engine"] = mis2_engine
    if "backend" in inspect.signature(fn).parameters:
        kwargs["backend"] = be
    return _traced(
        "coarsen", method, be,
        lambda: fn(gh, **kwargs),
        lambda r, dt: AggregationResult(
            r.labels, r.mis2_iterations, r.converged, dt,
            num_aggregates=r.num_aggregates, roots=r.roots, phase=r.phase))


def partition(graph, num_parts: int, *, coarse_target: Optional[int] = None,
              options: Optional[Mis2Options] = None,
              engine: str = "multilevel",
              backend: Optional[Backend] = None) -> PartitionResult:
    """Multilevel graph partitioning via MIS-2 aggregation (paper §VII)."""
    be = resolve_backend(backend)
    gh = _prepare(graph, be)
    fn = get_engine("partition", engine)
    return _traced(
        "partition", engine, be,
        lambda: fn(gh, num_parts, coarse_target, options, be),
        lambda r, dt: PartitionResult(
            r.parts, r.levels, r.converged, dt, num_parts=r.num_parts,
            edge_cut=r.edge_cut, levels=r.levels, history=list(r.history)))


# ---------------------------------------------------------------------------
# batched entry points (repro.batch): many graphs, few compiled shapes
# ---------------------------------------------------------------------------

def _traced_batch(kind: str, engine, be: Backend, call, wrap) -> BatchResult:
    """Batch variant of :func:`_traced`: the batch-level provenance record
    (one span covering every bucket dispatch) is shared by the
    ``BatchResult`` and each per-graph member Result."""
    batch = _traced(kind, engine, be, call, wrap)
    for r in batch.results:
        r.provenance = batch.provenance
    return batch


def _prepare_batch(graphs, backend: Backend) -> GraphBatch:
    if backend.device is not None:
        # honor Backend.device for prebuilt batches too: place every member
        # handle (cached formats move with it) and restack on that device
        members = graphs.graphs if isinstance(graphs, GraphBatch) else graphs
        return GraphBatch([as_graph(g).place(backend.device)
                           for g in members])
    return as_graph_batch(graphs)


def mis2_batch(graphs, *, options: Optional[Mis2Options] = None,
               backend: Optional[Backend] = None) -> BatchResult:
    """Distance-2 MIS over many graphs at once: size-bucketed, vmapped
    dense fixed point — one compilation per bucket shape, ``B`` graphs per
    dispatch.  Each per-graph result (and its determinism digest) is
    bit-identical to ``mis2(g, engine="dense")``; batching is purely a
    throughput optimization.

    ``graphs`` is a sequence of :class:`Graph` handles / structural
    containers, or a prebuilt :class:`~repro.batch.GraphBatch` (reusable
    across calls — stacking is cached on the handles).
    """
    be = resolve_backend(backend)
    batch = _prepare_batch(graphs, be)

    def _wrap(core, dt):
        per = dt / max(1, len(core))
        results = [Mis2Result(r.in_set, r.iterations, r.converged, per,
                              engine="dense_batched") for r in core]
        return BatchResult(results, dt, engine="dense_batched",
                           bucket_shapes=batch.bucket_shapes)

    return _traced_batch("mis2_batch", "dense_batched", be,
                         lambda: _mis2_batch_impl(batch, options), _wrap)


def color_batch(graphs, *, max_rounds: int = 256,
                backend: Optional[Backend] = None) -> BatchResult:
    """Batched deterministic greedy coloring (vmapped Luby rounds); each
    per-graph result matches ``color(g)`` bit-for-bit."""
    be = resolve_backend(backend)
    batch = _prepare_batch(graphs, be)

    def _wrap(core, dt):
        per = dt / max(1, len(core))
        results = [ColoringResult(r.colors, r.rounds, r.converged, per,
                                  num_colors=r.num_colors) for r in core]
        return BatchResult(results, dt, engine="luby_batched",
                           bucket_shapes=batch.bucket_shapes)

    return _traced_batch("color_batch", "luby_batched", be,
                         lambda: _color_batch_impl(batch, max_rounds), _wrap)


def coarsen_batch(graphs, *, method: str = "two_phase",
                  options: Optional[Mis2Options] = None,
                  min_secondary_neighbors: int = 2,
                  backend: Optional[Backend] = None) -> BatchResult:
    """Batched MIS-2 coarsening (paper Alg. 2/3) over the vmapped dense
    MIS-2; per-graph labels match ``coarsen(g, method=...,
    mis2_engine="dense")`` bit-for-bit."""
    be = resolve_backend(backend)
    if method == "serial":
        # host-sequential reference: no fixed point to batch, so skip the
        # bucket padding/stacking entirely
        from ..core.aggregation import _aggregate_serial_greedy_impl

        members = graphs.graphs if isinstance(graphs, GraphBatch) \
            else [as_graph(g) for g in graphs]

        def _wrap_serial(core, dt):
            per = dt / max(1, len(core))
            results = [AggregationResult(
                r.labels, r.mis2_iterations, r.converged, per,
                num_aggregates=r.num_aggregates, roots=r.roots,
                phase=r.phase) for r in core]
            return BatchResult(results, dt, engine="serial_batched")

        return _traced_batch(
            "coarsen_batch", "serial_batched", be,
            lambda: [_aggregate_serial_greedy_impl(g) for g in members],
            _wrap_serial)
    batch = _prepare_batch(graphs, be)

    def _wrap(core, dt):
        per = dt / max(1, len(core))
        results = [AggregationResult(
            r.labels, r.mis2_iterations, r.converged, per,
            num_aggregates=r.num_aggregates, roots=r.roots,
            phase=r.phase) for r in core]
        return BatchResult(results, dt, engine=f"{method}_batched",
                           bucket_shapes=batch.bucket_shapes)

    return _traced_batch(
        "coarsen_batch", f"{method}_batched", be,
        lambda: _coarsen_batch_impl(batch, method, options,
                                    min_secondary_neighbors), _wrap)


def _wrap_hierarchy(h, aggregation: str, engine: str,
                    wall_time: float) -> AmgSetup:
    import numpy as np

    sizes = np.asarray(h.level_sizes, dtype=np.int64).reshape(-1, 2)
    return AmgSetup(sizes, len(h.levels), True, wall_time,
                    hierarchy=h, aggregation=aggregation,
                    setup_seconds=h.setup_seconds,
                    aggregation_seconds=h.aggregation_seconds,
                    engine=engine, timings=dict(h.timings),
                    dispatches=h.dispatches)


def amg_setup(matrix, *, aggregation: str = "two_phase",
              engine: Optional[str] = None, max_levels: int = 10,
              coarse_size: int = 200, omega: float = 2.0 / 3.0,
              jacobi_weight: float = 2.0 / 3.0, smoother_sweeps: int = 2,
              options: Optional[Mis2Options] = None,
              mis2_engine: Optional[str] = None,
              coarse_dtype: Optional[str] = None,
              dense_coarse_cap: Optional[int] = None,
              explicit_restriction: bool = True,
              backend: Optional[Backend] = None) -> AmgSetup:
    """Smoothed-aggregation AMG setup (paper Table V), dispatched through
    the ``multilevel`` engine registry.

    ``engine``: ``host`` (scipy prolongator + canonical numpy Galerkin;
    matrix-sized host round-trips each level) or ``resident`` (the whole
    per-level setup jitted on device — fixed-shape prolongator assembly,
    padded sorted-COO SpGEMM, coarse ELL repack; zero matrix-sized host
    syncs).  Both produce digest-identical hierarchies (per-level ``A_l``
    ELL digests on the result, labels/colors from the shared aggregation
    and coloring fixed points).  ``engine=None`` auto-selects ``resident``
    on accelerators with IEEE float64, ``host`` on CPU hosts and on the
    TPU, where ``resident`` raises
    :class:`~repro.api.backend.InexactFloat64Error` (XLA emulates f64
    there, so the hierarchy could not match the host engine's).

    ``coarse_dtype`` controls the dense coarsest-level factorization
    (default: float64 on CPU hosts, float32 on accelerators);
    ``dense_coarse_cap`` (default: ``coarse_size``) bounds the densified
    size — a coarsest level left above it by a coarsening stall or the
    ``max_levels`` cut falls back to a weighted-Jacobi coarse solve
    instead of an unrequested O(n^2) dense factor.
    ``explicit_restriction=False`` drops the stored ``R = P^T`` matrices;
    the V-cycle then restricts matrix-free through the transposed ELL
    SpMV kernel (``kernels.spmv_ell.spmv_t``), halving transfer-operator
    memory at the cost of a scatter per restriction.
    """
    from .backend import check_exact_f64, default_multilevel_engine

    be = resolve_backend(backend)
    if engine is None:
        engine = default_multilevel_engine(be)
    check_exact_f64(engine, be)
    gh = _prepare(matrix, be)
    fn = get_engine("multilevel", engine)
    return _traced(
        "amg_setup", engine, be,
        lambda: fn("amg", gh, aggregation=aggregation,
                   max_levels=max_levels, coarse_size=coarse_size,
                   omega=omega, jacobi_weight=jacobi_weight,
                   smoother_sweeps=smoother_sweeps, options=options,
                   mis2_engine=mis2_engine,
                   interpret=be.resolve_interpret(),
                   coarse_dtype=coarse_dtype,
                   dense_coarse_cap=dense_coarse_cap,
                   explicit_restriction=explicit_restriction),
        lambda h, dt: _wrap_hierarchy(h, aggregation, engine, dt))


def amg(matrix, *, aggregation: str = "two_phase", max_levels: int = 10,
        coarse_size: int = 200, omega: float = 2.0 / 3.0,
        jacobi_weight: float = 2.0 / 3.0, smoother_sweeps: int = 2,
        options: Optional[Mis2Options] = None,
        backend: Optional[Backend] = None) -> AmgSetup:
    """Smoothed-aggregation AMG setup (paper Table V).  Returns an
    :class:`AmgSetup` whose ``.as_precond()`` plugs into ``solvers.cg``.

    Equivalent to :func:`amg_setup` with the auto-selected engine; kept
    for source compatibility."""
    return amg_setup(matrix, aggregation=aggregation, max_levels=max_levels,
                     coarse_size=coarse_size, omega=omega,
                     jacobi_weight=jacobi_weight,
                     smoother_sweeps=smoother_sweeps, options=options,
                     backend=backend)


def cluster_gs_setup(matrix, *, aggregation: str = "two_phase",
                     engine: Optional[str] = None,
                     options: Optional[Mis2Options] = None,
                     coarsen_levels: int = 1,
                     mis2_engine: Optional[str] = None,
                     backend: Optional[Backend] = None) -> ClusterGsSetup:
    """Cluster multicolor Gauss-Seidel setup (paper Alg. 4 / Table VI)
    dispatched through the ``multilevel`` engine registry: aggregate with
    MIS-2, color the coarse graph, pack cluster rows per color.

    The ``resident`` engine builds the coarse graph, runs the coloring
    fixed point, and packs the rows on device; ``host`` is the legacy
    numpy path.  Labels, colors, and the packed row matrices are
    bit-identical across engines; the result carries the structured
    setup-phase timings (``aggregate`` / ``color`` / ``pack``).
    """
    from ..graphs.ops import extract_diagonal
    from ..solvers.multicolor_gs import MulticolorGSPreconditioner
    from .backend import default_multilevel_engine

    be = resolve_backend(backend)
    gh = _prepare(matrix, be)
    if engine is None:
        engine = default_multilevel_engine(be, kind="cluster_gs")
    fn = get_engine("multilevel", engine)

    def _build(out, dt):
        color_rows, num_colors, nagg, labels, colors, timings = out
        ell = gh.ell_matrix
        diag = extract_diagonal(gh.csr_matrix)
        pre = MulticolorGSPreconditioner(ell, diag, color_rows, num_colors,
                                         nagg, dt, "cluster",
                                         timings=timings)
        return ClusterGsSetup(labels, 0, True, dt, preconditioner=pre,
                              num_colors=num_colors, num_clusters=nagg,
                              colors=colors, engine=engine, timings=timings)

    return _traced(
        "cluster_gs_setup", engine, be,
        lambda: fn("cluster_gs", gh, aggregation=aggregation,
                   options=options, coarsen_levels=coarsen_levels,
                   mis2_engine=mis2_engine),
        _build)


def amg_setup_batch(matrices, *, aggregation: str = "two_phase",
                    engine: Optional[str] = None,
                    options: Optional[Mis2Options] = None,
                    backend: Optional[Backend] = None,
                    **hierarchy_kwargs) -> BatchResult:
    """Batched AMG setup: every member's finest-level aggregation — the
    dominant setup cost — runs through the vmapped bucketed coarsening
    (one dispatch per bucket shape); each hierarchy is then finished with
    the selected multilevel engine.  Per-graph hierarchies are
    digest-identical to ``amg_setup(g, ...)``."""
    from ..batch.pipeline import _amg_setup_batch_impl
    from .backend import check_exact_f64, default_multilevel_engine

    be = resolve_backend(backend)
    if engine is None:
        engine = default_multilevel_engine(be)
    check_exact_f64(engine, be)
    batch = _prepare_batch(matrices, be)

    def _wrap(hierarchies, dt):
        per = dt / max(1, len(hierarchies))
        results = [_wrap_hierarchy(h, aggregation, engine, per)
                   for h in hierarchies]
        return BatchResult(results, dt, engine=f"{engine}_batched",
                           bucket_shapes=batch.bucket_shapes)

    return _traced_batch(
        "amg_setup_batch", f"{engine}_batched", be,
        lambda: _amg_setup_batch_impl(batch, aggregation, options,
                                      engine=engine, **hierarchy_kwargs),
        _wrap)


__all__ = [
    "mis2", "misk", "color", "coarsen", "partition", "amg",
    "amg_setup", "cluster_gs_setup",
    "mis2_batch", "color_batch", "coarsen_batch", "amg_setup_batch",
    "Graph", "GraphBatch", "Backend", "Mis2Options", "determinism_digest",
]
