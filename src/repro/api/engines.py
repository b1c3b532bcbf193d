"""Built-in engine registrations.

Imported by ``repro.api.__init__`` so that every process that touches the
facade (or looks an engine up lazily from a lower layer, e.g.
``solvers/amg.py``) sees the full engine table.

Engine call conventions
-----------------------
* ``mis2``:        fn(graph, active, options, backend) -> core Mis2Result
* ``aggregation``: fn(graph, options=None, mis2_engine=None,
                      interpret=None, min_secondary_neighbors=2,
                      backend=None) -> core AggregationResult
                   (``mis2_engine=None`` = the engine's own default inner
                   fixed point; ``backend`` is forwarded by the facade
                   only when the engine declares it, so externally
                   registered engines on the old convention keep working)
* ``misk``:        fn(graph, k, priority, max_iters, backend)
                   -> core Mis2Result
* ``multilevel``:  fn(kind, graph, **kwargs) with kind 'amg' | 'cluster_gs'
                   -> AMGHierarchy | cluster-GS setup tuple (the facade
                   wraps either in its Result type)
* ``coloring``:    fn(graph, max_rounds, backend) -> core ColoringResult
* ``partition``:   fn(graph, num_parts, coarse_target, options, backend)
                   -> core PartitionResult
"""
from __future__ import annotations

from ..batch.pipeline import _mis2_batch_impl
from ..core.aggregation import (
    _aggregate_basic_impl,
    _aggregate_serial_greedy_impl,
    _aggregate_two_phase_impl,
)
from ..core.coloring import _color_graph_impl
from ..core.mis2 import (
    Mis2Options,
    _mis2_compacted_impl,
    _mis2_dense_impl,
    _mis2_resident_impl,
)
from ..core.partition import _partition_impl
from .backend import Backend, default_mis2_engine
from .registry import register_engine


def _opts(options) -> Mis2Options:
    return Mis2Options() if options is None else options


def _dist_mesh_kw(mis2_engine, backend) -> dict:
    """mesh/axis kwargs for aggregation impls whose inner MIS-2 engine is
    distributed — the Backend mesh policy must reach the sharded fixed
    point even through the single-device aggregation drivers."""
    if mis2_engine in ("distributed", "distributed_single_gather"):
        from .backend import get_default_backend

        be = backend if backend is not None else get_default_backend()
        mesh, axis = be.resolve_mesh()
        return {"mesh": mesh, "axis": axis}
    return {}


# -- mis2 -------------------------------------------------------------------

@register_engine("mis2", "dense",
                 doc="single jitted lax.while_loop fixed point (masks, no "
                     "worklist compaction); safe inside larger jitted code")
def _mis2_dense(graph, active, options, backend: Backend):
    return _mis2_dense_impl(graph, active, _opts(options))


@register_engine("mis2", "compacted",
                 doc="host-orchestrated §V-B worklist compaction; the "
                     "production CPU/TPU path behind the Fig. 2 ablation")
def _mis2_compacted(graph, active, options, backend: Backend):
    return _mis2_compacted_impl(graph, active, _opts(options), pallas=False,
                                interpret=backend.resolve_interpret())


@register_engine("mis2", "pallas",
                 doc="compacted driver with the Pallas min-propagation "
                     "kernels on the measured hot loop")
def _mis2_pallas(graph, active, options, backend: Backend):
    return _mis2_compacted_impl(graph, active, _opts(options), pallas=True,
                                interpret=backend.resolve_interpret())


@register_engine("mis2", "compacted_resident",
                 doc="device-resident §V-B fixed point: one jitted "
                     "while_loop per solve, the update sets as masks over "
                     "full-width rounds (two neighbor gathers a round on "
                     "packed ELL; index worklists on the unpacked branch), "
                     "zero host round-trips — bit-identical to "
                     "'compacted'; the facade default on accelerators")
def _mis2_compacted_resident(graph, active, options, backend: Backend):
    return _mis2_resident_impl(graph, active, _opts(options), pallas=False,
                               interpret=backend.resolve_interpret())


@register_engine("mis2", "pallas_resident",
                 doc="resident driver with the FUSED Pallas passes (rank "
                     "packing folded into refresh_columns, row-gather "
                     "folded into decide): each round reads the ELL rows "
                     "once per pass, live counts feed pl.when block "
                     "skipping on device")
def _mis2_pallas_resident(graph, active, options, backend: Backend):
    return _mis2_resident_impl(graph, active, _opts(options), pallas=True,
                               interpret=backend.resolve_interpret())


@register_engine("mis2", "pallas_hybrid",
                 doc="resident driver over the degree-aware hybrid layout "
                     "(sliced-ELL degree buckets + sorted-COO spill for "
                     "heavy hitters): one fused Pallas pass per slice + "
                     "segment reductions for the spill, all inside one "
                     "jitted while_loop — O(E) memory on skewed graphs "
                     "whose padded ELL cannot be allocated, bit-identical "
                     "to 'dense'; auto-selected past the padded-ELL bytes "
                     "threshold")
def _mis2_pallas_hybrid(graph, active, options, backend: Backend):
    from ..core.mis2_hybrid import _mis2_hybrid_impl

    return _mis2_hybrid_impl(graph, active, _opts(options),
                             interpret=backend.resolve_interpret())


@register_engine("mis2", "dense_batched",
                 doc="vmapped dense fixed point over padded size buckets "
                     "(repro.batch); a single-graph call runs as a batch "
                     "of one — bit-identical to 'dense'")
def _mis2_dense_batched(graph, active, options, backend: Backend):
    from ..batch.container import GraphBatch

    actives = None if active is None else [active]
    return _mis2_batch_impl(GraphBatch([graph]), _opts(options), actives)[0]


@register_engine("mis2", "distributed",
                 doc="shard_map vertex partition over Backend(mesh=..., "
                     "axis=...): T and M all-gathered per iteration "
                     "(2·V·4 B collective traffic) — bit-identical to "
                     "'dense' for any device count")
def _mis2_distributed(graph, active, options, backend: Backend):
    from ..core.dist import _mis2_distributed_impl

    mesh, axis = backend.resolve_mesh()
    return _mis2_distributed_impl(graph, active, _opts(options),
                                  mesh=mesh, axis=axis, single_gather=False)


@register_engine("mis2", "distributed_single_gather",
                 doc="distributed variant gathering T once per iteration "
                     "and recomputing M locally (V·4 B collective traffic "
                     "— half of 'distributed'; replicates the ELL "
                     "adjacency)")
def _mis2_distributed_single_gather(graph, active, options, backend: Backend):
    from ..core.dist import _mis2_distributed_impl

    mesh, axis = backend.resolve_mesh()
    return _mis2_distributed_impl(graph, active, _opts(options),
                                  mesh=mesh, axis=axis, single_gather=True)


# -- aggregation (coarsening) ----------------------------------------------

@register_engine("aggregation", "basic", aliases=("mis2_basic",),
                 doc="paper Alg. 2 (Bell-style): MIS-2 roots + neighbors")
def _agg_basic(graph, options=None, mis2_engine=None, interpret=None,
               min_secondary_neighbors=2, backend=None):
    mis2_engine = mis2_engine or default_mis2_engine(backend, options, graph)
    return _aggregate_basic_impl(graph, _opts(options), mis2_engine,
                                 interpret=interpret,
                                 **_dist_mesh_kw(mis2_engine, backend))


@register_engine("aggregation", "two_phase", aliases=("mis2_agg",),
                 doc="paper Alg. 3 (ML-style): two MIS-2 phases + "
                     "max-coupling cleanup")
def _agg_two_phase(graph, options=None, mis2_engine=None,
                   interpret=None, min_secondary_neighbors=2, backend=None):
    mis2_engine = mis2_engine or default_mis2_engine(backend, options, graph)
    return _aggregate_two_phase_impl(graph, _opts(options), mis2_engine,
                                     min_secondary_neighbors,
                                     interpret=interpret,
                                     **_dist_mesh_kw(mis2_engine, backend))


@register_engine("aggregation", "serial",
                 doc="host-sequential greedy reference (Table V 'Serial Agg')")
def _agg_serial(graph, options=None, mis2_engine=None, interpret=None,
                min_secondary_neighbors=2, backend=None):
    return _aggregate_serial_greedy_impl(graph)


@register_engine("aggregation", "two_phase_distributed",
                 doc="paper Alg. 3 sharded over Backend(mesh=...): both "
                     "MIS-2 phases run the distributed fixed point and "
                     "each label-propagation round is one label "
                     "all-gather + local rowwise join — labels "
                     "bit-identical to 'two_phase'")
def _agg_two_phase_distributed(graph, options=None,
                               mis2_engine=None, interpret=None,
                               min_secondary_neighbors=2, backend=None):
    from ..core.aggregation import _aggregate_two_phase_distributed_impl
    from .backend import get_default_backend

    # None = this method's default fixed point; every explicit value must
    # name one of the two distributed engines (a deliberate 'compacted'
    # here is as wrong as 'pallas' and raises rather than being absorbed).
    if mis2_engine in (None, "distributed"):
        single_gather = False
    elif mis2_engine == "distributed_single_gather":
        single_gather = True
    else:
        raise ValueError(
            f"two_phase_distributed runs a distributed MIS-2; got "
            f"mis2_engine={mis2_engine!r} (use 'distributed' | "
            "'distributed_single_gather')")
    be = backend if backend is not None else get_default_backend()
    mesh, axis = be.resolve_mesh()
    return _aggregate_two_phase_distributed_impl(
        graph, _opts(options), min_secondary_neighbors, mesh=mesh, axis=axis,
        single_gather=single_gather)


# -- misk (distance-k MIS) --------------------------------------------------

@register_engine("misk", "dense",
                 doc="single jitted lax.while_loop over masked [V] state "
                     "(k-fold min-propagation)")
def _misk_dense(graph, k, priority, max_iters, backend: Backend):
    from ..core.misk import _mis_k_impl

    return _mis_k_impl(graph, k, priority, max_iters)


@register_engine("misk", "resident",
                 doc="§V-B worklist shape for distance-k: on-device "
                     "compacted worklist feeds the row refresh inside "
                     "the single jitted while_loop — bit-identical to "
                     "'dense' (which is already one dispatch per solve "
                     "and stays the default); kept for ablation")
def _misk_resident(graph, k, priority, max_iters, backend: Backend):
    from ..core.misk import _misk_resident_impl

    return _misk_resident_impl(graph, k, priority, max_iters)


# -- multilevel setup (AMG hierarchy / cluster-GS packing) ------------------

@register_engine("multilevel", "host",
                 doc="legacy host orchestration: scipy smoothed "
                     "prolongator, canonical sorted-COO Galerkin on "
                     "numpy, numpy cluster packing — ~3 matrix-sized "
                     "host round-trips per level (SETUP_STATS.host_syncs)")
def _multilevel_host(kind, graph, **kwargs):
    from ..multilevel.hierarchy import (
        _build_hierarchy_impl,
        _cluster_gs_setup_impl,
    )

    fn = _build_hierarchy_impl if kind == "amg" else _cluster_gs_setup_impl
    return fn(graph, engine="host", **kwargs)


@register_engine("multilevel", "resident",
                 doc="whole per-level setup jitted on device (x64): "
                     "fixed-shape prolongator assembly, padded sorted-COO "
                     "SpGEMM Galerkin, coarse ELL repack, cluster/color "
                     "packing — 7 dispatches per level, zero matrix-sized "
                     "host syncs, digest-identical to 'host'; the facade "
                     "default on accelerators")
def _multilevel_resident(kind, graph, **kwargs):
    from ..multilevel.hierarchy import (
        _build_hierarchy_impl,
        _cluster_gs_setup_impl,
    )

    fn = _build_hierarchy_impl if kind == "amg" else _cluster_gs_setup_impl
    return fn(graph, engine="resident", **kwargs)


# -- coloring ---------------------------------------------------------------

@register_engine("coloring", "luby",
                 doc="Luby-style rounds with xorshift* packed priorities")
def _color_luby(graph, max_rounds, backend: Backend):
    return _color_graph_impl(graph, max_rounds)


@register_engine("coloring", "luby_hybrid",
                 doc="Luby rounds over the degree-aware hybrid layout "
                     "(sliced-ELL + COO spill); bit-identical colors "
                     "without the monolithic padded ELL")
def _color_luby_hybrid(graph, max_rounds, backend: Backend):
    from ..core.coloring import _color_hybrid_impl

    return _color_hybrid_impl(graph, max_rounds)


# -- partition --------------------------------------------------------------

@register_engine("partition", "multilevel",
                 doc="MIS-2 multilevel coarsen + greedy coarse split + "
                     "boundary refinement per level")
def _partition_multilevel(graph, num_parts, coarse_target, options,
                          backend: Backend):
    return _partition_impl(graph, num_parts, coarse_target, _opts(options),
                           interpret=backend.resolve_interpret())
