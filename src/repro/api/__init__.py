"""``repro.api`` — the single public surface of the reproduction.

One algorithm, many backends, bit-identical results (the paper's
portability claim) expressed as three orthogonal concepts:

* :class:`Graph`   — cached-format handle (CSR/ELL/COO/bucketed computed
  lazily, exactly once, shared across every pipeline call);
* :class:`Backend` — execution policy (Pallas kernels on/off, interpret
  mode auto-derived from the attached accelerator, device placement),
  threaded down to ``kernels/*/ops.py``;
* engine registry  — ``(pipeline, engine-name)`` dispatch replacing the
  seed's ad-hoc per-engine entry points; all engines of a pipeline return
  the common :class:`Result` protocol (host-numpy payload + iterations +
  convergence + wall time + determinism digest).

Legacy entry points (``repro.core.mis2.mis2``, ``solvers.amg.AGGREGATORS``,
``Mis2Options(use_pallas=...)``, ...) still work but emit
``DeprecationWarning``; the old->new table is in API.md.
"""
from .backend import (
    Backend,
    InexactFloat64Error,
    accelerator_present,
    default_interpret,
    get_default_backend,
    set_default_backend,
    using_backend,
)
from .graph import Graph, as_csr_graph, as_ell_graph, as_graph
from ..batch.container import GraphBatch, as_graph_batch
from .registry import get_engine, get_engine_spec, list_engines, register_engine
from .result import (
    AggregationResult,
    AmgSetup,
    BatchResult,
    ClusterGsSetup,
    ColoringResult,
    Mis2Result,
    PartitionResult,
    Result,
    ResultLike,
    determinism_digest,
)
from . import engines as _engines  # noqa: F401  (registers built-in engines)
from .facade import (
    amg,
    amg_setup,
    amg_setup_batch,
    cluster_gs_setup,
    coarsen,
    coarsen_batch,
    color,
    color_batch,
    mis2,
    mis2_batch,
    misk,
    partition,
)
from ..core.mis2 import ABLATION_CHAIN, Mis2Options
from . import generators  # noqa: F401  (problem generators, re-exported)

__all__ = [
    # facade calls
    "mis2", "misk", "color", "coarsen", "partition", "amg",
    # multilevel setup (repro.multilevel engines)
    "amg_setup", "cluster_gs_setup",
    # batched facade calls (repro.batch)
    "mis2_batch", "color_batch", "coarsen_batch", "amg_setup_batch",
    "GraphBatch", "as_graph_batch", "BatchResult",
    # graph handle
    "Graph", "as_graph", "as_ell_graph", "as_csr_graph",
    # backend policy
    "Backend", "InexactFloat64Error", "accelerator_present",
    "default_interpret",
    "get_default_backend", "set_default_backend", "using_backend",
    # engine registry
    "register_engine", "get_engine", "get_engine_spec", "list_engines",
    # problem generators (repro.api.generators.laplace3d, ...)
    "generators",
    # options / results
    "Mis2Options", "ABLATION_CHAIN",
    "Result", "ResultLike", "Mis2Result", "ColoringResult",
    "AggregationResult", "PartitionResult", "AmgSetup", "ClusterGsSetup",
    "determinism_digest",
]
