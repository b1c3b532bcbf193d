"""The ``Graph`` handle: one object, every structural format, computed once.

Every pipeline in this repo (MIS-2, MIS-k, coloring, aggregation,
partitioning, AMG, cluster-GS) consumes the same graph in one of a few
layouts: CSR for host-side structure walks and segment reductions, ELL for
lane-aligned device gathers, COO edge lists for ``csr_segment`` kernels,
degree-bucketed ELL for skewed graphs.  Before the facade existed each
entry point re-derived its layout per call (``csr_to_ell_graph`` on every
``mis2``); the handle makes conversion a cached, observable, setup-time
event — the paper's setup/solve split, enforced by the API.

The handle is the canonical argument type of ``repro.api``; all legacy
entry points also accept it (they coerce through :func:`as_graph`, so a
bare ``CSRGraph`` still works and simply gets a fresh, uncached handle).

Conversion counting: ``graph.conversions`` maps conversion name ->
number of times the *work* was actually performed.  Tests assert a second
``.ell`` access is a cache hit (count stays 1).  Each conversion is also
timed (``graph.conversion_timings``) and mirrored into the process-wide
``repro.obs`` registry as ``graph.conversions{kind=...}`` /
``graph.conversion_seconds{kind=...}`` so one ``obs.snapshot()`` sees
format churn next to dispatches and compiles.  Each conversion runs in a
``graph.<kind>`` span; the host-to-device copy at its end is the child
span ``graph.to_device``.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any, Iterable

import jax
import numpy as np

from ..obs import metrics as _OBS
from ..obs import span as _obs_span

from .csr import (
    BucketedELL,
    CSRGraph,
    CSRMatrix,
    ELLGraph,
    ELLMatrix,
    csr_to_bucketed_ell,
    csr_to_ell_graph,
    csr_to_ell_matrix,
    ell_to_csr_graph,
    pad_ell_graph,
)
from . import hybrid as _hybrid
from .hybrid import HybridEllGraph, LayoutOverflowError, csr_to_hybrid_ell

_STRUCTS = (CSRGraph, CSRMatrix, ELLGraph, ELLMatrix)


class Graph:
    """Cached-format handle around one immutable graph (or square matrix).

    Construct from any structural container::

        g = Graph(laplace3d(32))          # CSRMatrix (keeps values)
        g = Graph(csr_graph)              # CSRGraph
        g = Graph.from_coo(rows, cols, n) # COO triples

    Formats are materialized lazily and cached: ``g.ell``, ``g.csr``,
    ``g.csr_matrix``, ``g.ell_matrix``, ``g.csr_edges``, ``g.bucketed()``.
    """

    def __init__(self, structure):
        if isinstance(structure, Graph):
            # share the cache: a handle of a handle is the same handle state
            self._cache = structure._cache
            self._counts = structure._counts
            self._timings = structure._timings
            return
        if not isinstance(structure, _STRUCTS):
            raise TypeError(
                f"Graph() expects CSRGraph/CSRMatrix/ELLGraph/ELLMatrix/Graph, "
                f"got {type(structure).__name__}"
            )
        self._cache: dict[str, Any] = {}
        self._counts: dict[str, int] = {}
        self._timings: dict[str, float] = {}
        if isinstance(structure, CSRGraph):
            self._cache["csr"] = structure
        elif isinstance(structure, CSRMatrix):
            self._cache["csr_matrix"] = structure
            self._cache["csr"] = structure.graph
        elif isinstance(structure, ELLGraph):
            self._cache["ell"] = structure
        else:  # ELLMatrix
            self._cache["ell_matrix"] = structure
            self._cache["ell"] = structure.graph

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_coo(cls, rows, cols, num_vertices: int, vals=None) -> "Graph":
        from .csr import csr_from_coo

        return cls(csr_from_coo(np.asarray(rows), np.asarray(cols),
                                num_vertices, vals))

    # -- cache plumbing -----------------------------------------------------

    @contextmanager
    def _convert(self, name: str):
        """Count + time one conversion's actual work, inside a
        ``graph.<name>`` span, and mirror it into the ``repro.obs``
        registry.  Callers hoist prerequisite format accesses (e.g.
        ``self.csr``) *before* entering, so nested conversions are
        attributed to their own kind rather than the outermost one."""
        t0 = time.perf_counter()
        try:
            with _obs_span(f"graph.{name}"):
                yield
                if self._cache.get("device") is not None:
                    # a placed handle keeps every later format on its device
                    self.place(self._cache["device"])
        finally:
            dt = time.perf_counter() - t0
            self._counts[name] = self._counts.get(name, 0) + 1
            self._timings[name] = self._timings.get(name, 0.0) + dt
            _OBS.counter("graph.conversions", labels={"kind": name}).inc()
            _OBS.histogram("graph.conversion_seconds",
                           labels={"kind": name}).observe(dt)

    @property
    def conversions(self) -> dict[str, int]:
        """Times each conversion's work actually ran (cache hits excluded)."""
        return dict(self._counts)

    @property
    def conversion_timings(self) -> dict[str, float]:
        """Cumulative seconds spent per conversion kind (this handle)."""
        return dict(self._timings)

    # -- structural formats -------------------------------------------------

    @property
    def has_values(self) -> bool:
        return "csr_matrix" in self._cache or "ell_matrix" in self._cache

    @property
    def csr(self) -> CSRGraph:
        if "csr" not in self._cache:
            with self._convert("ell_to_csr"):
                self._cache["csr"] = ell_to_csr_graph(self._cache["ell"])
        return self._cache["csr"]

    @property
    def ell(self) -> ELLGraph:
        if "ell" not in self._cache:
            csr = self.csr
            self._check_ell_budget(self.num_vertices, self.max_degree)
            with self._convert("csr_to_ell"):
                self._cache["ell"] = csr_to_ell_graph(csr)
        return self._cache["ell"]

    @property
    def csr_matrix(self) -> CSRMatrix:
        if "csr_matrix" not in self._cache:
            raise ValueError("this Graph carries structure only (no values)")
        return self._cache["csr_matrix"]

    @property
    def ell_matrix(self) -> ELLMatrix:
        if "ell_matrix" not in self._cache:
            csr_matrix = self.csr_matrix
            with self._convert("csr_to_ell_matrix"):
                self._cache["ell_matrix"] = csr_to_ell_matrix(csr_matrix)
        return self._cache["ell_matrix"]

    @property
    def csr_edges(self):
        """COO edge list ``(edge_rows, edge_cols)`` as device int32 arrays —
        the ``csr_segment`` layout consumed by segment-reduction kernels."""
        if "csr_edges" not in self._cache:
            csr = self.csr
            with self._convert("csr_edges"):
                import jax.numpy as jnp

                indptr = np.asarray(csr.indptr)
                indices = np.asarray(csr.indices)
                rows = np.repeat(np.arange(len(indptr) - 1, dtype=np.int32),
                                 np.diff(indptr))
                self._cache["csr_edges"] = (
                    jnp.asarray(rows),
                    jnp.asarray(indices.astype(np.int32)))
        return self._cache["csr_edges"]

    def padded_ell(self, num_rows: int, width: int) -> ELLGraph:
        """ELL padded to ``[num_rows, width]`` (self-loop slots, mask False),
        cached per target shape — repeated batched dispatches of the same
        graph into the same bucket shape reuse one padded copy."""
        key = f"padded_ell({num_rows},{width})"
        if key not in self._cache:
            self._check_ell_budget(num_rows, width)
            ell = self.ell
            with self._convert("pad_ell"):
                self._cache[key] = pad_ell_graph(ell, num_rows, width)
        return self._cache[key]

    # -- degree-aware layouts ------------------------------------------------

    @staticmethod
    def _check_ell_budget(num_rows: int, width: int) -> None:
        """Refuse a padded-ELL materialization whose bytes estimate exceeds
        ``repro.graphs.hybrid.ELL_BYTE_LIMIT`` *before* allocating anything
        (read at call time so tests and operators can tune the limit)."""
        est = _hybrid.ell_bytes_estimate(num_rows, width)
        limit = _hybrid.ELL_BYTE_LIMIT
        if est > limit:
            raise LayoutOverflowError(est, limit, num_rows, width)

    def ell_bytes_estimate(self) -> int:
        """Bytes the monolithic padded-ELL form would take — O(V) degree
        scan, no adjacency materialization.  This is what auto-selection
        (``engine=None``) and serve admission consult before committing to
        an ELL-bound engine."""
        return _hybrid.ell_bytes_estimate(self.num_vertices, self.max_degree)

    def hybrid(self, widths=None, spill_cap=None) -> HybridEllGraph:
        """Sliced-ELL + COO-spill layout (see ``graphs.hybrid``), cached per
        (widths, spill_cap) policy."""
        key = f"hybrid({widths},{spill_cap})"
        if key not in self._cache:
            csr = self.csr
            with self._convert("csr_to_hybrid"):
                self._cache[key] = csr_to_hybrid_ell(
                    csr, widths=widths, spill_cap=spill_cap)
        return self._cache[key]

    def bucketed(self, boundaries: Iterable[int] = (8, 32, 128)) -> BucketedELL:
        key = f"bucketed{tuple(boundaries)}"
        if key not in self._cache:
            csr = self.csr
            with self._convert("csr_to_bucketed_ell"):
                self._cache[key] = csr_to_bucketed_ell(csr, tuple(boundaries))
        return self._cache[key]

    @property
    def digest(self) -> str:
        """Canonical-format content digest (16 hex chars), cached.

        Hashes the CSR structure (indptr + indices bytes, shapes, dtypes)
        plus the values when the handle carries a matrix.  Because CSR
        construction is deterministic (sorted, deduplicated), two handles
        built from the same structure always share a digest — this is the
        key ingredient of the serving layer's digest-keyed result cache:
        equal graph digest + equal options means the cached result is
        *provably* the bytes a recomputation would produce (the repo-wide
        engine bit-identity invariant)."""
        if "digest" not in self._cache:
            csr = self.csr
            with self._convert("digest"):
                import hashlib

                h = hashlib.sha256()
                for arr in (csr.indptr, csr.indices):
                    a = np.asarray(arr)
                    h.update(str(a.dtype).encode())
                    h.update(str(a.shape).encode())
                    h.update(a.tobytes())
                if self.has_values:
                    a = np.asarray(self.csr_matrix.values)
                    h.update(str(a.dtype).encode())
                    h.update(str(a.shape).encode())
                    h.update(a.tobytes())
                self._cache["digest"] = h.hexdigest()[:16]
        return self._cache["digest"]

    # -- stats --------------------------------------------------------------

    @property
    def num_vertices(self) -> int:
        if "ell" in self._cache:
            return self._cache["ell"].num_vertices
        return self.csr.num_vertices

    @property
    def num_entries(self) -> int:
        if "csr" not in self._cache:   # ELL-seeded: count mask, don't convert
            return int(np.asarray(self._cache["ell"].mask).sum())
        return self.csr.num_entries

    @property
    def degrees(self) -> np.ndarray:
        if "degrees" not in self._cache:
            csr = self.csr
            with self._convert("degrees"):
                self._cache["degrees"] = np.diff(np.asarray(csr.indptr))
        return self._cache["degrees"]

    @property
    def max_degree(self) -> int:
        d = self.degrees
        return int(d.max()) if len(d) else 0

    def stats(self) -> dict:
        d = self.degrees
        return {
            "num_vertices": self.num_vertices,
            "num_entries": self.num_entries,
            "max_degree": self.max_degree,
            "avg_degree": float(d.mean()) if len(d) else 0.0,
            "has_values": self.has_values,
            "cached_formats": sorted(self._cache.keys()),
        }

    # -- device placement ---------------------------------------------------

    def place(self, device) -> "Graph":
        """Move every cached device array to ``device`` (in place; the
        handle's cache is shared, so all views see the placement)."""
        for key, val in list(self._cache.items()):
            if key in ("degrees", "device", "digest"):   # host-only entries
                continue
            if isinstance(val, HybridEllGraph):
                # keep the static int metadata out of device_put's pytree
                self._cache[key] = val._replace(
                    slices=jax.device_put(val.slices, device),
                    spill_rows=jax.device_put(val.spill_rows, device),
                    spill_seg=jax.device_put(val.spill_seg, device),
                    spill_cols=jax.device_put(val.spill_cols, device))
                continue
            self._cache[key] = jax.device_put(val, device)
        self._cache["device"] = device
        return self

    def __repr__(self) -> str:
        fmts = ",".join(sorted(k for k in self._cache if k != "device"))
        return (f"Graph(V={self.num_vertices}, E={self.num_entries}, "
                f"cached=[{fmts}])")


# ---------------------------------------------------------------------------
# coercion helpers — every pipeline entry point funnels through these, so
# passing a Graph handle reuses its cache and passing a bare container
# behaves exactly as before (fresh conversion).
# ---------------------------------------------------------------------------

def as_graph(obj) -> Graph:
    """Coerce any structural container (or handle) to a Graph handle."""
    return obj if isinstance(obj, Graph) else Graph(obj)


def as_ell_graph(obj) -> ELLGraph:
    if isinstance(obj, ELLGraph):
        return obj
    return as_graph(obj).ell


def as_csr_graph(obj) -> CSRGraph:
    if isinstance(obj, CSRGraph):
        return obj
    return as_graph(obj).csr
