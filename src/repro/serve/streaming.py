"""Streaming MIS-2: apply edge deltas, repair locally, stay bit-exact.

``StreamSession.apply_delta(edge_adds, edge_removes)`` updates a live
MIS-2 solution without recomputing from scratch: only the closed 2-hop
neighborhood of the touched endpoints is reactivated (re-seeded
undecided), everything else keeps its previous T state (``IN``/``OUT``
frozen), and the warm-started fixed point
(:func:`repro.core.mis2.mis2_repair_fixed_point`) re-decides the region.

Exactness — why the repaired set is bit-identical to from-scratch:

* With the round-independent ``"fixed"`` priority, the MIS-2 fixed point
  computes the unique *lexicographically-first* MIS-2 under the packed
  priority order ``p``; that set is characterized pointwise by the
  recurrence "``v IN`` iff no member within distance 2 has smaller
  ``p``" (unique by induction along the priority order — the repo's
  port of Blelloch–Fineman–Shun's deterministic-reservation argument).
* After each repair solve, :func:`repro.core.mis2.lexfirst_violations`
  checks that recurrence *globally* with two closed-neighborhood min
  propagations.  Any violation necessarily implicates a frozen vertex
  within distance 2 (inside the reactivated region the fixed point is
  already consistent), so the violators' closed 2-hop is reactivated and
  the solve repeats; the region grows monotonically, hence terminates —
  in practice one or two expansions.  An all-clear certifies the
  assignment satisfies the recurrence everywhere, and the unique such
  assignment *is* the from-scratch answer.

With a round-varying priority (the ``xorshift_star`` default elsewhere)
the fixed point is history-dependent and no warm start can be exact, so
``apply_delta`` falls back to a full recompute (``mode="recompute"``) —
the documented streaming caveat.  ``check_fraction`` additionally
digest-checks sampled deltas against an actual from-scratch run
(belt-and-braces on top of the recurrence certificate).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..core.mis2 import (
    Mis2Options,
    fixed_packed_priorities,
    lexfirst_violations,
    mis2_dense_jittable,
    mis2_repair_fixed_point,
)
from ..core.tuples import IN, OUT, id_bits, is_undecided
from ..graphs.csr import CSRGraph, csr_from_coo, ensure_self_loops
from ..graphs.handle import Graph, as_graph
from ..api.backend import backend_platform
from ..api.result import Mis2Result
from ..obs import Provenance
from ..obs import metrics as _OBS
from ..obs import span as _obs_span
from .faults import InjectedFault


@dataclass
class RepairStats:
    """Observability for one ``apply_delta`` call."""

    mode: str                   # "repair" | "recompute"
    touched: int = 0            # endpoints named by the delta
    reactivated: int = 0        # vertices re-seeded undecided (final region)
    expansions: int = 0         # recurrence-check driven region growths
    iterations: int = 0         # fixed-point rounds across all solves
    checked: bool = False       # from-scratch digest check ran
    degraded: bool = False      # repair path failed; served via recompute
    wall_time_s: float = 0.0


def _two_hop(mask: np.ndarray, rows: np.ndarray, cols: np.ndarray,
             hops: int = 2) -> np.ndarray:
    """Closed ``hops``-neighborhood of ``mask`` over COO edges (host)."""
    reach = mask.copy()
    for _ in range(hops):
        nxt = reach.copy()
        np.logical_or.at(nxt, rows, reach[cols])
        reach = nxt
    return reach


def _edge_keys(pairs, num_vertices: int) -> np.ndarray:
    """Symmetric (u, v) pairs -> sorted unique int64 ``u * V + v`` keys."""
    if pairs is None or len(pairs) == 0:
        return np.empty(0, dtype=np.int64)
    arr = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    if (arr < 0).any() or (arr >= num_vertices).any():
        raise ValueError("delta edge endpoint out of range")
    keys = np.concatenate([arr[:, 0] * num_vertices + arr[:, 1],
                           arr[:, 1] * num_vertices + arr[:, 0]])
    return np.unique(keys)


class StreamSession:
    """A live MIS-2 solution over a mutating graph (fixed vertex set).

    ``options.priority == "fixed"`` (the default here) enables exact
    incremental repair; any other priority downgrades ``apply_delta`` to
    full recomputation.  ``check_fraction`` in ``[0, 1]`` recomputes that
    fraction of deltas from scratch and asserts digest equality
    (deterministic error-diffusion sampling, like the result cache).

    ``faults`` (a :class:`~repro.serve.faults.FaultPlan` or None)
    consults the ``repair`` site: an injected repair failure degrades the
    delta to a from-scratch recompute (``last_repair.degraded``, counted
    ``serve.fallbacks{from=repair,to=recompute}``) — the session stays
    live and bit-exact, it just pays full price for that delta.
    """

    def __init__(self, graph, *, options: Optional[Mis2Options] = None,
                 check_fraction: float = 0.0, faults=None):
        self.options = options if options is not None else \
            Mis2Options(priority="fixed")
        self.faults = faults
        self.check_fraction = float(check_fraction)
        self._check_acc = 0.0
        gh = as_graph(graph)
        csr = ensure_self_loops(gh.csr)
        self._v = csr.num_vertices
        indptr = np.asarray(csr.indptr)
        self._cols = np.asarray(csr.indices).astype(np.int64)
        self._rows = np.repeat(np.arange(self._v, dtype=np.int64),
                               np.diff(indptr))
        self.graph = Graph(CSRGraph(csr.indptr, csr.indices))
        self._p = None
        if self.options.priority == "fixed":
            self._p = fixed_packed_priorities(self._v)
        self.result = self._solve_scratch(self.graph)
        self.in_set = np.asarray(self.result.payload)
        self.last_repair: Optional[RepairStats] = None

    # -- internals ----------------------------------------------------------

    def _solve_scratch(self, gh: Graph) -> Mis2Result:
        t0 = time.perf_counter()
        t, iters = mis2_dense_jittable(
            gh.ell.neighbors, None, self.options.priority, self.options.max_iters)
        t_np = np.asarray(t)
        if is_undecided(t_np).any():
            raise RuntimeError("MIS-2 fixed point hit max_iters during "
                               "streaming solve; raise Mis2Options.max_iters")
        return Mis2Result(t_np == np.uint32(IN), int(iters), True,
                          time.perf_counter() - t0, engine="dense")

    def _recompute_full(self, gh: Graph, touched: np.ndarray,
                        t_start: float, degraded: bool = False) -> Mis2Result:
        """Serve one delta by full recomputation (the round-varying-
        priority path and the degraded fallback when incremental repair
        faults)."""
        self.result = self._solve_scratch(gh)
        self.in_set = np.asarray(self.result.payload)
        self.graph = gh
        self.last_repair = RepairStats(
            mode="recompute", touched=int(touched.sum()),
            reactivated=self._v,
            iterations=self.result.iterations,
            degraded=degraded,
            wall_time_s=time.perf_counter() - t_start)
        return self.result

    def _apply_keys(self, adds: np.ndarray, removes: np.ndarray) -> Graph:
        cur = self._rows * self._v + self._cols
        new = np.union1d(cur, adds)
        if len(removes):
            new = np.setdiff1d(new, removes, assume_unique=False)
        diag = np.arange(self._v, dtype=np.int64) * (self._v + 1)
        new = np.union1d(new, diag)     # self-loops are structural here
        rows, cols = new // self._v, new % self._v
        csr = csr_from_coo(rows, cols, self._v)
        self._rows, self._cols = rows, cols
        return Graph(csr)

    # -- public -------------------------------------------------------------

    def apply_delta(self, edge_adds=None, edge_removes=None) -> Mis2Result:
        """Apply symmetric edge insertions/removals and repair the set.

        Returns the updated facade ``Mis2Result`` (also stored as
        ``self.result``); per-call accounting lands in ``self.last_repair``
        and mirrors into the ``repro.obs`` registry (``serve.repair.*``);
        the result carries a span-tree ``provenance`` like facade results.
        Self-loops cannot be removed (closed-neighborhood semantics) and
        the vertex set is fixed — grow-by-vertex is a resize, not a delta.
        """
        with _obs_span("serve.repair") as sp:
            result = self._apply_delta_impl(edge_adds, edge_removes)
            st = self.last_repair
            sp.annotate(mode=st.mode, touched=st.touched,
                        reactivated=st.reactivated, expansions=st.expansions)
            _OBS.counter("serve.repair.deltas",
                         labels={"mode": st.mode}).inc()
            _OBS.counter("serve.repair.reactivated").inc(st.reactivated)
            _OBS.counter("serve.repair.expansions").inc(st.expansions)
            _OBS.counter("serve.repair.iterations").inc(st.iterations)
        result.provenance = Provenance(
            "mis2", result.engine, backend_platform(), result.digest,
            sp.to_dict())
        return result

    def _apply_delta_impl(self, edge_adds=None, edge_removes=None):
        t_start = time.perf_counter()
        adds = _edge_keys(edge_adds, self._v)
        removes = _edge_keys(edge_removes, self._v)
        old_rows, old_cols = self._rows, self._cols
        gh = self._apply_keys(adds, removes)

        touched_keys = np.concatenate([adds, removes])
        touched = np.zeros(self._v, dtype=bool)
        touched[np.unique(touched_keys // self._v)] = True
        touched[np.unique(touched_keys % self._v)] = True

        if self._p is None:     # round-varying priority: repair is inexact
            return self._recompute_full(gh, touched, t_start)

        try:
            if self.faults is not None:
                self.faults.fire("repair")
            # reactivate the closed 2-hop of touched endpoints, under the
            # union of old and new adjacency (a removed edge still
            # mediated influence)
            u_rows = np.concatenate([old_rows, self._rows])
            u_cols = np.concatenate([old_cols, self._cols])
            region = _two_hop(touched, u_rows, u_cols)

            neighbors = gh.ell.neighbors
            b = jnp.uint32(id_bits(self._v))
            prev_in = self.in_set
            stats = RepairStats(mode="repair", touched=int(touched.sum()))
            while True:
                t0 = jnp.asarray(np.where(
                    region, np.uint32(1), np.where(prev_in, IN, OUT)))
                t, iters = mis2_repair_fixed_point(
                    neighbors, t0, b, self.options.priority,
                    self.options.max_iters)
                stats.iterations += int(iters)
                t_np = np.asarray(t)
                if is_undecided(t_np).any():
                    raise RuntimeError(
                        "repair fixed point hit max_iters; raise "
                        "Mis2Options.max_iters")
                in_set = t_np == np.uint32(IN)
                viol = np.asarray(lexfirst_violations(neighbors, jnp.asarray(
                    in_set), self._p))
                if not viol.any():
                    break
                # violations implicate frozen vertices within distance 2:
                # reactivate their closed 2-hop and re-solve (region grows)
                region = region | _two_hop(viol, self._rows, self._cols)
                stats.expansions += 1
                if stats.expansions > self._v:      # unreachable; safety net
                    raise RuntimeError("repair failed to converge")
        except InjectedFault:
            # degraded but live: the delta is served via full recompute,
            # which is exact by construction — the session never emits a
            # wrong set, it just pays full price for this delta
            _OBS.counter("serve.fallbacks",
                         labels={"from": "repair", "to": "recompute"}).inc()
            return self._recompute_full(gh, touched, t_start, degraded=True)
        stats.reactivated = int(region.sum())

        result = Mis2Result(in_set, stats.iterations, True,
                            time.perf_counter() - t_start,
                            engine="stream_repair")
        if self.check_fraction > 0.0:
            self._check_acc += min(1.0, self.check_fraction)
            if self._check_acc >= 1.0:
                self._check_acc -= 1.0
                stats.checked = True
                scratch = self._solve_scratch(gh)
                if scratch.digest != result.digest:
                    raise AssertionError(
                        f"incremental repair diverged from from-scratch: "
                        f"{result.digest} != {scratch.digest}")
        self.graph = gh
        self.in_set = in_set
        self.result = result
        stats.wall_time_s = time.perf_counter() - t_start
        self.last_repair = stats
        return result
