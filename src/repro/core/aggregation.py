"""MIS-2 based graph coarsening (paper Algorithms 2 and 3).

* ``aggregate_basic``   — Algorithm 2 (Bell-style): MIS-2 roots + direct
  neighbors; leftovers join an adjacent aggregate (deterministically: the
  minimum adjacent label, standing in for the paper's "arbitrarily").
* ``aggregate_two_phase`` — Algorithm 3 (ML-style, the paper's contribution):
  phase 1 = MIS-2 roots + neighbors; phase 2 = second MIS-2 on the induced
  unaggregated subgraph, roots with >= 2 unaggregated neighbors form
  secondary aggregates; phase 3 = leftovers join the max-coupling adjacent
  aggregate (ties -> smaller aggregate -> smaller label), computed against
  frozen "tentative" labels for determinism.
* ``aggregate_serial_greedy`` — host-sequential reference (MueLu "Serial
  Agg" stand-in for Table V).

All device phases are vectorized over ELL adjacency.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops
from .._compat import warn_deprecated
from ..graphs.csr import ELLGraph
from ..graphs.handle import as_graph
from ..obs import span as _obs_span
from .mis2 import Mis2Options, run_mis2

INT32_MAX = np.int32(2**31 - 1)


@dataclass
class AggregationResult:
    labels: np.ndarray       # int32 [V] aggregate id (all >= 0 on success)
    num_aggregates: int
    roots: np.ndarray        # bool [V] (phase-1 + phase-2 roots)
    phase: np.ndarray        # uint8 [V]: phase that aggregated each vertex
    mis2_iterations: int     # total MIS-2 iterations spent
    converged: bool = True   # every underlying MIS-2 reached its fixed point

    def __post_init__(self):
        # Result-protocol guarantee: host numpy payloads on every engine.
        self.labels = np.asarray(self.labels)
        self.roots = np.asarray(self.roots)
        self.phase = np.asarray(self.phase)

    @property
    def coarsening_ratio(self) -> float:
        return len(self.labels) / max(1, self.num_aggregates)


# ---------------------------------------------------------------------------
# vectorized helpers
#
# Each helper is split into a rowwise body over an explicit row block
# (``*_rows``) plus the single-device full-graph wrapper: the distributed
# coarsening in ``core.dist`` runs the SAME rowwise body on shard_map row
# blocks (with gathered global label vectors), which is what makes the
# sharded labels bit-identical to the single-device engines.
# ---------------------------------------------------------------------------

def _join_rows(neighbors_rows: jnp.ndarray, root_label_global: jnp.ndarray):
    """Rowwise body of :func:`_join_adjacent_root` over a row block."""
    cand = xla_ops.gather_rows(root_label_global, neighbors_rows)   # [rows, D]
    lab = jnp.min(cand, axis=1)
    return jnp.where(lab == INT32_MAX, jnp.int32(-1), lab)


@jax.jit
def _join_adjacent_root(neighbors: jnp.ndarray, root_label: jnp.ndarray):
    """label[v] = root label of the (unique) adjacent root, else -1.

    ``root_label`` is int32 [V]: aggregate id for roots, INT32_MAX otherwise.
    A vertex adjacent to two distinct roots would contradict distance-2
    independence, so min() is exact, not a tie-break.
    """
    return _join_rows(neighbors, root_label)


def _count_unagg_rows(neighbors_rows, mask_rows, row_ids, labels_global):
    """Rowwise body of :func:`_count_unagg_neighbors` over a row block."""
    real = mask_rows & (neighbors_rows != row_ids[:, None])
    unagg = xla_ops.gather_rows(labels_global, neighbors_rows) < 0
    return jnp.sum(real & unagg, axis=1)


@jax.jit
def _count_unagg_neighbors(neighbors, mask, labels):
    """# real neighbors (excluding self) that are unaggregated."""
    v = neighbors.shape[0]
    row_ids = jnp.arange(v, dtype=neighbors.dtype)
    return _count_unagg_rows(neighbors, mask, row_ids, labels)


def _phase3_keys(labels_n, valid, aggsize):
    """Per-slot (coupling, aggsize) selection keys over slot-major
    ``[D, R]`` tiles: coupling = # valid slots of the row holding the
    same label.  The slot loop is a ``fori_loop`` so the program size
    does not grow with the ELL width (a hub row makes D large)."""
    def add_slot(k, coupling):
        lab_k = jax.lax.dynamic_index_in_dim(labels_n, k, keepdims=False)
        val_k = jax.lax.dynamic_index_in_dim(valid, k, keepdims=False)
        same = (labels_n == lab_k) & val_k & valid
        return coupling + same.astype(jnp.int32)

    coupling = jax.lax.fori_loop(0, labels_n.shape[0], add_slot,
                                 jnp.zeros(labels_n.shape, jnp.int32))
    size_n = aggsize[jnp.clip(labels_n, 0, aggsize.shape[0] - 1)]
    return coupling, size_n


def _phase3_rows(neighbors_rows, mask_rows, row_ids, labels_global,
                 labels_rows, aggsize):
    """Rowwise body of :func:`_phase3_join` over a row block (neighbor
    labels looked up in ``labels_global``, joins applied to
    ``labels_rows``).  The slots run down axis 0 (``[D, R]``) so each
    per-slot step is one lane-dense ``[R]`` vector on the TPU."""
    nbrs = neighbors_rows.T
    labels_n = labels_global[nbrs]                   # tentative labels
    valid = mask_rows.T & (nbrs != row_ids[None, :]) & (labels_n >= 0)
    coupling, size_n = _phase3_keys(labels_n, valid, aggsize)
    # per-slot keys of (-coupling, size, label); invalid slots last
    keys_c = jnp.where(valid, coupling, -1)
    keys_l = jnp.where(valid, labels_n, INT32_MAX)

    def pick(j, best):
        """Lexicographic argmin step over slot ``j`` (slot order kept)."""
        best_c, best_s, best_l = best
        cj, sj, lj = (jax.lax.dynamic_index_in_dim(x, j, keepdims=False)
                      for x in (keys_c, size_n, keys_l))
        better = (cj > best_c) | ((cj == best_c) & ((sj < best_s) |
                 ((sj == best_s) & (lj < best_l))))
        return (jnp.where(better, cj, best_c), jnp.where(better, sj, best_s),
                jnp.where(better, lj, best_l))

    best_c, _, best_l = jax.lax.fori_loop(
        1, nbrs.shape[0], pick, (keys_c[0], size_n[0], keys_l[0]))
    joined = (best_c > 0) & (best_l != INT32_MAX)
    return jnp.where((labels_rows < 0) & joined, best_l, labels_rows)


@jax.jit
def _phase3_join(neighbors, mask, labels, aggsize):
    """Leftovers join max-coupling adjacent aggregate (Alg 3 phase 3)."""
    v = neighbors.shape[0]
    row_ids = jnp.arange(v, dtype=neighbors.dtype)
    return _phase3_rows(neighbors, mask, row_ids, labels, labels, aggsize)


def _labels_from_roots(ell: ELLGraph, roots: np.ndarray):
    """Phase-1 style aggregate formation: roots + direct neighbors."""
    v = ell.num_vertices
    agg_ids = np.cumsum(roots) - 1
    root_label = np.where(roots, agg_ids, INT32_MAX).astype(np.int32)
    labels = np.asarray(_join_adjacent_root(ell.neighbors, jnp.asarray(root_label)))
    return labels, int(roots.sum())


# ---------------------------------------------------------------------------
# device-resident join loops (the hot-loop pattern of core.mis2's resident
# engines applied to the Alg. 2/3 label propagation): each multi-round
# host loop below used to sync ``labels`` device<->host every round — one
# jitted ``lax.while_loop`` replaces up to 4 round trips per phase while
# running the exact same rowwise arithmetic (labels stay bit-identical).
# ---------------------------------------------------------------------------

@jax.jit
def _cleanup_join_resident(neighbors, labels, phase):
    """Alg. 2 leftover cleanup: up to 4 min-adjacent-label join rounds,
    early exit once every vertex is labeled, phase marks applied on
    device."""
    def cond(state):
        labels, _, rounds = state
        return jnp.any(labels < 0) & (rounds < 4)

    def body(state):
        labels, phase, rounds = state
        lab_j = jnp.where(labels >= 0, labels, INT32_MAX).astype(jnp.int32)
        adj = _join_rows(neighbors, lab_j)
        newly = (labels < 0) & (adj >= 0)
        labels = jnp.where(newly, adj, labels)
        phase = jnp.where(newly, jnp.uint8(3), phase)
        return labels, phase, rounds + jnp.int32(1)

    labels, phase, _ = jax.lax.while_loop(
        cond, body, (labels, phase, jnp.int32(0)))
    return labels, phase


@functools.partial(jax.jit, static_argnames=("min_secondary",))
def _phase2_join_resident(neighbors, mask, labels, in_set2, nagg,
                          min_secondary: int):
    """Alg. 3 phase 2 on device: unaggregated-neighbor counting, secondary
    root selection, cumsum aggregate ids and the root join — one dispatch
    instead of three label round trips.  ``nagg`` is traced (no
    recompilation per aggregate count)."""
    v = neighbors.shape[0]
    row_ids = jnp.arange(v, dtype=neighbors.dtype)
    n_unagg = _count_unagg_rows(neighbors, mask, row_ids, labels)
    roots2 = in_set2 & (n_unagg >= min_secondary)
    agg_ids2 = nagg + xla_ops.cumsum(roots2.astype(jnp.int32)) - 1
    rl2 = jnp.where(roots2, agg_ids2, INT32_MAX).astype(jnp.int32)
    adj2 = _join_rows(neighbors, rl2)
    newly = (labels < 0) & (adj2 >= 0)
    labels = jnp.where(newly, adj2, labels)
    return labels, roots2, newly


@jax.jit
def _phase3_resident(neighbors, mask, labels, phase):
    """Alg. 3 phase 3 on device: up to 4 max-coupling join rounds against
    frozen tentative labels, aggregate sizes recomputed per round via a
    scatter-add histogram (slot ``v`` is the dump for unlabeled vertices,
    so entries ``0..nagg-1`` match ``np.bincount`` exactly; labels never
    reference the padding slots, making the join bit-identical to the
    host-driven rounds)."""
    v = neighbors.shape[0]
    row_ids = jnp.arange(v, dtype=neighbors.dtype)

    def cond(state):
        labels, _, rounds = state
        return jnp.any(labels < 0) & (rounds < 4)

    def body(state):
        labels, phase, rounds = state
        aggsize = jnp.zeros(v + 1, jnp.int32).at[
            jnp.where(labels >= 0, labels, v)].add(1)
        new_labels = _phase3_rows(neighbors, mask, row_ids, labels, labels,
                                  aggsize)
        newly = (labels < 0) & (new_labels >= 0)
        phase = jnp.where(newly, jnp.uint8(3), phase)
        return new_labels, phase, rounds + jnp.int32(1)

    labels, phase, _ = jax.lax.while_loop(
        cond, body, (labels, phase, jnp.int32(0)))
    return labels, phase


# ---------------------------------------------------------------------------
# hybrid-layout join loops (degree-aware sliced-ELL + COO spill)
#
# Twins of the resident loops above for graphs whose monolithic padded ELL
# is infeasible: each round runs the SAME rowwise bodies per slice slab
# (``row_ids = slice.rows``) plus a segment-reduce pass over the sorted-COO
# spill.  Within a round every read comes from the frozen round-start
# labels and writes accumulate into a fresh buffer — the slice/spill
# partition is disjoint and covering, so each round is exactly the
# monolithic round's gather/update evaluated piecewise (labels stay
# bit-identical to the ELL engines).
# ---------------------------------------------------------------------------

def _hybrid_join_labels(slices, spill_rows, spill_seg, spill_cols,
                        root_label):
    """Hybrid twin of :func:`_join_adjacent_root` (min label over the
    closed neighborhood; INT32_MAX -> -1).  The spill's explicit self min
    mirrors the ELL padding slots, which hold the row's own id."""
    v = root_label.shape[0]
    adj = jnp.full(v, -1, dtype=jnp.int32)
    for sl in slices:
        adj = adj.at[sl.rows].set(_join_rows(sl.neighbors, root_label))
    h = spill_rows.shape[0]
    if h > 0:
        mn = jax.ops.segment_min(root_label[spill_cols], spill_seg,
                                 num_segments=h)
        mn = jnp.minimum(mn, root_label[spill_rows])
        adj = adj.at[spill_rows].set(
            jnp.where(mn == INT32_MAX, jnp.int32(-1), mn))
    return adj


@jax.jit
def _hybrid_join_jit(slices, spill_rows, spill_seg, spill_cols, root_label):
    return _hybrid_join_labels(slices, spill_rows, spill_seg, spill_cols,
                               root_label)


def _labels_from_roots_hybrid(hyb, roots: np.ndarray):
    """Hybrid twin of :func:`_labels_from_roots` (same host cumsum)."""
    agg_ids = np.cumsum(roots) - 1
    root_label = np.where(roots, agg_ids, INT32_MAX).astype(np.int32)
    labels = np.asarray(_hybrid_join_jit(
        tuple(hyb.slices), hyb.spill_rows, hyb.spill_seg, hyb.spill_cols,
        jnp.asarray(root_label)))
    return labels, int(roots.sum())


@jax.jit
def _cleanup_join_resident_hybrid(slices, spill_rows, spill_seg, spill_cols,
                                  labels, phase):
    """Hybrid twin of :func:`_cleanup_join_resident`."""
    def cond(state):
        labels, _, rounds = state
        return jnp.any(labels < 0) & (rounds < 4)

    def body(state):
        labels, phase, rounds = state
        lab_j = jnp.where(labels >= 0, labels, INT32_MAX).astype(jnp.int32)
        adj = _hybrid_join_labels(slices, spill_rows, spill_seg, spill_cols,
                                  lab_j)
        newly = (labels < 0) & (adj >= 0)
        labels = jnp.where(newly, adj, labels)
        phase = jnp.where(newly, jnp.uint8(3), phase)
        return labels, phase, rounds + jnp.int32(1)

    labels, phase, _ = jax.lax.while_loop(
        cond, body, (labels, phase, jnp.int32(0)))
    return labels, phase


@functools.partial(jax.jit, static_argnames=("min_secondary",))
def _phase2_join_resident_hybrid(slices, spill_rows, spill_seg, spill_cols,
                                 labels, in_set2, nagg, min_secondary: int):
    """Hybrid twin of :func:`_phase2_join_resident`: the per-row
    unaggregated-neighbor count runs rowwise per slice and as a segment
    sum over the spill; root selection/cumsum/join are unchanged (they
    operate on global [V] vectors)."""
    v = labels.shape[0]
    n_unagg = jnp.zeros(v, dtype=jnp.int32)
    for sl in slices:
        n_unagg = n_unagg.at[sl.rows].set(
            _count_unagg_rows(sl.neighbors, sl.mask, sl.rows, labels))
    h = spill_rows.shape[0]
    if h > 0:
        real = spill_cols != spill_rows[spill_seg]
        unagg_e = labels[spill_cols] < 0
        n_sp = jax.ops.segment_sum((real & unagg_e).astype(jnp.int32),
                                   spill_seg, num_segments=h)
        n_unagg = n_unagg.at[spill_rows].set(n_sp)
    roots2 = in_set2 & (n_unagg >= min_secondary)
    agg_ids2 = nagg + xla_ops.cumsum(roots2.astype(jnp.int32)) - 1
    rl2 = jnp.where(roots2, agg_ids2, INT32_MAX).astype(jnp.int32)
    adj2 = _hybrid_join_labels(slices, spill_rows, spill_seg, spill_cols, rl2)
    newly = (labels < 0) & (adj2 >= 0)
    labels = jnp.where(newly, adj2, labels)
    return labels, roots2, newly


def _phase3_spill(spill_rows, spill_seg, spill_cols, labels, aggsize):
    """Phase-3 body over the sorted-COO spill: pick the max-coupling
    adjacent aggregate (ties -> smaller size -> smaller label).

    Coupling counts need a per-(row, label) histogram, which the ELL body
    gets by an O(d^2) slot comparison.  Here entries are sorted by
    (segment, label) — ``lax.sort`` with two keys — so equal-label entries
    form runs whose length IS the coupling; the lexicographic argmin then
    becomes a three-step segment-reduce cascade (max coupling, then min
    size among those, then min label among those).  Valid slots are
    distinct real neighbors in both layouts, so the counts — and therefore
    the chosen labels — are bit-identical to :func:`_phase3_rows`."""
    h = spill_rows.shape[0]
    s = spill_cols.shape[0]
    lab_n = labels[spill_cols]
    real = spill_cols != spill_rows[spill_seg]
    valid = real & (lab_n >= 0)
    key_lab = jnp.where(valid, lab_n, INT32_MAX)
    seg_s, lab_s = jax.lax.sort((spill_seg, key_lab), num_keys=2)
    start = jnp.concatenate([
        jnp.ones(1, dtype=bool),
        (seg_s[1:] != seg_s[:-1]) | (lab_s[1:] != lab_s[:-1])])
    run_id = xla_ops.cumsum(start.astype(jnp.int32)) - 1
    run_len = jax.ops.segment_sum(jnp.ones(s, jnp.int32), run_id,
                                  num_segments=s)
    c_e = jnp.where(lab_s < INT32_MAX, run_len[run_id], -1)
    size_e = aggsize[jnp.clip(lab_s, 0, aggsize.shape[0] - 1)]
    best_c = jax.ops.segment_max(c_e, seg_s, num_segments=h)
    on_c = c_e == best_c[seg_s]
    best_s = jax.ops.segment_min(jnp.where(on_c, size_e, INT32_MAX), seg_s,
                                 num_segments=h)
    on_s = on_c & (size_e == best_s[seg_s])
    best_l = jax.ops.segment_min(jnp.where(on_s, lab_s, INT32_MAX), seg_s,
                                 num_segments=h)
    joined = (best_c > 0) & (best_l < INT32_MAX)
    own = labels[spill_rows]
    return jnp.where((own < 0) & joined, best_l, own)


@jax.jit
def _phase3_resident_hybrid(slices, spill_rows, spill_seg, spill_cols,
                            labels, phase):
    """Hybrid twin of :func:`_phase3_resident` (same frozen-tentative-label
    rounds; aggregate sizes recomputed per round on the global vector)."""
    v = labels.shape[0]
    h = spill_rows.shape[0]

    def cond(state):
        labels, _, rounds = state
        return jnp.any(labels < 0) & (rounds < 4)

    def body(state):
        labels, phase, rounds = state
        aggsize = jnp.zeros(v + 1, jnp.int32).at[
            jnp.where(labels >= 0, labels, v)].add(1)
        new_labels = labels
        for sl in slices:
            vals = _phase3_rows(sl.neighbors, sl.mask, sl.rows, labels,
                                labels[sl.rows], aggsize)
            new_labels = new_labels.at[sl.rows].set(vals)
        if h > 0:
            vals = _phase3_spill(spill_rows, spill_seg, spill_cols, labels,
                                 aggsize)
            new_labels = new_labels.at[spill_rows].set(vals)
        newly = (labels < 0) & (new_labels >= 0)
        phase = jnp.where(newly, jnp.uint8(3), phase)
        return new_labels, phase, rounds + jnp.int32(1)

    labels, phase, _ = jax.lax.while_loop(
        cond, body, (labels, phase, jnp.int32(0)))
    return labels, phase


def _aggregate_basic_hybrid_impl(graph, options: Mis2Options | None = None,
                                 interpret=None) -> AggregationResult:
    """Algorithm 2 over the hybrid layout — never touches ``gh.ell``."""
    gh = as_graph(graph)
    hyb = gh.hybrid()
    parts = (tuple(hyb.slices), hyb.spill_rows, hyb.spill_seg, hyb.spill_cols)
    r = run_mis2(gh, options=options, engine="pallas_hybrid",
                 interpret=interpret)
    labels, nagg = _labels_from_roots_hybrid(hyb, r.in_set)
    phase = np.where(labels >= 0, 1, 0).astype(np.uint8)
    labels_j, phase_j = _cleanup_join_resident_hybrid(
        *parts, jnp.asarray(labels.astype(np.int32)), jnp.asarray(phase))
    labels, phase = np.asarray(labels_j), np.array(phase_j)
    labels, nagg = _finalize_singletons(labels, nagg, phase)
    return AggregationResult(labels.astype(np.int32), nagg, r.in_set, phase,
                             r.iterations, r.converged)


def _aggregate_two_phase_hybrid_impl(
        graph, options: Mis2Options | None = None,
        min_secondary_neighbors: int = 2,
        interpret=None) -> AggregationResult:
    """Algorithm 3 over the hybrid layout — never touches ``gh.ell``."""
    gh = as_graph(graph)
    hyb = gh.hybrid()
    parts = (tuple(hyb.slices), hyb.spill_rows, hyb.spill_seg, hyb.spill_cols)
    v = gh.num_vertices

    r1 = run_mis2(gh, options=options, engine="pallas_hybrid",
                  interpret=interpret)
    with _obs_span("coarsen.root_join"):
        labels, nagg = _labels_from_roots_hybrid(hyb, r1.in_set)
        phase = np.where(labels >= 0, 1, 0).astype(np.uint8)
    total_iters = r1.iterations
    converged = r1.converged

    unagg = labels < 0
    roots2 = np.zeros(v, dtype=bool)
    if unagg.any():
        r2 = run_mis2(gh, active=jnp.asarray(unagg), options=options,
                      engine="pallas_hybrid", interpret=interpret)
        total_iters += r2.iterations
        converged = converged and r2.converged
        with _obs_span("coarsen.phase2_join"):
            labels_j, roots2_j, newly_j = _phase2_join_resident_hybrid(
                *parts, jnp.asarray(labels.astype(np.int32)),
                jnp.asarray(r2.in_set), jnp.int32(nagg),
                min_secondary_neighbors)
            labels, roots2 = np.asarray(labels_j), np.asarray(roots2_j)
            phase[np.asarray(newly_j)] = 2
            nagg += int(roots2.sum())

    with _obs_span("coarsen.phase3_join"):
        labels_j, phase_j = _phase3_resident_hybrid(
            *parts, jnp.asarray(labels.astype(np.int32)), jnp.asarray(phase))
        labels, phase = np.asarray(labels_j), np.array(phase_j)

    return _finalize(labels, nagg, phase, r1.in_set | roots2, total_iters,
                     converged)


# ---------------------------------------------------------------------------
# Algorithm 2
# ---------------------------------------------------------------------------

def _aggregate_basic_impl(graph, options: Mis2Options | None = None,
                          engine: str = "compacted",
                          interpret=None, mesh=None,
                          axis=None) -> AggregationResult:
    if engine == "pallas_hybrid":
        return _aggregate_basic_hybrid_impl(graph, options,
                                            interpret=interpret)
    gh = as_graph(graph)
    ell = gh.ell
    r = run_mis2(gh, options=options, engine=engine, interpret=interpret,
                 mesh=mesh, axis=axis)
    labels, nagg = _labels_from_roots(ell, r.in_set)
    phase = np.where(labels >= 0, 1, 0).astype(np.uint8)

    # leftovers: join min adjacent aggregate (deterministic "arbitrary");
    # the whole multi-round loop is one resident dispatch
    labels_j, phase_j = _cleanup_join_resident(
        ell.neighbors, jnp.asarray(labels.astype(np.int32)),
        jnp.asarray(phase))
    # np.array (not asarray): _finalize_singletons mutates phase in place
    labels, phase = np.asarray(labels_j), np.array(phase_j)
    labels, nagg = _finalize_singletons(labels, nagg, phase)
    return AggregationResult(labels.astype(np.int32), nagg, r.in_set, phase,
                             r.iterations, r.converged)


# ---------------------------------------------------------------------------
# Algorithm 3
# ---------------------------------------------------------------------------

def _aggregate_two_phase_impl(graph, options: Mis2Options | None = None,
                              engine: str = "compacted",
                              min_secondary_neighbors: int = 2,
                              interpret=None, mesh=None,
                              axis=None) -> AggregationResult:
    if engine == "pallas_hybrid":
        return _aggregate_two_phase_hybrid_impl(
            graph, options, min_secondary_neighbors, interpret=interpret)
    gh = as_graph(graph)
    ell = gh.ell
    v = ell.num_vertices

    # Phase 1: MIS-2 roots + direct neighbors
    r1 = run_mis2(gh, options=options, engine=engine, interpret=interpret,
                  mesh=mesh, axis=axis)
    with _obs_span("coarsen.root_join"):
        labels, nagg = _labels_from_roots(ell, r1.in_set)
        phase = np.where(labels >= 0, 1, 0).astype(np.uint8)
    total_iters = r1.iterations
    converged = r1.converged

    # Phase 2: MIS-2 on the induced unaggregated subgraph.  The label join
    # (unagg-neighbor count, secondary-root cumsum, root join) runs as one
    # resident dispatch instead of three label round trips.
    unagg = labels < 0
    roots2 = np.zeros(v, dtype=bool)
    if unagg.any():
        r2 = run_mis2(gh, active=jnp.asarray(unagg), options=options,
                      engine=engine, interpret=interpret, mesh=mesh,
                      axis=axis)
        total_iters += r2.iterations
        converged = converged and r2.converged
        with _obs_span("coarsen.phase2_join"):
            labels_j, roots2_j, newly_j = _phase2_join_resident(
                ell.neighbors, ell.mask,
                jnp.asarray(labels.astype(np.int32)),
                jnp.asarray(r2.in_set), jnp.int32(nagg),
                min_secondary_neighbors)
            labels, roots2 = np.asarray(labels_j), np.asarray(roots2_j)
            phase[np.asarray(newly_j)] = 2
            nagg += int(roots2.sum())

    # Phase 3: max-coupling join against frozen tentative labels — the
    # whole up-to-4-round loop is one resident dispatch
    with _obs_span("coarsen.phase3_join"):
        labels_j, phase_j = _phase3_resident(
            ell.neighbors, ell.mask, jnp.asarray(labels.astype(np.int32)),
            jnp.asarray(phase))
        labels, phase = np.asarray(labels_j), np.array(phase_j)

    return _finalize(labels, nagg, phase, r1.in_set | roots2, total_iters,
                     converged)


def _finalize(labels, nagg, phase, roots, total_iters, converged):
    """Alg. 3's host finalisation (singletons, the result), in the
    ``coarsen.finalize`` span."""
    with _obs_span("coarsen.finalize"):
        labels, nagg = _finalize_singletons(labels, nagg, phase)
        return AggregationResult(labels.astype(np.int32), nagg, roots,
                                 phase, total_iters, converged)


# ---------------------------------------------------------------------------
# Algorithm 3, sharded (paper Alg. 2/3 rounds over the mesh — see core.dist)
# ---------------------------------------------------------------------------

def _aggregate_two_phase_distributed_impl(
        graph, options: Mis2Options | None = None,
        min_secondary_neighbors: int = 2, *, mesh=None, axis=None,
        single_gather: bool = False) -> AggregationResult:
    """Distributed ML-style coarsening: both MIS-2 phases run the sharded
    fixed point, and every label-propagation round (root join, unaggregated
    count, max-coupling phase 3) is one label all-gather + local rowwise
    join per round (V·4 bytes of collective traffic each).  Labels are
    bit-identical to the single-device ``two_phase`` engine: the sharded
    rounds share the exact rowwise arithmetic via the ``*_rows`` helpers.
    """
    from .dist import (
        _mis2_distributed_impl,
        _resolve_mesh,
        count_unagg_neighbors_distributed,
        join_adjacent_root_distributed,
        phase3_join_distributed,
        prepare_padded,
    )

    gh = as_graph(graph)
    v = gh.ell.num_vertices
    # pad + place the sharded adjacency ONCE for the whole pipeline (2
    # MIS-2 fixed points + up to ~6 label-propagation rounds reuse it);
    # ditto the replicated copy the single_gather schedule needs
    mesh, axis, _ = _resolve_mesh(mesh, axis)
    padded, _ = prepare_padded(gh, mesh, axis)
    dist_kw = {"mesh": mesh, "axis": axis, "padded": padded}
    mis2_kw = dict(dist_kw, single_gather=single_gather)
    if single_gather:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        mis2_kw["neighbors_replicated"] = jax.device_put(
            padded.neighbors, NamedSharding(mesh, PartitionSpec()))

    # Phase 1: sharded MIS-2 roots + direct neighbors (sharded root join)
    r1 = _mis2_distributed_impl(gh, options=options, **mis2_kw)
    agg_ids = np.cumsum(r1.in_set) - 1
    root_label = np.where(r1.in_set, agg_ids, INT32_MAX).astype(np.int32)
    labels = join_adjacent_root_distributed(gh, root_label, **dist_kw)
    nagg = int(r1.in_set.sum())
    phase = np.where(labels >= 0, 1, 0).astype(np.uint8)
    total_iters = r1.iterations
    converged = r1.converged

    # Phase 2: sharded MIS-2 on the induced unaggregated subgraph
    unagg = labels < 0
    roots2 = np.zeros(v, dtype=bool)
    if unagg.any():
        r2 = _mis2_distributed_impl(gh, active=jnp.asarray(unagg),
                                    options=options, **mis2_kw)
        total_iters += r2.iterations
        converged = converged and r2.converged
        n_unagg_nbrs = count_unagg_neighbors_distributed(gh, labels, **dist_kw)
        roots2 = r2.in_set & (n_unagg_nbrs >= min_secondary_neighbors)
        if roots2.any():
            agg_ids2 = nagg + np.cumsum(roots2) - 1
            rl2 = np.where(roots2, agg_ids2, INT32_MAX).astype(np.int32)
            adj2 = join_adjacent_root_distributed(gh, rl2, **dist_kw)
            newly = (labels < 0) & (adj2 >= 0)
            labels = np.where(newly, adj2, labels)
            phase[newly] = 2
            nagg += int(roots2.sum())

    # Phase 3: sharded max-coupling join against frozen tentative labels
    rounds = 0
    while (labels < 0).any() and rounds < 4:
        aggsize = np.bincount(labels[labels >= 0], minlength=max(nagg, 1))
        new_labels = phase3_join_distributed(
            gh, labels.astype(np.int32), aggsize.astype(np.int32), **dist_kw)
        newly = (labels < 0) & (new_labels >= 0)
        phase[newly] = 3
        labels = new_labels
        rounds += 1

    labels, nagg = _finalize_singletons(labels, nagg, phase)
    return AggregationResult(labels.astype(np.int32), nagg,
                             r1.in_set | roots2, phase, total_iters,
                             converged)


def _finalize_singletons(labels: np.ndarray, nagg: int, phase: np.ndarray):
    """Isolated leftovers (no aggregated neighbor at all) become singletons."""
    left = np.flatnonzero(labels < 0)
    if len(left):
        labels = labels.copy()
        labels[left] = nagg + np.arange(len(left))
        phase[left] = 3
        nagg += len(left)
    return labels, nagg


# ---------------------------------------------------------------------------
# host-sequential reference (Table V "Serial Agg" stand-in)
# ---------------------------------------------------------------------------

def _aggregate_serial_greedy_impl(graph) -> AggregationResult:
    csr = as_graph(graph).csr
    indptr = np.asarray(csr.indptr)
    indices = np.asarray(csr.indices)
    v = csr.num_vertices
    labels = np.full(v, -1, dtype=np.int32)
    roots = np.zeros(v, dtype=bool)
    nagg = 0
    for u in range(v):
        if labels[u] >= 0:
            continue
        nbrs = indices[indptr[u]:indptr[u + 1]]
        nbrs = nbrs[nbrs != u]
        free = nbrs[labels[nbrs] < 0]
        if len(free) >= 2:
            labels[u] = nagg
            labels[free] = nagg
            roots[u] = True
            nagg += 1
    for u in range(v):   # cleanup: join first aggregated neighbor
        if labels[u] < 0:
            nbrs = indices[indptr[u]:indptr[u + 1]]
            agg = nbrs[labels[nbrs] >= 0]
            if len(agg):
                labels[u] = labels[agg[0]]
            else:
                labels[u] = nagg
                nagg += 1
    phase = np.ones(v, dtype=np.uint8)
    return AggregationResult(labels, nagg, roots, phase, 0)


# ---------------------------------------------------------------------------
# legacy public entry points (deprecated — use repro.api.coarsen)
# ---------------------------------------------------------------------------

def aggregate_basic(graph, options: Mis2Options | None = None,
                    engine: str = "compacted") -> AggregationResult:
    """Deprecated entry point — use ``repro.api.coarsen(method="basic")``."""
    warn_deprecated("repro.core.aggregation.aggregate_basic",
                    'repro.api.coarsen(..., method="basic")')
    return _aggregate_basic_impl(graph, options, engine)


def aggregate_two_phase(graph, options: Mis2Options | None = None,
                        engine: str = "compacted",
                        min_secondary_neighbors: int = 2) -> AggregationResult:
    """Deprecated entry point — use ``repro.api.coarsen(method="two_phase")``."""
    warn_deprecated("repro.core.aggregation.aggregate_two_phase",
                    'repro.api.coarsen(..., method="two_phase")')
    return _aggregate_two_phase_impl(graph, options, engine,
                                     min_secondary_neighbors)


def aggregate_serial_greedy(graph) -> AggregationResult:
    """Deprecated entry point — use ``repro.api.coarsen(method="serial")``."""
    warn_deprecated("repro.core.aggregation.aggregate_serial_greedy",
                    'repro.api.coarsen(..., method="serial")')
    return _aggregate_serial_greedy_impl(graph)
