"""Parallel, deterministic distance-2 maximal independent set (paper Alg. 1).

Three execution strategies, bit-identical results:

* ``mis2_dense``  — a single ``lax.while_loop`` fixed point over dense vertex
  arrays.  Fully jittable, usable inside larger jitted programs (distributed
  MIS-2, dry-run lowering).  Worklists degenerate to masks here: on a vector
  machine masked lanes cost bandwidth, not serialization (DESIGN.md §3).
* ``mis2_compacted`` — host-orchestrated iteration with *real* worklist
  compaction (paper §V-B): per-iteration work is proportional to the live
  worklists, padded to power-of-two buckets so XLA caches a handful of
  compiled step sizes.  This is the legacy host-driven path and the engine
  behind the Fig. 2 ablation.
* ``compacted_resident`` / ``pallas_resident`` — the production hot loop:
  the *same* per-round update sets as ``mis2_compacted``, but the whole
  fixed point is one jitted ``lax.while_loop`` over fixed ``[V]``-shaped
  state.  The packed XLA ELL branch applies the sets as ``[V]`` masks over
  full-width rounds (two neighbor gathers a round, no scatter); the Pallas
  and unpacked branches compact index worklists **on device**
  (cumsum-based stream compaction producing ``(indices[V], count)``
  pairs; dead slots hold the sentinel ``V`` and are scatter-dropped), and
  the live ``count`` feeds the Pallas ``pl.when`` block-skip logic instead
  of a host-side ``len(wl)``.  Zero host round-trips inside the fixed
  point, one dispatch per solve, no jit churn across worklist sizes — and
  results stay bit-identical to the host-driven engines (enforced by the
  digest-parity matrix in ``tests/test_resident.py``).

The Fig. 2 optimization chain is exposed through ``Mis2Options`` — each knob
is one of the paper's four optimizations:

=================  =========================================================
``priority``       §V-A fresh pseudo-random priorities (fixed | xorshift |
                   xorshift_star)
``worklists``      §V-B worklist compaction
``packed``         §V-C compressed 32-bit status tuples (False = 3-field
                   tuples: status uint8 / rand uint32 / id uint32 — the
                   unpacked lexicographic min costs three reduction passes)
``layout``         §V-D 'ell' = padded lane-aligned gathers (TPU analogue of
                   warp-coalesced rows) | 'csr_segment' = segment reductions
=================  =========================================================

Cumulative chain reproduced by ``benchmarks/fig2_optimizations.py``:
baseline(Bell: fixed, no worklists, unpacked, csr) -> +priorities ->
+worklists -> +packed -> +ELL('SIMD') == production defaults.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops
from .._compat import warn_deprecated
from ..graphs.handle import as_graph
from ..obs import metrics as _OBS
from ..obs import span as _obs_span
from .hashing import PRIORITY_FNS
from .tuples import IN, OUT, effective_priority, id_bits, is_undecided, pack

MAX_ITERS_DEFAULT = 128

U32MAX = np.uint32(0xFFFFFFFF)
S_IN, S_UND, S_OUT = np.uint8(0), np.uint8(1), np.uint8(2)


@dataclass(frozen=True)
class Mis2Options:
    priority: str = "xorshift_star"     # fixed | xorshift | xorshift_star
    worklists: bool = True              # §V-B
    packed: bool = True                 # §V-C
    layout: str = "ell"                 # ell | csr_segment  (§V-D)
    max_iters: int = MAX_ITERS_DEFAULT
    use_pallas: bool = False            # deprecated: use engine="pallas"

    def __post_init__(self):
        if self.use_pallas:
            warn_deprecated("Mis2Options(use_pallas=True)",
                            'repro.api.mis2(..., engine="pallas")')


@dataclass
class Mis2Result:
    in_set: np.ndarray        # bool [V]
    iterations: int
    converged: bool
    collectives: Optional[dict] = None  # distributed engines: §V-C traffic
    num_compiles: Optional[int] = None  # distinct jitted step shapes this
    #                                     solve required (resident: always 1;
    #                                     legacy compacted: pow2 bucket pairs)

    def __post_init__(self):
        # Result-protocol guarantee: payloads are host numpy arrays
        # regardless of which engine produced them.
        self.in_set = np.asarray(self.in_set)

    @property
    def size(self) -> int:
        return int(self.in_set.sum())


# ===========================================================================
# dense (fully jitted) engine — packed tuples, ELL layout
# ===========================================================================

def mis2_dense_fixed_point(neighbors: jnp.ndarray,
                           active: Optional[jnp.ndarray], b: jnp.ndarray,
                           priority: str = "xorshift_star",
                           max_iters: int = MAX_ITERS_DEFAULT):
    """Mask-aware MIS-2 fixed point over one (possibly padded) graph.

    ``b`` is the packing id-bit count as a *traced* uint32 scalar rather
    than a Python int derived from ``neighbors.shape[0]``.  That makes the
    function vmappable over stacked ``[B, rows, deg]`` buckets whose member
    graphs have different real vertex counts: each graph keeps its own
    ``b = id_bits(V_real)``, so priorities — and therefore the resulting
    set — are bit-identical to the single-graph run at shape ``[V_real]``.
    Padded rows ride along inactive (T pinned to OUT, self-loop adjacency)
    and cannot influence real rows.

    The iteration counter doubles as the §V-A priority round, so it only
    advances while this graph still has undecided vertices — under vmap a
    converged graph stops counting (and its state is a fixed point of
    ``body``) while its bucket mates continue.

    A round makes two neighbor gathers (``t`` and ``m``) through the
    slot-major ``[D, V]`` index block and no scatter; the undecided set is
    §V-B's wl1 as a mask.  ``active`` is loop-invariant, so its per-slot
    view is gathered once per solve; ``active=None`` (every vertex
    active) skips that table.
    """
    v = neighbors.shape[0]
    vids = jnp.arange(v, dtype=jnp.uint32)
    prio_fn = PRIORITY_FNS[priority]
    nbT = neighbors.T                                    # [D, V]
    slot_active = None if active is None else active[nbT]

    # inactive vertices are invisible: T pinned to OUT, never refreshed
    t0 = jnp.ones(v, dtype=jnp.uint32) if active is None \
        else jnp.where(active, jnp.uint32(1), OUT)

    def cond(state):
        t, it = state
        return jnp.any(is_undecided(t)) & (it < max_iters)

    def body(state):
        t, it = state
        und = is_undecided(t)
        live = jnp.any(und)
        # refresh row (§V-A)
        t = jnp.where(und, pack(prio_fn(it, vids), vids, b), t)
        # refresh column: closed-neighborhood min (§V-D layout)
        m = jnp.min(t[nbT], axis=0)
        m = jnp.where(m == IN, OUT, m)          # IN-adjacent poison
        # decide (distance-2 via neighbors' minima); inactive slots are
        # neither OUT nor unequal
        mn = m[nbT]
        out, eq = mn == OUT, mn == t
        if slot_active is not None:
            out, eq = out & slot_active, eq | ~slot_active
        any_out = jnp.any(out, axis=0)
        all_eq = jnp.all(eq, axis=0)
        t = jnp.where(und & any_out, OUT, t)
        t = jnp.where(und & ~any_out & all_eq, IN, t)
        return t, it + live.astype(jnp.uint32)

    t, iters = jax.lax.while_loop(cond, body, (t0, jnp.uint32(0)))
    return t, iters


@functools.partial(jax.jit, static_argnames=("priority", "max_iters"))
def mis2_dense_jittable(neighbors: jnp.ndarray, active: jnp.ndarray,
                        priority: str = "xorshift_star",
                        max_iters: int = MAX_ITERS_DEFAULT):
    """Core fixed point; returns (packed tuple vector T, iterations).

    Safe to call inside larger jitted programs (e.g. AMG setup dry-runs).
    """
    b = jnp.uint32(id_bits(neighbors.shape[0]))
    return mis2_dense_fixed_point(neighbors, active, b, priority, max_iters)


def _mis2_dense_impl(graph, active: Optional[jnp.ndarray] = None,
                     options: Optional[Mis2Options] = None) -> Mis2Result:
    options = Mis2Options() if options is None else options
    ell = as_graph(graph).ell
    if active is not None:
        active = jnp.asarray(active)
    t, iters = mis2_dense_jittable(ell.neighbors, active,
                                   options.priority, options.max_iters)
    t_np = np.asarray(t)
    return Mis2Result(t_np == np.uint32(IN), int(iters),
                      not is_undecided(t_np).any())


# ===========================================================================
# incremental repair (repro.serve streaming mode)
# ===========================================================================

@functools.partial(jax.jit, static_argnames=("priority", "max_iters"))
def mis2_repair_fixed_point(neighbors: jnp.ndarray, t_init: jnp.ndarray,
                            b: jnp.ndarray, priority: str = "fixed",
                            max_iters: int = MAX_ITERS_DEFAULT):
    """Warm-started MIS-2 fixed point: the dense body, seeded from a prior
    solution instead of all-undecided.

    ``t_init`` holds ``IN`` / ``OUT`` on *frozen* vertices (carried over
    from the pre-delta solution) and the undecided seed ``1`` on the
    reactivated region.  Frozen vertices are never refreshed — frozen
    ``IN`` poisons its distance-2 neighborhood exactly like a decided
    vertex mid-run, frozen ``OUT`` is invisible (the same encoding the
    dense engine uses for inactive rows) — so per-round work is
    proportional to the reactivated region, not ``V``.

    Only meaningful with a round-independent priority (``"fixed"``): the
    result is then the unique lexicographically-first MIS-2, so a repaired
    solution that satisfies the lex-first recurrence everywhere (see
    :func:`lexfirst_violations`) is *bit-identical* to a from-scratch run.
    Round-varying priorities make the fixed point history-dependent and
    repair inexact; ``repro.serve`` falls back to recomputation there.
    """
    vids = jnp.arange(neighbors.shape[0], dtype=jnp.uint32)
    prio_fn = PRIORITY_FNS[priority]

    def cond(state):
        t, it = state
        return jnp.any(is_undecided(t)) & (it < max_iters)

    def body(state):
        t, it = state
        und = is_undecided(t)
        live = jnp.any(und)
        t = jnp.where(und, pack(prio_fn(it, vids), vids, b), t)
        tn = xla_ops.gather_rows(t, neighbors)
        m = jnp.min(tn, axis=1)
        m = jnp.where(m == IN, OUT, m)
        mn = xla_ops.gather_rows(m, neighbors)
        any_out = jnp.any(mn == OUT, axis=1)
        all_eq = jnp.all(mn == t[:, None], axis=1)
        t = jnp.where(und & any_out, OUT, t)
        t = jnp.where(und & ~any_out & all_eq, IN, t)
        return t, it + live.astype(jnp.uint32)

    return jax.lax.while_loop(cond, body, (t_init, jnp.uint32(0)))


@jax.jit
def lexfirst_violations(neighbors: jnp.ndarray, in_set: jnp.ndarray,
                        p: jnp.ndarray) -> jnp.ndarray:
    """Vertices violating the lex-first MIS-2 recurrence (bool ``[V]``).

    The lexicographically-first MIS-2 under the packed priority total
    order ``p`` is the unique assignment with: ``v IN`` iff no member
    within distance <= 2 has strictly smaller priority.  Two closed-
    neighborhood min-propagations of the members' priorities check it
    globally: ``m2[v]`` is the smallest member priority within distance 2
    of ``v`` (inclusive), so ``v IN`` must see ``m2 == p[v]`` (itself) and
    ``v OUT`` must see ``m2 < p[v]`` (a strictly earlier member justifies
    the exclusion — this also covers maximality: no member at all means
    ``m2 == OUT > p[v]``).  An all-clear certifies the assignment *is*
    the lex-first solution; violations tell the repair loop which frozen
    vertices to reactivate.
    """
    pin = jnp.where(in_set, p, OUT)
    m1 = jnp.minimum(jnp.min(xla_ops.gather_rows(pin, neighbors), axis=1), pin)
    m2 = jnp.minimum(jnp.min(xla_ops.gather_rows(m1, neighbors), axis=1), m1)
    return ~jnp.where(in_set, m2 == p, m2 < p)


def fixed_packed_priorities(num_vertices: int) -> jnp.ndarray:
    """The packed ``"fixed"``-priority total order (uint32 ``[V]``) — the
    order under which the MIS-2 fixed point computes the lex-first set."""
    vids = jnp.arange(num_vertices, dtype=jnp.uint32)
    b = jnp.uint32(id_bits(num_vertices))
    return pack(PRIORITY_FNS["fixed"](jnp.uint32(0), vids), vids, b)


# ===========================================================================
# hot-loop accounting (test-only observability; no effect on results)
# ===========================================================================

class HotLoopStats:
    """Compatibility view over the MIS-2 hot-loop registry counters.

    ``host_syncs`` counts device->host transfers issued *inside* a fixed
    point (the legacy compacted driver pays 2 per iteration to rebuild its
    worklists); ``resident_dispatches`` counts whole-fixed-point jitted
    dispatches (the resident engines pay exactly 1 per solve).

    The numbers live in the process-wide :mod:`repro.obs` registry
    (``mis2.host_syncs`` / ``mis2.resident_dispatches``), so one
    ``obs.snapshot()`` sees them alongside every other subsystem; this
    shim keeps the legacy attribute surface (including ``+=`` writes)
    working.  Tests should prefer ``obs.capture()`` over :meth:`reset` —
    capture is scoped, reset is process-global and order-dependent.
    """

    _SYNCS = "mis2.host_syncs"
    _DISPATCHES = "mis2.resident_dispatches"

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


HOTLOOP_STATS = HotLoopStats()

#: rounds run by the resident fixed points, labelled by layout
#: (``ell`` | ``csr_segment`` | ``hybrid``)
ROUNDS = "mis2.rounds"

#: packed-ELL ``compacted_resident`` solves given a caller's ``active``
#: mask, whose per-slot table is gathered once per solve, before the loop
ACTIVE_FOLDS = "mis2.active_folds"


# ===========================================================================
# step kernels for the compacted / ablation engine
#   worklists are padded int32 index buffers; sentinel == V (scatter-dropped)
# ===========================================================================

def _bucket(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _pad_worklist(idx: np.ndarray, v: int) -> jnp.ndarray:
    size = _bucket(len(idx))
    out = np.full(size, v, dtype=np.int32)
    out[: len(idx)] = idx
    return jnp.asarray(out)


class _WorklistPadCache:
    """Per-solve bucket-shape cache for the host-driven driver.

    ``shape_pairs`` records the distinct ``(len(wl1), len(wl2))`` pow2
    bucket pairs the solve dispatched — the jit-churn metric surfaced as
    ``Mis2Result.num_compiles`` (each new pair is a fresh XLA
    specialization of the step kernels; the resident engines hold this at
    1 by construction).  Conversion itself stays :func:`_pad_worklist`
    with a fresh host buffer per call: staging through a reused mutable
    buffer is unsafe, because ``jnp.asarray`` of an aligned numpy array
    can be zero-copy on CPU, and a later refill would silently rewrite
    the live device worklist.
    """

    def __init__(self, v: int):
        self.v = v
        self.shape_pairs: set[tuple[int, int]] = set()

    def pad(self, idx: np.ndarray) -> jnp.ndarray:
        return _pad_worklist(idx, self.v)


# ---- packed representation ----

@functools.partial(jax.jit, static_argnames=("priority", "b"))
def _refresh_rows_packed(t, wl1, it, priority: str, b: int):
    v = t.shape[0]
    rows = jnp.clip(wl1, 0, v - 1)
    ids = rows.astype(jnp.uint32)
    told = t[rows]
    newt = pack(PRIORITY_FNS[priority](it, ids), ids, b)
    newt = jnp.where(is_undecided(told), newt, told)   # idempotent on decided
    return t.at[wl1].set(newt, mode="drop")


@jax.jit
def _refresh_cols_packed_ell(t, m, wl2, neighbors):
    tn = t[xla_ops.slot_major_rows(neighbors, wl2)]     # [D, W]
    mv = jnp.min(tn, axis=0)
    mv = jnp.where(mv == IN, OUT, mv)
    return m.at[wl2].set(mv, mode="drop")


@jax.jit
def _decide_packed_ell(t, m, wl1, neighbors, active):
    v = neighbors.shape[0]
    nb = xla_ops.slot_major_rows(neighbors, wl1)        # [D, W]
    mn = m[nb]
    an = active[nb]
    tv = t[jnp.clip(wl1, 0, v - 1)]
    any_out = jnp.any(jnp.where(an, mn, IN) == OUT, axis=0)
    all_eq = jnp.all(jnp.where(an, mn, tv[None, :]) == tv[None, :], axis=0)
    newt = jnp.where(any_out, OUT, jnp.where(all_eq, IN, tv))
    newt = jnp.where(is_undecided(tv), newt, tv)
    return t.at[wl1].set(newt, mode="drop")


@functools.partial(jax.jit, static_argnames=("v",))
def _refresh_cols_packed_csr(t, m, wl2_mask, edge_rows, edge_cols, v: int):
    te = t[edge_cols]
    mv = jax.ops.segment_min(te, edge_rows, num_segments=v)
    mv = jnp.minimum(mv, t)                    # closed neighborhood
    mv = jnp.where(mv == IN, OUT, mv)
    return jnp.where(wl2_mask, mv, m)


@functools.partial(jax.jit, static_argnames=("v",))
def _decide_packed_csr(t, m, wl1_mask, edge_rows, edge_cols, active, v: int):
    mn = m[edge_cols]
    an = active[edge_cols]
    te = t[edge_rows]
    has_out = jax.ops.segment_max(
        ((an & (mn == OUT)).astype(jnp.int32)), edge_rows, num_segments=v
    ) > 0
    has_out = has_out | (m == OUT)             # closed (self term)
    neq = jax.ops.segment_max(
        (an & (mn != te)).astype(jnp.int32), edge_rows, num_segments=v
    ) > 0
    all_eq = ~neq & (m == t)                   # closed (self term)
    newt = jnp.where(has_out, OUT, jnp.where(all_eq, IN, t))
    newt = jnp.where(is_undecided(t), newt, t)
    return jnp.where(wl1_mask, newt, t)


# ---- unpacked (3-field) representation (§V-C ablation) ----

def _lex_lt(s1, r1, i1, s2, r2, i2):
    return (s1 < s2) | ((s1 == s2) & ((r1 < r2) | ((r1 == r2) & (i1 < i2))))


@functools.partial(jax.jit, static_argnames=("priority", "b"))
def _refresh_rows_unpacked(ts, tr, ti, wl1, it, priority: str, b: int):
    v = ts.shape[0]
    rows = jnp.clip(wl1, 0, v - 1)
    ids = rows.astype(jnp.uint32)
    und = ts[rows] == S_UND
    prio = effective_priority(PRIORITY_FNS[priority](it, ids), b)
    newr = jnp.where(und, prio, tr[rows])
    tr = tr.at[wl1].set(newr, mode="drop")
    return ts, tr, ti


@jax.jit
def _refresh_cols_unpacked_ell(ts, tr, ti, ms, mr, mi, wl2, neighbors):
    v = neighbors.shape[0]
    rows = jnp.clip(wl2, 0, v - 1)
    nb = neighbors[rows]                      # [W, D]
    cs, cr, ci = (xla_ops.gather_rows(x, nb) for x in (ts, tr, ti))
    bs, br, bi = cs[:, 0], cr[:, 0], ci[:, 0]
    for j in range(1, nb.shape[1]):           # unrolled lexicographic min
        lt = _lex_lt(cs[:, j], cr[:, j], ci[:, j], bs, br, bi)
        bs = jnp.where(lt, cs[:, j], bs)
        br = jnp.where(lt, cr[:, j], br)
        bi = jnp.where(lt, ci[:, j], bi)
    poisoned = bs == S_IN                     # IN-adjacent poison
    bs = jnp.where(poisoned, S_OUT, bs)
    ms = ms.at[wl2].set(bs, mode="drop")
    mr = mr.at[wl2].set(br, mode="drop")
    mi = mi.at[wl2].set(bi, mode="drop")
    return ms, mr, mi


@functools.partial(jax.jit, static_argnames=("v",))
def _refresh_cols_unpacked_csr(ts, tr, ti, ms, mr, mi, wl2_mask,
                               edge_rows, edge_cols, v: int):
    """Three segment passes — the traffic cost packing removes (§V-C)."""
    es, er, ei = ts[edge_cols], tr[edge_cols], ti[edge_cols]
    smin = jax.ops.segment_min(es, edge_rows, num_segments=v)
    smin = jnp.minimum(smin, ts)
    on_s = es == smin[edge_rows]
    rmin = jax.ops.segment_min(jnp.where(on_s, er, U32MAX), edge_rows,
                               num_segments=v)
    rmin = jnp.where(ts == smin, jnp.minimum(rmin, tr), rmin)
    on_r = on_s & (er == rmin[edge_rows])
    imin = jax.ops.segment_min(jnp.where(on_r, ei, U32MAX), edge_rows,
                               num_segments=v)
    imin = jnp.where((ts == smin) & (tr == rmin), jnp.minimum(imin, ti), imin)
    poisoned = smin == S_IN
    smin = jnp.where(poisoned, S_OUT, smin)
    ms = jnp.where(wl2_mask, smin, ms)
    mr = jnp.where(wl2_mask, rmin, mr)
    mi = jnp.where(wl2_mask, imin, mi)
    return ms, mr, mi


@jax.jit
def _decide_unpacked_ell(ts, tr, ti, ms, mr, mi, wl1, neighbors, active):
    v = neighbors.shape[0]
    rows = jnp.clip(wl1, 0, v - 1)
    nb = neighbors[rows]
    an = xla_ops.gather_rows(active, nb)
    cs, cr, ci = (xla_ops.gather_rows(x, nb) for x in (ms, mr, mi))
    tvs, tvr, tvi = ts[rows], tr[rows], ti[rows]
    any_out = jnp.any(an & (cs == S_OUT), axis=1)
    eq = (cs == S_UND) & (cr == tvr[:, None]) & (ci == tvi[:, None])
    all_eq = jnp.all(jnp.where(an, eq, True), axis=1)
    news = jnp.where(any_out, S_OUT, jnp.where(all_eq, S_IN, tvs))
    news = jnp.where(tvs == S_UND, news, tvs)
    return ts.at[wl1].set(news, mode="drop")


@functools.partial(jax.jit, static_argnames=("v",))
def _decide_unpacked_csr(ts, tr, ti, ms, mr, mi, wl1_mask,
                         edge_rows, edge_cols, active, v: int):
    an = active[edge_cols]
    cs, cr, ci = ms[edge_cols], mr[edge_cols], mi[edge_cols]
    any_out = jax.ops.segment_max(
        (an & (cs == S_OUT)).astype(jnp.int32), edge_rows, num_segments=v
    ) > 0
    any_out = any_out | (ms == S_OUT)
    neq = (cs != S_UND) | (cr != tr[edge_rows]) | (ci != ti[edge_rows])
    some_neq = jax.ops.segment_max(
        (an & neq).astype(jnp.int32), edge_rows, num_segments=v
    ) > 0
    self_eq = (ms == S_UND) & (mr == tr) & (mi == ti)
    all_eq = ~some_neq & self_eq
    news = jnp.where(any_out, S_OUT, jnp.where(all_eq, S_IN, ts))
    news = jnp.where(ts == S_UND, news, ts)
    return jnp.where(wl1_mask, news, ts)


# ===========================================================================
# compacted / ablation driver
# ===========================================================================

def _mis2_compacted_impl(graph, active: Optional[np.ndarray] = None,
                         options: Optional[Mis2Options] = None, *,
                         pallas: Optional[bool] = None,
                         interpret: Optional[bool] = None) -> Mis2Result:
    options = Mis2Options() if options is None else options
    gh = as_graph(graph)
    if options.layout == "ell":
        ell = gh.ell
        v = ell.num_vertices
    elif options.layout == "csr_segment":
        edge_rows, edge_cols = gh.csr_edges
        v = gh.num_vertices
    else:
        raise ValueError(options.layout)

    active_np = np.ones(v, bool) if active is None else np.asarray(active)
    active_j = jnp.asarray(active_np)
    b = id_bits(v)

    use_pallas = options.use_pallas if pallas is None else pallas
    minprop_ops = None
    if use_pallas:
        if not (options.layout == "ell" and options.packed):
            raise ValueError("pallas path requires packed tuples + ELL layout")
        from ..kernels.minprop_ell import ops as minprop_ops  # noqa: F811

    if options.packed:
        t = jnp.where(active_j, jnp.uint32(1), U32MAX)
        m = jnp.full(v, U32MAX, dtype=jnp.uint32)
    else:
        ts = jnp.where(active_j, S_UND, S_OUT).astype(jnp.uint8)
        tr = jnp.zeros(v, dtype=jnp.uint32)
        ti = jnp.arange(v, dtype=jnp.uint32)
        ms = jnp.full(v, S_OUT, dtype=jnp.uint8)
        mr = jnp.full(v, U32MAX, dtype=jnp.uint32)
        mi = jnp.full(v, U32MAX, dtype=jnp.uint32)

    pads = _WorklistPadCache(v)
    wl1_np = np.flatnonzero(active_np).astype(np.int32)
    wl2_np = np.arange(v, dtype=np.int32)
    it = 0
    while len(wl1_np) and it < options.max_iters:
        if options.worklists or it == 0:
            wl1 = pads.pad(wl1_np)
            wl2 = pads.pad(wl2_np)
            if options.layout == "csr_segment":
                wl1_mask = jnp.zeros(v, bool).at[wl1].set(True, mode="drop")
                wl2_mask = jnp.zeros(v, bool).at[wl2].set(True, mode="drop")
        # without worklists, the full it==0 buffers are reused every iteration
        pads.shape_pairs.add((len(wl1), len(wl2)))

        if options.packed:
            t = _refresh_rows_packed(t, wl1, np.uint32(it), options.priority, b)
            if options.layout == "ell":
                if minprop_ops is not None:
                    m = minprop_ops.refresh_columns(t, m, wl2, ell.neighbors,
                                                    len(wl2_np),
                                                    interpret=interpret)
                    t = minprop_ops.decide(t, m, wl1, ell.neighbors, active_j,
                                           len(wl1_np), interpret=interpret)
                else:
                    m = _refresh_cols_packed_ell(t, m, wl2, ell.neighbors)
                    t = _decide_packed_ell(t, m, wl1, ell.neighbors, active_j)
            else:
                m = _refresh_cols_packed_csr(t, m, wl2_mask, edge_rows,
                                             edge_cols, v)
                t = _decide_packed_csr(t, m, wl1_mask, edge_rows, edge_cols,
                                       active_j, v)
            t_np = np.asarray(t)
            und = is_undecided(t_np)
            live = np.asarray(m) != U32MAX
            _OBS.counter(HotLoopStats._SYNCS).inc(2)  # t + m pulled to rebuild worklists
        else:
            ts, tr, ti = _refresh_rows_unpacked(ts, tr, ti, wl1, np.uint32(it),
                                                options.priority, b)
            if options.layout == "ell":
                ms, mr, mi = _refresh_cols_unpacked_ell(
                    ts, tr, ti, ms, mr, mi, wl2, ell.neighbors)
                ts = _decide_unpacked_ell(ts, tr, ti, ms, mr, mi, wl1,
                                          ell.neighbors, active_j)
            else:
                ms, mr, mi = _refresh_cols_unpacked_csr(
                    ts, tr, ti, ms, mr, mi, wl2_mask, edge_rows, edge_cols, v)
                ts = _decide_unpacked_csr(ts, tr, ti, ms, mr, mi, wl1_mask,
                                          edge_rows, edge_cols, active_j, v)
            t_np = np.asarray(ts)
            und = t_np == S_UND
            live = np.asarray(ms) != S_OUT
            _OBS.counter(HotLoopStats._SYNCS).inc(2)  # ts + ms pulled to rebuild worklists
        wl1_np = np.flatnonzero(und).astype(np.int32)
        wl2_np = np.flatnonzero(live).astype(np.int32)
        it += 1

    in_set = (np.asarray(t) == np.uint32(IN)) if options.packed \
        else (np.asarray(ts) == S_IN)
    return Mis2Result(in_set, it, len(wl1_np) == 0,
                      num_compiles=max(1, len(pads.shape_pairs)))


# ===========================================================================
# device-resident engine: the whole §V-B fixed point is ONE jitted
# lax.while_loop — update sets as masks or on-device worklists, zero host
# round-trips
# ===========================================================================

def compact_worklist(mask: jnp.ndarray):
    """Cumsum-based stream compaction of a live-vertex mask.

    Returns ``(indices[V] int32, count int32)``: the first ``count`` slots
    hold the indices of the set bits in ascending order (exactly
    ``np.flatnonzero`` order, so the device worklists match the host-driven
    driver's buffers element for element); dead slots hold the sentinel
    ``V`` and are dropped by every downstream ``.at[wl].set(..., 'drop')``
    scatter — the same convention as :func:`_pad_worklist`.
    """
    v = mask.shape[0]
    vids = jnp.arange(v, dtype=jnp.int32)
    pos = xla_ops.cumsum(mask.astype(jnp.int32)) - 1
    wl = jnp.full(v, v, dtype=jnp.int32)
    wl = wl.at[jnp.where(mask, pos, v)].set(vids, mode="drop")
    return wl, jnp.sum(mask, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "priority", "packed", "max_iters", "b", "use_pallas", "interpret"))
def _resident_ell_fixed_point(neighbors, active, *, priority: str,
                              packed: bool, max_iters: int, b: int,
                              use_pallas: bool = False,
                              interpret: Optional[bool] = None):
    """Device-resident fixed point, ELL layout: one ``lax.while_loop``, a
    single dispatch per solve, the same sets and rounds as the host-driven
    driver.

    The packed XLA branch runs :func:`mis2_dense_fixed_point`: the §V-B
    update sets as ``[V]`` masks, two neighbor gathers a round, and no
    per-slot mask table when ``active`` is ``None`` (every vertex
    active).  The unpacked §V-C
    ablation runs the host driver's step kernels over index worklists
    (``[V]``-sentinel convention) rebuilt on device by
    :func:`compact_worklist`.  With ``use_pallas`` the round runs the
    *fused* Pallas passes (``kernels.minprop_ell.ops.fused_refresh_columns``
    / ``fused_decide``) over those index worklists: the §V-A rank packing
    is recomputed on the fly from the gathered neighbor ids, so no
    separate refresh_rows pass runs and each round reads the ELL rows once
    per pass, with the live ``count`` feeding the ``pl.when`` block-skip
    logic.  ``interpret=None`` defers to the platform policy
    (``kernels._interpret.resolve_interpret``).
    """
    if packed and not use_pallas:
        t, it = mis2_dense_fixed_point(neighbors, active, jnp.uint32(b),
                                       priority, max_iters)
        return t, it, jnp.sum(is_undecided(t), dtype=jnp.int32)
    v = neighbors.shape[0]
    if active is None:
        active = jnp.ones(v, dtype=bool)
    if use_pallas:
        from ..kernels._interpret import resolve_interpret
        from ..kernels.minprop_ell import ops as minprop_ops

        interpret = resolve_interpret(interpret)

    if packed:
        t0 = jnp.where(active, jnp.uint32(1), U32MAX)
        m0 = jnp.full(v, U32MAX, dtype=jnp.uint32)
        tup0 = (t0, m0)
    else:
        ts0 = jnp.where(active, S_UND, S_OUT).astype(jnp.uint8)
        tr0 = jnp.zeros(v, dtype=jnp.uint32)
        ti0 = jnp.arange(v, dtype=jnp.uint32)
        ms0 = jnp.full(v, S_OUT, dtype=jnp.uint8)
        mr0 = jnp.full(v, U32MAX, dtype=jnp.uint32)
        mi0 = jnp.full(v, U32MAX, dtype=jnp.uint32)
        tup0 = (ts0, tr0, ti0, ms0, mr0, mi0)

    wl1_0, n1_0 = compact_worklist(active)
    wl2_0 = jnp.arange(v, dtype=jnp.int32)   # iteration 0: refresh every M row
    state0 = (tup0, wl1_0, n1_0, wl2_0, jnp.int32(v), jnp.uint32(0))

    def cond(state):
        _, _, n1, _, _, it = state
        return (n1 > 0) & (it < max_iters)

    def body(state):
        tup, wl1, n1, wl2, n2, it = state
        if packed:                                # the fused Pallas passes
            t, m = tup
            m = minprop_ops.fused_refresh_columns(
                t, m, wl2, n2, neighbors, it, priority=priority, b=b,
                interpret=interpret)
            t = minprop_ops.fused_decide(
                t, m, wl1, n1, neighbors, active, it, priority=priority,
                b=b, interpret=interpret)
            und = is_undecided(t)
            live = m != U32MAX
            tup = (t, m)
        else:
            ts, tr, ti, ms, mr, mi = tup
            ts, tr, ti = _refresh_rows_unpacked(ts, tr, ti, wl1, it,
                                                priority, b)
            ms, mr, mi = _refresh_cols_unpacked_ell(ts, tr, ti, ms, mr, mi,
                                                    wl2, neighbors)
            ts = _decide_unpacked_ell(ts, tr, ti, ms, mr, mi, wl1,
                                      neighbors, active)
            und = ts == S_UND
            live = ms != S_OUT
            tup = (ts, tr, ti, ms, mr, mi)
        wl1, n1 = compact_worklist(und)
        wl2, n2 = compact_worklist(live)
        return tup, wl1, n1, wl2, n2, it + jnp.uint32(1)

    tup, _, n1, _, _, it = jax.lax.while_loop(cond, body, state0)
    return tup[0], it, n1


@functools.partial(jax.jit, static_argnames=(
    "priority", "packed", "max_iters", "b", "v"))
def _resident_csr_fixed_point(edge_rows, edge_cols, active, *, priority: str,
                              packed: bool, max_iters: int, b: int, v: int):
    """Device-resident compacted fixed point, ``csr_segment`` layout.

    The segment kernels already consume ``[V]`` worklist *masks*, so
    compaction degenerates to mask recomputation — the loop state stays
    fixed-shape and the whole fixed point is one dispatch, like the ELL
    variant.  The row refresh is applied through the mask (the wl1 set is
    exactly the undecided set, so this matches the host driver's
    index-buffer scatter bit for bit).
    """
    vids = jnp.arange(v, dtype=jnp.uint32)
    prio_fn = PRIORITY_FNS[priority]

    if packed:
        t0 = jnp.where(active, jnp.uint32(1), U32MAX)
        m0 = jnp.full(v, U32MAX, dtype=jnp.uint32)
        tup0 = (t0, m0)
    else:
        ts0 = jnp.where(active, S_UND, S_OUT).astype(jnp.uint8)
        tup0 = (ts0, jnp.zeros(v, dtype=jnp.uint32),
                jnp.arange(v, dtype=jnp.uint32),
                jnp.full(v, S_OUT, dtype=jnp.uint8),
                jnp.full(v, U32MAX, dtype=jnp.uint32),
                jnp.full(v, U32MAX, dtype=jnp.uint32))

    # iteration 0: wl1 = active rows, wl2 = every row (host-driver parity)
    state0 = (tup0, active, jnp.ones(v, dtype=bool), jnp.uint32(0))

    def cond(state):
        _, wl1_mask, _, it = state
        return jnp.any(wl1_mask) & (it < max_iters)

    def body(state):
        tup, wl1_mask, wl2_mask, it = state
        if packed:
            t, m = tup
            newt = pack(prio_fn(it, vids), vids, b)
            t = jnp.where(wl1_mask & is_undecided(t), newt, t)
            m = _refresh_cols_packed_csr(t, m, wl2_mask, edge_rows,
                                         edge_cols, v)
            t = _decide_packed_csr(t, m, wl1_mask, edge_rows, edge_cols,
                                   active, v)
            und = is_undecided(t)
            live = m != U32MAX
            tup = (t, m)
        else:
            ts, tr, ti, ms, mr, mi = tup
            prio = effective_priority(prio_fn(it, vids), b)
            tr = jnp.where(wl1_mask & (ts == S_UND), prio, tr)
            ms, mr, mi = _refresh_cols_unpacked_csr(
                ts, tr, ti, ms, mr, mi, wl2_mask, edge_rows, edge_cols, v)
            ts = _decide_unpacked_csr(ts, tr, ti, ms, mr, mi, wl1_mask,
                                      edge_rows, edge_cols, active, v)
            und = ts == S_UND
            live = ms != S_OUT
            tup = (ts, tr, ti, ms, mr, mi)
        return tup, und, live, it + jnp.uint32(1)

    tup, wl1_mask, _, it = jax.lax.while_loop(cond, body, state0)
    return tup[0], it, jnp.sum(wl1_mask, dtype=jnp.int32)


def _mis2_resident_impl(graph, active: Optional[np.ndarray] = None,
                        options: Optional[Mis2Options] = None, *,
                        pallas: bool = False,
                        interpret: Optional[bool] = None) -> Mis2Result:
    """Engine entry for ``compacted_resident`` / ``pallas_resident``.

    Exactly one jitted dispatch per solve (counted in
    ``HOTLOOP_STATS.resident_dispatches``); the only device->host transfer
    is the final result pull after the fixed point has converged.  Both
    engines update only §V-B's sets each round: the packed XLA ELL branch
    runs :func:`mis2_dense_fixed_point`, which applies them as masks over
    full-width rounds, the Pallas and unpacked branches as on-device index
    worklists.  On that packed XLA branch a caller's ``active`` mask is
    gathered into a per-slot table once per solve (counted in
    ``mis2.active_folds``); ``active=None`` skips that table.
    """
    options = Mis2Options() if options is None else options
    if not options.worklists:
        raise ValueError(
            "resident engines always restrict each round to §V-B's "
            "worklist sets (as masks or as index worklists); use "
            "engine='dense' (masked lanes) or the host-driven 'compacted' "
            "driver for the no-worklist ablation")
    gh = as_graph(graph)
    if pallas and not (options.layout == "ell" and options.packed):
        raise ValueError("pallas path requires packed tuples + ELL layout")

    if options.layout == "ell":
        v = gh.ell.num_vertices
    elif options.layout == "csr_segment":
        v = gh.num_vertices
    else:
        raise ValueError(options.layout)
    active_j = None if active is None else jnp.asarray(active)
    b = id_bits(v)

    with _obs_span("mis2.resident_fixed_point", layout=options.layout,
                   pallas=pallas, packed=options.packed, v=v) as sp:
        with _obs_span("mis2.launch"):
            if options.layout == "ell":
                t, it, n1 = _resident_ell_fixed_point(
                    gh.ell.neighbors, active_j, priority=options.priority,
                    packed=options.packed, max_iters=options.max_iters, b=b,
                    use_pallas=pallas, interpret=interpret)
                if options.packed and not pallas and active is not None:
                    _OBS.counter(ACTIVE_FOLDS).inc()
            else:
                edge_rows, edge_cols = gh.csr_edges
                if active_j is None:
                    active_j = jnp.ones(v, dtype=bool)
                t, it, n1 = _resident_csr_fixed_point(
                    edge_rows, edge_cols, active_j, priority=options.priority,
                    packed=options.packed, max_iters=options.max_iters, b=b,
                    v=v)
        _OBS.counter(HotLoopStats._DISPATCHES).inc()
        iterations = wait_rounds(sp, t, it, options.layout)

    with _obs_span("mis2.pull"):
        t_np = np.asarray(t)
        in_set = (t_np == np.uint32(IN)) if options.packed \
            else (t_np == S_IN)
        return Mis2Result(in_set, iterations, int(n1) == 0, num_compiles=1)


def wait_rounds(sp, t, it, layout: str) -> int:
    """Close a resident fixed point: wait for the device in a
    ``mis2.wait`` span (so the enclosing span covers device execution),
    then record the rounds run on ``sp`` and in ``mis2.rounds{layout}``."""
    with _obs_span("mis2.wait"):
        jax.block_until_ready(t)
    iterations = int(it)
    sp.annotate(iterations=iterations)
    _OBS.counter(ROUNDS, labels={"layout": layout}).inc(iterations)
    return iterations


# ===========================================================================
# engine dispatch (internal, warning-free) + legacy public entry points
# ===========================================================================

def run_mis2(graph, active=None, options: Optional[Mis2Options] = None,
             engine: str = "compacted",
             interpret: Optional[bool] = None,
             mesh=None, axis=None) -> Mis2Result:
    """Warning-free engine dispatch used by ``repro.api`` and by the other
    core pipelines (aggregation, partitioning).  Engines ``'compacted'``
    (host-driven §V-B worklists), ``'compacted_resident'`` (the same fixed
    point as one jitted ``while_loop``: masked full-width rounds on packed
    ELL, on-device index worklists on the unpacked ablation),
    ``'dense'`` (single jitted ``while_loop`` over masks), ``'pallas'`` /
    ``'pallas_resident'`` (the Pallas min-propagation kernels on the
    measured hot loop; the resident variant runs the fused single-row-read
    passes) and the sharded ``'distributed'``/``'distributed_single_gather'``
    (which honor ``mesh``/``axis``, defaulting to all attached devices)
    produce bit-identical sets for equal options."""
    options = Mis2Options() if options is None else options
    if engine == "dense":
        return _mis2_dense_impl(graph, active, options)
    if engine == "compacted":
        return _mis2_compacted_impl(graph, active, options,
                                    interpret=interpret)
    if engine == "pallas":
        return _mis2_compacted_impl(graph, active, options, pallas=True,
                                    interpret=interpret)
    if engine in ("compacted_resident", "pallas_resident"):
        return _mis2_resident_impl(graph, active, options,
                                   pallas=engine.startswith("pallas"),
                                   interpret=interpret)
    if engine == "pallas_hybrid":
        from .mis2_hybrid import _mis2_hybrid_impl
        return _mis2_hybrid_impl(graph, active, options, interpret=interpret)
    if engine in ("distributed", "distributed_single_gather"):
        from .dist import _mis2_distributed_impl
        return _mis2_distributed_impl(
            graph, active, options, mesh=mesh, axis=axis,
            single_gather=engine.endswith("single_gather"))
    raise ValueError(
        f"unknown mis2 engine {engine!r} (dense | compacted | "
        "compacted_resident | pallas | pallas_resident | pallas_hybrid | "
        "distributed | distributed_single_gather)")


def mis2(graph, active=None, options: Optional[Mis2Options] = None,
         engine: str = "compacted") -> Mis2Result:
    """Deprecated entry point — use :func:`repro.api.mis2`."""
    warn_deprecated("repro.core.mis2.mis2", "repro.api.mis2")
    return run_mis2(graph, active, options, engine)


def mis2_dense(graph, active: Optional[jnp.ndarray] = None,
               options: Optional[Mis2Options] = None) -> Mis2Result:
    """Deprecated entry point — use ``repro.api.mis2(..., engine="dense")``."""
    warn_deprecated("repro.core.mis2.mis2_dense",
                    'repro.api.mis2(..., engine="dense")')
    return _mis2_dense_impl(graph, active, options)


def mis2_compacted(graph, active: Optional[np.ndarray] = None,
                   options: Optional[Mis2Options] = None) -> Mis2Result:
    """Deprecated entry point — use ``repro.api.mis2`` (default engine)."""
    warn_deprecated("repro.core.mis2.mis2_compacted",
                    'repro.api.mis2(..., engine="compacted")')
    return _mis2_compacted_impl(graph, active, options)


# Fig. 2 cumulative ablation chain (benchmarks/fig2_optimizations.py)
ABLATION_CHAIN = {
    "baseline_bell": Mis2Options(priority="fixed", worklists=False,
                                 packed=False, layout="csr_segment"),
    "+rand_priority": Mis2Options(priority="xorshift_star", worklists=False,
                                  packed=False, layout="csr_segment"),
    "+worklists": Mis2Options(priority="xorshift_star", worklists=True,
                              packed=False, layout="csr_segment"),
    "+packed_status": Mis2Options(priority="xorshift_star", worklists=True,
                                  packed=True, layout="csr_segment"),
    "+simd_ell": Mis2Options(priority="xorshift_star", worklists=True,
                             packed=True, layout="ell"),
}
