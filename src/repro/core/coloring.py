"""Deterministic parallel greedy distance-1 coloring.

Used by cluster multicolor Gauss-Seidel (paper Alg. 4) to color the coarse
graph, and by point multicolor GS to color the fine graph.  Luby-style
rounds with the same xorshift* priorities as MIS-2: in each round, every
uncolored vertex that holds the minimum packed tuple among its uncolored
neighbors picks its smallest feasible color.  Deterministic across devices
and runs, like everything else in core/.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops
from .._compat import warn_deprecated
from ..graphs.handle import as_ell_graph
from .hashing import priorities_xorshift_star
from .tuples import id_bits, pack

MAX_COLORS = 64


@dataclass
class ColoringResult:
    colors: np.ndarray      # int32 [V]; uncolored (unconverged) hold -1
    num_colors: int
    rounds: int
    converged: bool = True  # every vertex colored within the round limit

    def __post_init__(self):
        # Result-protocol guarantee: host numpy payloads on every engine.
        self.colors = np.asarray(self.colors)


def _color_round_rows(neighbors_rows, mask_rows, row_ids, colors, prio):
    """Rowwise body of one Luby round over a row block: ``colors`` and
    ``prio`` are global ``[V]`` vectors, the adjacency covers just the
    block's rows.  Shared verbatim by the monolithic round and the hybrid
    (sliced) round, which is what keeps their colors bit-identical."""
    uncolored = colors < 0
    own = colors[row_ids]
    # local-min among uncolored real neighbors (excluding self)
    real = mask_rows & (neighbors_rows != row_ids[:, None])
    pn = xla_ops.gather_rows(prio, neighbors_rows)
    un = xla_ops.gather_rows(uncolored, neighbors_rows)
    contender = real & un
    is_min = jnp.all(jnp.where(contender, prio[row_ids][:, None] < pn, True),
                     axis=1)
    # forbidden colors bitmask (two uint32 words -> up to 64 colors)
    cn = xla_ops.gather_rows(colors, neighbors_rows)
    has = real & (cn >= 0)
    lo_bits = jnp.where(has & (cn < 32),
                        jnp.uint32(1) << jnp.clip(cn, 0, 31).astype(jnp.uint32),
                        jnp.uint32(0))
    hi_bits = jnp.where(has & (cn >= 32),
                        jnp.uint32(1) << jnp.clip(cn - 32, 0, 31).astype(jnp.uint32),
                        jnp.uint32(0))
    forb_lo = jnp.bitwise_or.reduce(lo_bits, axis=1)
    forb_hi = jnp.bitwise_or.reduce(hi_bits, axis=1)
    chosen = _smallest_free_color(forb_lo, forb_hi)
    return jnp.where((own < 0) & is_min, chosen, own)


def _smallest_free_color(forb_lo, forb_hi):
    """Smallest color whose bit is clear in the 64-bit forbidden mask."""
    free_lo = ~forb_lo
    low_idx = _lowest_set_bit(free_lo)
    free_hi = ~forb_hi
    high_idx = _lowest_set_bit(free_hi) + 32
    return jnp.where(free_lo != 0, low_idx, high_idx).astype(jnp.int32)


def _round_priorities(v: int, rnd, b):
    vids = jnp.arange(v, dtype=jnp.uint32)
    return pack(priorities_xorshift_star(rnd, vids), vids, b)


def _color_round_masked(neighbors, mask, colors, rnd, b):
    """One Luby round; ``b`` is a traced uint32 scalar so the function is
    vmappable over padded ``[B, rows, deg]`` buckets (each graph keeps its
    own ``b = id_bits(V_real)``, preserving single-graph priorities).
    Padded rows must enter pre-colored so they are never contenders."""
    v = neighbors.shape[0]
    prio = _round_priorities(v, rnd, b)
    row_ids = jnp.arange(v, dtype=neighbors.dtype)
    return _color_round_rows(neighbors, mask, row_ids, colors, prio)


@jax.jit
def _color_round(neighbors, mask, colors, rnd):
    b = jnp.uint32(id_bits(neighbors.shape[0]))
    return _color_round_masked(neighbors, mask, colors, rnd, b)


def _lowest_set_bit(x: jnp.ndarray) -> jnp.ndarray:
    """Index of lowest set bit of uint32 (x != 0 assumed where used)."""
    isolated = x & (~x + jnp.uint32(1))
    f = isolated.astype(jnp.float32)
    exp = (jax.lax.bitcast_convert_type(f, jnp.uint32) >> jnp.uint32(23)) - jnp.uint32(127)
    return exp.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def _color_fixed_point(neighbors, mask, max_rounds: int):
    """Device-resident Luby round loop: one jitted ``lax.while_loop``
    instead of a per-round host sync of ``colors`` (the hot-loop pattern
    shared with the resident MIS-2 engines).  Round-for-round identical to
    the old host-driven loop, including its do-while shape (at least one
    round always runs)."""
    v = neighbors.shape[0]
    b = jnp.uint32(id_bits(v))
    colors0 = jnp.full(v, -1, dtype=jnp.int32)

    def cond(state):
        colors, rnd = state
        return (rnd == 0) | (jnp.any(colors < 0) & (rnd < max_rounds))

    def body(state):
        colors, rnd = state
        colors = _color_round_masked(neighbors, mask, colors,
                                     rnd.astype(jnp.uint32), b)
        return colors, rnd + jnp.int32(1)

    return jax.lax.while_loop(cond, body, (colors0, jnp.int32(0)))


def _spill_color_round(spill_rows, spill_seg, spill_cols, colors, prio):
    """Spill-side Luby round: the heavy rows' slots live in sorted COO, so
    the rowwise reductions become segment reductions.  Bit-matches
    :func:`_color_round_rows` on the same rows: ``all(own < pn)`` over
    contenders is ``own < segment_min(pn)`` (vacuously true on empty
    segments), and the forbidden-color OR becomes a one-hot scatter-max
    summed against distinct powers of two (sum == OR for distinct bits)."""
    h = spill_rows.shape[0]
    own = colors[spill_rows]
    prio_own = prio[spill_rows]
    cn = colors[spill_cols]
    pn = prio[spill_cols]
    real = spill_cols != spill_rows[spill_seg]
    contender = real & (cn < 0)
    n_cont = jax.ops.segment_sum(contender.astype(jnp.int32), spill_seg,
                                 num_segments=h)
    min_pn = jax.ops.segment_min(
        jnp.where(contender, pn, jnp.uint32(0xFFFFFFFF)), spill_seg,
        num_segments=h)
    is_min = (n_cont == 0) | (prio_own < min_pn)
    has = real & (cn >= 0)
    onehot = jnp.zeros((h, MAX_COLORS), dtype=jnp.bool_)
    onehot = onehot.at[spill_seg, jnp.clip(cn, 0, MAX_COLORS - 1)].max(has)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    forb_lo = jnp.sum(jnp.where(onehot[:, :32], weights[None, :],
                                jnp.uint32(0)), axis=1, dtype=jnp.uint32)
    forb_hi = jnp.sum(jnp.where(onehot[:, 32:], weights[None, :],
                                jnp.uint32(0)), axis=1, dtype=jnp.uint32)
    chosen = _smallest_free_color(forb_lo, forb_hi)
    return jnp.where((own < 0) & is_min, chosen, own)


@functools.partial(jax.jit, static_argnames=("v", "max_rounds"))
def _color_fixed_point_hybrid(slices, spill_rows, spill_seg, spill_cols,
                              v: int, max_rounds: int):
    """Hybrid-layout twin of :func:`_color_fixed_point`: one resident
    ``while_loop``, each round touching every slice slab plus the COO
    spill.  All reads within a round come from the frozen round-start
    ``colors``; writes land in a fresh buffer — the slice/spill partition
    is disjoint and covering, so the round is exactly the monolithic
    round's gather/update evaluated piecewise."""
    b = jnp.uint32(id_bits(v))
    colors0 = jnp.full(v, -1, dtype=jnp.int32)
    h = spill_rows.shape[0]

    def cond(state):
        colors, rnd = state
        return (rnd == 0) | (jnp.any(colors < 0) & (rnd < max_rounds))

    def body(state):
        colors, rnd = state
        prio = _round_priorities(v, rnd.astype(jnp.uint32), b)
        new_colors = colors
        for sl in slices:
            vals = _color_round_rows(sl.neighbors, sl.mask, sl.rows,
                                     colors, prio)
            new_colors = new_colors.at[sl.rows].set(vals)
        if h > 0:
            vals = _spill_color_round(spill_rows, spill_seg, spill_cols,
                                      colors, prio)
            new_colors = new_colors.at[spill_rows].set(vals)
        return new_colors, rnd + jnp.int32(1)

    return jax.lax.while_loop(cond, body, (colors0, jnp.int32(0)))


def _coloring_result(colors, rounds) -> ColoringResult:
    c = np.asarray(colors)
    rnd = int(rounds)
    num = int(c.max()) + 1 if (c >= 0).any() else 0
    if num > MAX_COLORS:
        raise RuntimeError(f"{num} colors exceed MAX_COLORS={MAX_COLORS}")
    # hitting max_rounds is reported, not raised: callers get the partial
    # coloring (uncolored vertices = -1) with converged=False
    return ColoringResult(c, num, rnd, converged=not (c < 0).any())


def _color_graph_impl(graph, max_rounds: int = 256) -> ColoringResult:
    ell = as_ell_graph(graph)
    colors, rounds = _color_fixed_point(ell.neighbors, ell.mask, max_rounds)
    return _coloring_result(colors, rounds)


def _color_hybrid_impl(graph, max_rounds: int = 256) -> ColoringResult:
    """Luby coloring over the degree-aware hybrid layout (sliced-ELL +
    COO spill).  Never materializes the monolithic padded ELL, so it runs
    on skewed graphs whose ``.ell`` would blow the byte budget; colors are
    bit-identical to the ``luby`` engine's."""
    from ..graphs.handle import as_graph

    gh = as_graph(graph)
    hyb = gh.hybrid()
    colors, rounds = _color_fixed_point_hybrid(
        tuple(hyb.slices), hyb.spill_rows, hyb.spill_seg, hyb.spill_cols,
        gh.num_vertices, max_rounds)
    return _coloring_result(colors, rounds)


def color_graph(graph, max_rounds: int = 256) -> ColoringResult:
    """Deprecated entry point — use :func:`repro.api.color`."""
    warn_deprecated("repro.core.coloring.color_graph", "repro.api.color")
    return _color_graph_impl(graph, max_rounds)


def check_coloring(graph, colors: np.ndarray) -> bool:
    """Validity: no two adjacent distinct vertices share a color."""
    ell = as_ell_graph(graph)
    nbrs = np.asarray(ell.neighbors)
    mask = np.asarray(ell.mask)
    v = nbrs.shape[0]
    self_ids = np.arange(v)[:, None]
    real = mask & (nbrs != self_ids)
    return not (real & (colors[nbrs] == colors[:, None])).any()
