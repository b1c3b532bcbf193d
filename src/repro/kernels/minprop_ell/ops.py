"""Jitted wrappers wiring the Pallas min-propagation kernels into the
compacted MIS-2 driver (core/mis2.py, engine ``"pallas"``).

The XLA side does the irregular parts (worklist row gather, the
neighbor-tuple gathers, scatter-back); the Pallas kernels do the
closed-neighborhood reductions and the IN/OUT decision over the gathered
``[D, W]`` tiles (see ``kernel.py`` for why the gathers cannot run
in-kernel on the TPU).

``interpret=None`` (the default) defers to the :class:`repro.api.Backend`
policy: interpret only when no accelerator is attached.  The seed
hard-coded ``interpret=True``, silently running the Pallas interpreter
even on TPU/GPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .._interpret import resolve_interpret as _resolve_interpret
from .kernel import (
    _refresh_inline,
    decide_pallas,
    fused_decide_pallas,
    fused_refresh_columns_pallas,
    refresh_columns_pallas,
    slice_block_rows,
)

IN = np.uint32(0)
OUT = np.uint32(0xFFFFFFFF)


@jax.jit
def _gather_rows(neighbors, wl):
    v = neighbors.shape[0]
    return neighbors[jnp.clip(wl, 0, v - 1)]


def refresh_columns(t, m, wl2, neighbors, count, *, interpret=None):
    """M.at[wl2] <- poisoned min of T over wl2 rows' closed neighborhoods."""
    wl_nbrs = _gather_rows(neighbors, wl2)
    mv = refresh_columns_pallas(t, wl_nbrs, jnp.asarray(count, jnp.int32),
                                interpret=_resolve_interpret(interpret))
    return m.at[wl2].set(mv, mode="drop")


def decide(t, m, wl1, neighbors, active, count, *, interpret=None):
    """T.at[wl1] <- IN/OUT decision for wl1 rows."""
    v = neighbors.shape[0]
    wl_nbrs = _gather_rows(neighbors, wl1)
    t_rows = t[jnp.clip(wl1, 0, v - 1)]
    newt = decide_pallas(t_rows, m, active, wl_nbrs,
                         jnp.asarray(count, jnp.int32),
                         interpret=_resolve_interpret(interpret))
    return t.at[wl1].set(newt, mode="drop")


# ---------------------------------------------------------------------------
# fused wrappers for the device-resident driver: worklist *indices* go in
# (no pre-gathered [W, D] row copies), counts may be traced (they feed the
# pl.when block skipping via scalar prefetch inside a lax.while_loop)
# ---------------------------------------------------------------------------

def fused_refresh_columns(t, m, wl2, count, neighbors, it, *, priority: str,
                          b: int, interpret=None):
    """M.at[wl2] <- poisoned min over wl2 rows' closed neighborhoods, with
    the §V-A row refresh applied to the gathered tuples on the fly."""
    mv = fused_refresh_columns_pallas(
        t, neighbors, wl2, jnp.asarray(count, jnp.int32),
        jnp.asarray(it, jnp.uint32), priority=priority, b=b,
        interpret=_resolve_interpret(interpret))
    return m.at[wl2].set(mv, mode="drop")


def fused_decide(t, m, wl1, count, neighbors, active, it, *, priority: str,
                 b: int, interpret=None):
    """T.at[wl1] <- IN/OUT decision, row tuple gather + refresh fused.

    Because still-undecided rows get their *refreshed* tuple written back,
    this single scatter leaves T exactly as the host pipeline's
    refresh_rows + decide pair would."""
    newt = fused_decide_pallas(
        t, m, active, neighbors, wl1,
        jnp.asarray(count, jnp.int32), jnp.asarray(it, jnp.uint32),
        priority=priority, b=b, interpret=_resolve_interpret(interpret))
    return t.at[wl1].set(newt, mode="drop")


# ---------------------------------------------------------------------------
# hybrid-layout passes (``pallas_hybrid``): the fused passes per slice over
# the sliced-ELL slabs + XLA segment reductions over the sorted-COO spill.
# All of these trace inside the hybrid resident while_loop; the slice
# worklists are slice-local (sentinel R_i) and every write back into the
# global [V] state goes through a global-id scatter with drop semantics.
# ---------------------------------------------------------------------------

def _slice_gids(slice_rows, wl, v: int):
    """Worklist slots -> global scatter targets (sentinel slots -> V,
    dropped by ``mode='drop'``)."""
    r = slice_rows.shape[0]
    return jnp.where(wl < r, slice_rows[jnp.clip(wl, 0, r - 1)],
                     jnp.int32(v))


def sliced_refresh_columns(t, m, slice_rows, neighbors, wl, count, it, *,
                           priority: str, b: int, interpret=None,
                           block_rows=None):
    """M.at[slice rows on the worklist] <- poisoned closed-neighborhood min
    (the fused refresh, restricted to one degree-bucket slab)."""
    interp = _resolve_interpret(interpret)
    if block_rows is None:
        block_rows = slice_block_rows(*neighbors.shape, interp)
    mv = fused_refresh_columns_pallas(
        t, neighbors, wl, jnp.asarray(count, jnp.int32),
        jnp.asarray(it, jnp.uint32), priority=priority, b=b,
        interpret=interp, block_rows=block_rows)
    gids = _slice_gids(slice_rows, wl, t.shape[0])
    return m.at[gids].set(mv, mode="drop")


def sliced_decide(t, m, active, slice_rows, neighbors, wl, count, it, *,
                  priority: str, b: int, interpret=None, block_rows=None):
    """T.at[slice rows on the worklist] <- IN/OUT decision (fused decide,
    restricted to one slab; global row ids ride alongside the worklist)."""
    interp = _resolve_interpret(interpret)
    if block_rows is None:
        block_rows = slice_block_rows(*neighbors.shape, interp)
    gids = _slice_gids(slice_rows, wl, t.shape[0])
    newt = fused_decide_pallas(
        t, m, active, neighbors, wl, jnp.asarray(count, jnp.int32),
        jnp.asarray(it, jnp.uint32), gids, priority=priority, b=b,
        interpret=interp, block_rows=block_rows)
    return t.at[gids].set(newt, mode="drop")


def spill_refresh_columns(t, m, spill_rows, spill_seg, spill_cols, live, it,
                          *, priority: str, b: int):
    """M over the heavy (COO-spill) rows via segment_min — same closed
    min + IN->OUT poison as the slab kernels, with the §V-A refresh applied
    to every gathered tuple on the fly.  ``live`` is the [V] round mask;
    rows off the worklist keep their previous M (the worklist contract)."""
    h = spill_rows.shape[0]
    it = jnp.asarray(it, jnp.uint32)
    te = _refresh_inline(t[spill_cols], spill_cols.astype(jnp.uint32), it,
                         priority, b)
    mv = jax.ops.segment_min(te, spill_seg, num_segments=h)
    tself = _refresh_inline(t[spill_rows], spill_rows.astype(jnp.uint32), it,
                            priority, b)
    mv = jnp.minimum(mv, tself)                # closed neighborhood
    mv = jnp.where(mv == IN, OUT, mv)
    newm = jnp.where(live[spill_rows], mv, m[spill_rows])
    return m.at[spill_rows].set(newm)


def spill_decide(t, m, active, spill_rows, spill_seg, spill_cols, it, *,
                 priority: str, b: int):
    """IN/OUT decision over the heavy rows via segment reductions,
    bit-matching the fused slab decide: neighbor terms gated by ``active``
    (padding-slot semantics), the self term folded in explicitly, and
    still-undecided rows written with their refreshed tuple."""
    h = spill_rows.shape[0]
    it = jnp.asarray(it, jnp.uint32)
    tv_old = t[spill_rows]
    tv = _refresh_inline(tv_old, spill_rows.astype(jnp.uint32), it,
                         priority, b)
    mn = m[spill_cols]
    an = active[spill_cols]
    tv_e = tv[spill_seg]
    any_out = jax.ops.segment_max(
        (an & (mn == OUT)).astype(jnp.int32), spill_seg, num_segments=h) > 0
    neq = jax.ops.segment_max(
        (an & (mn != tv_e)).astype(jnp.int32), spill_seg, num_segments=h) > 0
    m_self = m[spill_rows]
    a_self = active[spill_rows]
    any_out = any_out | (a_self & (m_self == OUT))
    neq = neq | (a_self & (m_self != tv))
    newt = jnp.where(any_out, OUT, jnp.where(~neq, IN, tv))
    und = (tv_old != IN) & (tv_old != OUT)
    return t.at[spill_rows].set(jnp.where(und, newt, tv_old))
