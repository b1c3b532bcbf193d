"""Pallas TPU kernels for the MIS-2 hot loops (paper §V-D, TPU-adapted).

The paper's SIMD optimization reads each vertex's adjacency row with a
warp so loads coalesce.  The TPU analogue (DESIGN.md §3): rows live in
ELL layout and the closed-neighborhood min / forall / exists reductions
run on the VPU.  The paper's *worklist* optimization maps to
block-granular work skipping: the live worklist length is
scalar-prefetched into SMEM and grid blocks whose slot range lies entirely
past ``count`` skip their compute via ``pl.when`` — the TPU equivalent of
launching fewer thread blocks.

Tiling — column-major ELL tiles:
* every kernel input is a ``[D, W]`` tile stack: the ``D`` neighbor slots
  of a worklist row run down the sublanes and the ``W`` worklist slots
  run across the lanes, blocked ``[D, block]`` along the grid.  A
  closed-neighborhood reduction is then a sublane reduction whose result
  is already a lane-dense ``[1, block]`` row of the ``[1, W]`` output.
* the neighbor gathers (``T[nbrs]``, ``M[nbrs]``, ``active[nbrs]``) run
  as XLA gathers in the same jitted program, in front of the kernel:
  Mosaic gathers only within one vreg, so a gather from a ``[V]`` state
  vector cannot run in-kernel, and the ``[V]`` vectors never have to fit
  VMEM (V = 1M keeps 4 MB per state vector in HBM).
* Mosaic reduces signed integers only; :func:`_umin_rows` maps the
  unsigned tuple order onto the signed order by flipping the sign bit.

Validated with ``interpret=True`` on CPU against ref.py (bit-exact — all
integer math) and compiled for a described TPU v5e in
``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# hashing/tuples are leaf modules (jax/numpy only) — importing them here
# keeps the §V-A rank packing bit-identical to the XLA path without
# touching the core drivers (no import cycle: core imports kernels lazily)
from ...core.hashing import PRIORITY_FNS
from ...core.tuples import pack
from ...xla_ops import slot_major_rows
from .._interpret import resolve_interpret

IN = np.uint32(0)
OUT = np.uint32(0xFFFFFFFF)
_SIGN = np.uint32(0x80000000)

# worklist slots per grid step (the lane dim of a tile: a multiple of 128)
BLOCK_ROWS = 256
# The fused resident passes run their whole grid inside a lax.while_loop,
# so per-grid-step overhead is paid every round; a larger block amortizes
# it (512 slots x D neighbor tuples x 4 B is ~16 KB per tile at D=8).
FUSED_BLOCK_ROWS = 512


def _umin_rows(x):
    """Unsigned min over the sublanes of a uint32 tile -> ``[1, B]``."""
    key = jax.lax.bitcast_convert_type(x ^ _SIGN, jnp.int32)
    mn = jnp.min(key, axis=0, keepdims=True)
    return jax.lax.bitcast_convert_type(mn, jnp.uint32) ^ _SIGN


def _any_rows(pred):
    """Exists over the sublanes of a bool tile -> ``[1, B]`` bool."""
    return jnp.max(pred.astype(jnp.int32), axis=0, keepdims=True) > 0


def _refresh_kernel(count_ref, tn_ref, m_ref):
    """One grid step: M[block] = poisoned closed-neighborhood min of T.

    ``tn`` holds the neighbor tuples already gathered (and, on the fused
    path, already §V-A-refreshed) for this block of worklist slots."""
    i = pl.program_id(0)
    block = m_ref.shape[1]

    @pl.when(i * block < count_ref[0])          # §V-B: skip dead blocks
    def _():
        mv = _umin_rows(tn_ref[...])
        m_ref[...] = jnp.where(mv == IN, OUT, mv)

    @pl.when(i * block >= count_ref[0])
    def _():
        m_ref[...] = jnp.full(m_ref.shape, OUT, dtype=jnp.uint32)


def _decide_kernel(count_ref, told_ref, tv_ref, mn_ref, an_ref, out_ref):
    """One grid step: decide IN/OUT for a block of worklist slots.

    ``told`` is the slot's stored tuple, ``tv`` the tuple the decision
    compares against (the §V-A-refreshed one on the fused path), ``mn`` /
    ``an`` the neighbors' M values and active flags."""
    i = pl.program_id(0)
    block = out_ref.shape[1]

    @pl.when(i * block < count_ref[0])
    def _():
        tv_old = told_ref[...]                  # [1, B]
        tv = tv_ref[...]                        # [1, B]
        mn = mn_ref[...]                        # [D, B]
        an = an_ref[...] != 0                   # [D, B]
        any_out = _any_rows(an & (mn == OUT))
        any_neq = _any_rows(an & (mn != tv))
        newt = jnp.where(any_out, OUT, jnp.where(any_neq, tv, IN))
        und = (tv_old != IN) & (tv_old != OUT)
        out_ref[...] = jnp.where(und, newt, tv_old)

    @pl.when(i * block >= count_ref[0])
    def _():
        out_ref[...] = told_ref[...]


def _tiled_call(kernel, count, tiles, *, block_rows: int, interpret: bool):
    """Run ``kernel`` over ``[D_k, W]`` tiles blocked ``[D_k, block]``
    along the worklist slots; returns the ``[W]`` uint32 output row."""
    w = tiles[0].shape[1]
    block = w if w <= block_rows else block_rows
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(w, block),),
            in_specs=[pl.BlockSpec((x.shape[0], block), lambda i, *_: (0, i))
                      for x in tiles],
            out_specs=pl.BlockSpec((1, block), lambda i, *_: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, w), jnp.uint32),
        interpret=interpret,
    )(jnp.asarray(count, jnp.int32).reshape(1), *tiles)[0]


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def refresh_columns_pallas(t: jnp.ndarray, wl_neighbors: jnp.ndarray,
                           count: jnp.ndarray, *, interpret=None,
                           block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """M values for the (padded) worklist rows. Rows >= count return OUT."""
    tn = t[wl_neighbors.T]                      # [D, W]
    return _tiled_call(_refresh_kernel, count, [tn], block_rows=block_rows,
                       interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def decide_pallas(t_rows: jnp.ndarray, m: jnp.ndarray, active: jnp.ndarray,
                  wl_neighbors: jnp.ndarray, count: jnp.ndarray, *,
                  interpret=None,
                  block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """New T values for the (padded) worklist rows; rows >= count keep
    their ``t_rows`` value."""
    nb = wl_neighbors.T                         # [D, W]
    trow = t_rows[None, :]
    return _tiled_call(_decide_kernel, count,
                       [trow, trow, m[nb], active[nb].astype(jnp.int32)],
                       block_rows=block_rows,
                       interpret=resolve_interpret(interpret))


# ===========================================================================
# fused passes for the device-resident engines (``pallas_resident``,
# ``pallas_hybrid``)
#
# The host-driven round materializes each live row's ELL entries as a
# ``[W, D]`` worklist copy in its own dispatch, then gathers T through the
# copy.  The fused wrappers take the worklist *indices* plus the
# adjacency, so the row gather, the T/M gathers and the kernel trace into
# one program with the resident while_loop.  The §V-A rank packing is
# folded into the same gather: an undecided tuple's refreshed value is a
# pure function of (vertex id, round), so instead of a separate
# refresh_rows scatter pass the gather recomputes it on the fly for every
# gathered neighbor.  The stored T is only written once per round, by the
# decide scatter — and because decide writes the refreshed tuple for
# still-undecided rows, the stored state after each round is bit-identical
# to the three-pass host pipeline.
#
# One wrapper pair serves both layouts: ``neighbors`` is either the
# monolithic ``[V, D]`` ELL (worklist slots index global rows) or one
# degree-bucket slab ``[R, W]`` of the hybrid layout (slots index slab
# rows and ``gids`` carries the matching global ids, because the refresh
# packing and the T read are keyed by global id).
# ===========================================================================

def _refresh_inline(t_vals, ids, it, priority: str, b: int):
    """T after the §V-A row refresh, recomputed from ids instead of memory.

    ``wl1`` is exactly the undecided set, so ``refresh_rows`` is the pure
    map ``t -> undecided(t) ? pack(prio(it, id), id) : t`` — no pass
    over stored T needed.
    """
    fresh = pack(PRIORITY_FNS[priority](it, ids), ids, b)
    und = (t_vals != IN) & (t_vals != OUT)
    return jnp.where(und, fresh, t_vals)


@functools.partial(jax.jit, static_argnames=("priority", "b", "interpret",
                                             "block_rows"))
def fused_refresh_columns_pallas(t: jnp.ndarray, neighbors: jnp.ndarray,
                                 wl: jnp.ndarray, count: jnp.ndarray,
                                 it: jnp.ndarray, *, priority: str, b: int,
                                 interpret=None,
                                 block_rows: int = FUSED_BLOCK_ROWS) -> jnp.ndarray:
    """Fused refresh_rows+refresh_columns: M values per worklist slot.

    ``wl`` is a sentinel-padded index buffer over the rows of
    ``neighbors`` (the resident drivers' fixed-shape worklist); ``count``
    may be traced — it reaches the kernel via scalar prefetch, so block
    skipping follows the *live* worklist length with no host involvement.
    ``t`` is always the global ``[V]`` state.
    """
    nb = slot_major_rows(neighbors, wl)
    tn = _refresh_inline(t[nb], nb.astype(jnp.uint32),
                         jnp.asarray(it, jnp.uint32), priority, b)
    return _tiled_call(_refresh_kernel, count, [tn], block_rows=block_rows,
                       interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("priority", "b", "interpret",
                                             "block_rows"))
def fused_decide_pallas(t: jnp.ndarray, m: jnp.ndarray, active: jnp.ndarray,
                        neighbors: jnp.ndarray, wl: jnp.ndarray,
                        count: jnp.ndarray, it: jnp.ndarray, gids=None, *,
                        priority: str, b: int, interpret=None,
                        block_rows: int = FUSED_BLOCK_ROWS) -> jnp.ndarray:
    """Fused row-gather+decide: new T values per worklist slot.

    ``gids`` maps each slot to its global row when ``neighbors`` is a
    slab (``None``: the slots index global rows); the driver scatters the
    output back into T at those ids with drop semantics."""
    v = t.shape[0]
    g = jnp.clip(wl if gids is None else gids, 0, v - 1)
    tv_old = t[g]
    tv = _refresh_inline(tv_old, g.astype(jnp.uint32),
                         jnp.asarray(it, jnp.uint32), priority, b)
    nb = slot_major_rows(neighbors, wl)
    return _tiled_call(
        _decide_kernel, count,
        [tv_old[None, :], tv[None, :], m[nb], active[nb].astype(jnp.int32)],
        block_rows=block_rows, interpret=resolve_interpret(interpret))


# VMEM budget per worklist block in slab entries (rows x width); the block
# row count adapts to the slice width so wide slices don't blow the tile.
SLICE_BLOCK_ENTRIES = FUSED_BLOCK_ROWS * 8


def slice_block_rows(num_rows: int, width: int, interpret: bool) -> int:
    """Worklist block size for one slice.  Interpret mode executes grid
    steps as a sequential host scan, so it takes the whole slice as one
    block; compiled mode tiles to ~SLICE_BLOCK_ENTRIES slab entries, in
    whole 128-lane columns."""
    if interpret:
        return max(1, num_rows)
    lanes = SLICE_BLOCK_ENTRIES // max(width, 1) // 128 * 128
    return max(128, lanes)
