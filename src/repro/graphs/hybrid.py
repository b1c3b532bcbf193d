"""Degree-aware hybrid sparse layout: sliced ELL + sorted-COO spill.

The padded ELL layout (``csr.ELLGraph``) pads *every* row to the max
degree.  On bounded-degree meshes (laplace3d) that wastes nothing; on a
power-law graph at paper scale one hub row of degree ~V^(1/(a-1)) forces a
``[V, max_degree]`` slab that cannot even be allocated (`Graph.ell` raises
:class:`LayoutOverflowError` past :data:`ELL_BYTE_LIMIT`).  TC-MIS and the
SELL-C-sigma family solve this with degree bucketing; this module is the
TPU-shaped version:

* rows are sorted into a small pow2 width ladder (8, 16, 32, ... up to
  the spill cap); each bucket becomes one **slice**: a ``[R_i, W_i]`` ELL
  slab padded only to its own bucket width, plus the global row ids that
  own the slab rows.  Kernels dispatch once per slice — compile count is
  O(#slices), not O(#distinct pow2 shapes) — and padding waste is bounded
  by 2x per slice instead of max_degree/avg_degree overall.
* rows past the **spill cap** (the heavy hitters that make padded ELL
  explode) go to a sorted-COO segment (``spill_rows``/``spill_seg``/
  ``spill_cols``) consumed by segment reductions — O(E_spill) work with
  zero padding, the right shape for a handful of huge rows.

Padding convention matches ``csr.ELLGraph``: padded slab slots hold the
row's own **global** vertex id with ``mask == False``, so closed-
neighborhood reductions (the MIS-2 min / forall / exists) are
semantically inert over padding and the Pallas kernels never need to
read the mask.

Memory thresholds (module-level so tests can monkeypatch them):

* :data:`ELL_BYTE_LIMIT` — hard cap: ``Graph.ell`` / ``Graph.padded_ell``
  raise :class:`LayoutOverflowError` instead of attempting an allocation
  whose bytes estimate exceeds it (the seed's behaviour was an opaque
  host OOM mid-``np.repeat``).
* :data:`HYBRID_AUTO_BYTES` — auto-selection: ``repro.api.mis2`` with
  ``engine=None`` routes to ``pallas_hybrid`` once the padded-ELL bytes
  estimate crosses this threshold (see ``api.backend.default_mis2_engine``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .csr import CSRGraph, to_device

Array = jnp.ndarray

# int32 neighbor id + bool mask byte per ELL slot
ELL_BYTES_PER_SLOT = 5

#: hard allocation cap for the monolithic padded-ELL formats (2 GiB)
ELL_BYTE_LIMIT = 2 * 1024 ** 3

#: auto-selection threshold: engine=None prefers the hybrid layout once
#: the padded-ELL estimate crosses this (256 MiB)
HYBRID_AUTO_BYTES = 256 * 1024 ** 2

#: smallest slice width of the default pow2 ladder
MIN_SLICE_WIDTH = 8


class LayoutOverflowError(MemoryError):
    """A monolithic padded-ELL materialization was refused *before*
    allocation: the ``[V, max_degree]`` bytes estimate exceeds the
    configured limit.  The message names the degree-aware alternative
    (``mis2: pallas_hybrid`` over :class:`HybridEllGraph`), which handles
    exactly the skewed graphs that trip this."""

    def __init__(self, estimate: int, limit: int, v: int, max_degree: int):
        self.estimate = int(estimate)
        self.limit = int(limit)
        super().__init__(
            f"padded ELL [{v} x {max_degree}] needs ~{estimate:,} bytes "
            f"(> limit {limit:,}): the max-degree padding of a skewed graph "
            f"blows out memory before the solve starts. Use the hybrid "
            f"layout instead (engine='pallas_hybrid' / Graph.hybrid(): "
            f"sliced ELL + COO spill, O(E) memory), or raise "
            f"repro.graphs.hybrid.ELL_BYTE_LIMIT if the allocation is "
            f"intentional.")


def ell_bytes_estimate(num_vertices: int, max_degree: int) -> int:
    """Bytes a monolithic padded-ELL graph would allocate (neighbors int32
    + mask byte), without touching any adjacency data."""
    return int(num_vertices) * int(max_degree) * ELL_BYTES_PER_SLOT


class HybridSlice(NamedTuple):
    """One degree bucket: global row ids + an ELL slab padded to the
    bucket width.  ``neighbors[j]`` are the (global-id) neighbors of
    vertex ``rows[j]``; padded slots hold ``rows[j]`` itself, mask False."""

    rows: Array       # int32 [R]   global vertex ids (ascending)
    neighbors: Array  # int32 [R, W] global neighbor ids
    mask: Array       # bool  [R, W]

    @property
    def num_rows(self) -> int:
        return int(self.neighbors.shape[0])

    @property
    def width(self) -> int:
        return int(self.neighbors.shape[1])


class HybridEllGraph(NamedTuple):
    """Sliced-ELL + sorted-COO spill decomposition of one graph.

    Every vertex appears in exactly one slice or in the spill, so scatter
    targets are disjoint and per-row reductions are complete within their
    partition — which is what makes the hybrid MIS-2 / coloring /
    coarsening passes bit-identical to the monolithic ELL engines.
    """

    slices: tuple          # tuple[HybridSlice, ...], ascending widths
    spill_rows: Array      # int32 [H] heavy vertex ids (ascending)
    spill_seg: Array       # int32 [S] index into spill_rows per COO entry
    spill_cols: Array      # int32 [S] neighbor ids (CSR order: sorted)
    num_vertices: int
    spill_cap: int         # rows with degree > spill_cap went to the spill

    @property
    def num_slices(self) -> int:
        return len(self.slices)

    @property
    def slice_widths(self) -> tuple:
        return tuple(s.width for s in self.slices)

    @property
    def num_spill_rows(self) -> int:
        return int(self.spill_rows.shape[0])

    @property
    def num_spill_entries(self) -> int:
        return int(self.spill_cols.shape[0])

    @property
    def padded_bytes(self) -> int:
        """Bytes the slabs + spill actually hold (the number the padded
        monolith is compared against)."""
        slab = sum(s.num_rows * s.width for s in self.slices)
        return slab * ELL_BYTES_PER_SLOT + self.num_spill_entries * 2 * 4

    @property
    def padding_ratio(self) -> float:
        """Padded slab slots / real entries (1.0 = no waste); the spill
        segment is unpadded by construction."""
        padded = sum(s.num_rows * s.width for s in self.slices)
        real = sum(int(np.asarray(s.mask).sum()) for s in self.slices)
        real += self.num_spill_entries
        return (padded + self.num_spill_entries) / max(1, real)


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def default_spill_cap(degrees: np.ndarray) -> int:
    """Spill-cap policy: the smallest pow2 >= 4x the mean degree (floor
    :data:`MIN_SLICE_WIDTH`).  Rows above it are the heavy hitters whose
    padding the slices must not pay; on bounded-degree meshes (max <=
    cap) the spill is empty and the layout degenerates to sliced ELL."""
    if len(degrees) == 0:
        return MIN_SLICE_WIDTH
    mean = float(degrees.mean())
    return max(MIN_SLICE_WIDTH, _next_pow2(int(np.ceil(4.0 * max(1.0, mean)))))


def slice_width_ladder(max_slab_degree: int,
                       min_width: int = MIN_SLICE_WIDTH) -> tuple:
    """Pow2 width ladder ``min_width, 2*min_width, ...`` covering every
    non-spill degree; the top rung is clamped to the actual max slab
    degree so a bounded-degree graph pays no ladder overshoot."""
    widths = []
    w = min_width
    while w < max_slab_degree:
        widths.append(w)
        w *= 2
    widths.append(min(w, max(max_slab_degree, min_width)))
    return tuple(widths)


def _build_slab(sel: np.ndarray, deg: np.ndarray, indptr: np.ndarray,
                indices: np.ndarray, width: int) -> HybridSlice:
    """Vectorized slab assembly for the selected rows, on the host (no
    per-row loop — this runs at V=1M)."""
    r = len(sel)
    nbrs = np.repeat(sel.astype(np.int32)[:, None], width, axis=1)
    mask = np.zeros((r, width), dtype=bool)
    dsel = deg[sel].astype(np.int64)
    flat_rows = np.repeat(np.arange(r), dsel)
    slot = np.arange(int(dsel.sum()), dtype=np.int64) \
        - np.repeat(np.cumsum(dsel) - dsel, dsel)
    src = np.repeat(indptr[sel].astype(np.int64), dsel) + slot
    nbrs[flat_rows, slot] = indices[src]
    mask[flat_rows, slot] = True
    return HybridSlice(sel.astype(np.int32), nbrs, mask)


def csr_to_hybrid_ell(g: CSRGraph, widths: Optional[Sequence[int]] = None,
                      spill_cap: Optional[int] = None) -> HybridEllGraph:
    """CSR -> hybrid layout.

    ``widths`` (ascending) overrides the pow2 ladder; ``spill_cap``
    overrides :func:`default_spill_cap`.  Empty buckets produce no slice
    (the kernel stack iterates actual slices, so a graph whose degrees
    all land in one bucket compiles exactly one slab pass).  Every array
    is built on the host first and the layout is copied to the device
    once (:func:`repro.graphs.csr.to_device`).
    """
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    v = len(indptr) - 1
    deg = np.diff(indptr)
    max_deg = int(deg.max()) if v else 0

    if spill_cap is None:
        spill_cap = default_spill_cap(deg)
    spill_cap = int(spill_cap)
    heavy = deg > spill_cap
    max_slab_deg = int(deg[~heavy].max()) if (~heavy).any() else 0

    if widths is None:
        widths = slice_width_ladder(max(max_slab_deg, 1))
    widths = tuple(sorted(int(w) for w in widths))
    if max_slab_deg > widths[-1]:
        raise ValueError(
            f"explicit widths {widths} do not cover max non-spill degree "
            f"{max_slab_deg} (spill_cap={spill_cap})")

    slices = []
    lo = 0
    for w in widths:
        sel = np.flatnonzero((deg > lo) & (deg <= w) & ~heavy)
        lo = w
        if len(sel) == 0:
            continue                      # empty bucket: no slice
        slices.append(_build_slab(sel, deg, indptr, indices, w))
    # degree-0 rows (no entries, not even a self loop) ride in the first
    # bucket so every vertex is owned by exactly one partition
    zero = np.flatnonzero(deg == 0)
    if len(zero):
        slices.insert(0, _build_slab(zero, deg, indptr, indices, widths[0]))

    hsel = np.flatnonzero(heavy)
    hdeg = deg[hsel].astype(np.int64)
    spill_seg = np.repeat(np.arange(len(hsel), dtype=np.int32), hdeg)
    spill_cols = np.concatenate(
        [indices[indptr[r]:indptr[r + 1]] for r in hsel]) if len(hsel) \
        else np.zeros(0, dtype=np.int32)

    slices, rows, seg, cols = to_device((
        tuple(slices), hsel.astype(np.int32), spill_seg,
        spill_cols.astype(np.int32)))
    return HybridEllGraph(slices, rows, seg, cols, v, spill_cap)
