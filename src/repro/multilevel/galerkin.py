"""Galerkin triple product ``A_c = P^T A P`` as a padded sorted-COO SpGEMM.

Both multilevel engines compute the product through ONE canonical
two-stage algorithm so their hierarchies stay bit-identical (the
PR-3/PR-4 digest discipline extended to floats):

* stage 1 — ``Q = A P``: expand every (A slot, P-row slot) candidate
  ``val = A[v,w] * P[w,b]`` in fixed ``(v, j, l)`` order over the padded
  ELL slot grid (padding contributes exact ``0.0``, an IEEE no-op inside
  the later sums), stable-sort by the packed key ``v*K + b``, sum each
  run sequentially in sorted order, drop exact-zero sums, repack to a
  padded ``[V, Dq]`` row form;
* stage 2 — ``A_c = P^T Q``: expand ``val = P[v,a] * Q[v,b]`` in fixed
  ``(v, i, m)`` order, stable-sort by ``a*K + b``, run-sum, zero-drop.

Two stages keep the expansion at ``O(E·Dp + V·Dp·Dq)`` candidates
instead of the quartic ``O(E·Dp²)`` of a one-shot triple expansion —
the difference between milliseconds and minutes on the denser coarse
levels.

The host backend mirrors the device backend primitive-for-primitive
(``np.argsort(kind='stable')``/``np.add.at`` against jnp stable argsort/
``segment_sum`` — both accumulate in order on CPU, asserted by the
digest-parity gate).  All arithmetic is float64 (the device backend runs
under ``jax.enable_x64``); the float32 results agree with
the legacy scipy path ``graphs.ops.galerkin_coarse_matrix`` to rounding
(property-tested in ``tests/test_multilevel.py``) and agree across the
two backends bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops
from ..graphs.csr import CSRMatrix, ELLMatrix


# ---------------------------------------------------------------------------
# device backend (jitted, x64)
# ---------------------------------------------------------------------------

def _kept_row_slots(rows, keep, num_rows: int):
    """Scatter coordinates for a sorted kept-entry stream: ``r`` is the
    entry's row (sentinel ``num_rows`` when dropped), ``s`` its
    within-row rank among kept entries.  Shared by every repack kernel so
    the slot arithmetic cannot drift between them."""
    counts = jnp.zeros(num_rows + 1, jnp.int32).at[
        jnp.where(keep, rows, num_rows)].add(1)[:-1]
    starts = xla_ops.cumsum(counts) - counts
    rank = xla_ops.cumsum(keep.astype(jnp.int32)) - 1
    slot = rank - starts[jnp.clip(rows, 0, num_rows - 1)]
    return jnp.where(keep, rows, num_rows), slot


def _run_sums_device(keys, vals):
    """Stable-sort ``(keys, vals)``, sum each key run sequentially, drop
    exact-zero totals.  Returns ``(keys_sorted, sums, keep)`` with
    ``sums`` replicated over each run and ``keep`` marking nonzero run
    heads."""
    order = xla_ops.argsort_int64(keys)
    keys = keys[order]
    vals = vals[order]
    head = jnp.concatenate([jnp.ones(1, bool), keys[1:] != keys[:-1]])
    rid = xla_ops.cumsum(head.astype(jnp.int32)) - 1
    totals = jax.ops.segment_sum(vals, rid, num_segments=vals.shape[0])
    sums = totals[rid]
    keep = head & (sums != 0.0)
    return keys, sums, keep


@functools.partial(jax.jit, static_argnames=("key_base",))
def _spgemm_stage1_device(a_cols, a_vals64, p_cols, p_vals64, *,
                          key_base: int):
    """``Q = A P`` candidates: keys ``v*K + b`` over the ``[V, D, Dp]``
    slot grid in ``(v, j, l)`` order, run-summed.  Also returns the
    padded row width of Q (``dq``, the scalar the repack dispatch
    needs)."""
    v, d = a_cols.shape
    dp = p_cols.shape[1]
    w = a_cols                                               # [V, D]
    vals = (a_vals64[:, :, None] * xla_ops.gather_rows(p_vals64, w)).reshape(-1)
    vids = jnp.arange(v, dtype=jnp.int64)
    keys = (vids[:, None, None] * key_base
            + xla_ops.gather_rows(p_cols.astype(jnp.int64), w)
            + jnp.zeros((v, d, dp), dtype=jnp.int64)).reshape(-1)
    keys, sums, keep = _run_sums_device(keys, vals)
    rows = (keys // key_base).astype(jnp.int32)
    counts = jnp.zeros(v + 1, jnp.int32).at[
        jnp.where(keep, rows, v)].add(1)[:-1]
    return keys, sums, keep, jnp.max(counts)


@functools.partial(jax.jit, static_argnames=("key_base", "num_rows",
                                             "width"))
def _coo_rows_repack_device(keys, sums, keep, *, key_base: int,
                            num_rows: int, width: int):
    """Repack kept sorted runs into a padded f64 row form ``(cols[R, W],
    vals64[R, W])`` (padding col 0, val 0.0) — the Q input of stage 2."""
    rows = (keys // key_base).astype(jnp.int32)
    cols = (keys % key_base).astype(jnp.int32)
    r, slot = _kept_row_slots(rows, keep, num_rows)
    s = jnp.clip(slot, 0, max(1, width) - 1)
    out_cols = jnp.zeros((num_rows, max(1, width)), jnp.int32
                         ).at[r, s].set(cols, mode="drop")
    out_vals = jnp.zeros((num_rows, max(1, width)), jnp.float64
                         ).at[r, s].set(sums, mode="drop")
    return out_cols, out_vals


@functools.partial(jax.jit, static_argnames=("key_base",))
def _spgemm_stage2_device(p_cols, p_vals64, q_cols, q_vals64, *,
                          key_base: int):
    """``A_c = P^T Q`` candidates: keys ``a*K + b`` over the
    ``[V, Dp, Dq]`` pair grid in ``(v, i, m)`` order, run-summed; returns
    the per-coarse-row nnz histogram inputs (counts max + total) too."""
    v, dp = p_cols.shape
    dq = q_cols.shape[1]
    vals = (p_vals64[:, :, None] * q_vals64[:, None, :]).reshape(-1)
    keys = (p_cols.astype(jnp.int64)[:, :, None] * key_base
            + q_cols.astype(jnp.int64)[:, None, :]
            + jnp.zeros((v, dp, dq), dtype=jnp.int64)).reshape(-1)
    keys, sums, keep = _run_sums_device(keys, vals)
    rows = (keys // key_base).astype(jnp.int32)
    counts = jnp.zeros(key_base + 1, jnp.int32).at[
        jnp.where(keep, rows, key_base)].add(1)[:-1]
    return keys, sums, keep, jnp.sum(keep, dtype=jnp.int32), jnp.max(counts)


@functools.partial(jax.jit, static_argnames=("key_base", "num_rows", "width"))
def _coo_to_ell_device(keys, sums, keep, *, key_base: int, num_rows: int,
                       width: int):
    """Repack kept sorted-COO runs into a square float32 ELL matrix.

    Follows the ``csr_to_ell_matrix`` convention exactly (padding
    ``col = row``, ``val = 0``, ``mask = False``) so the result's digest
    matches the host engine's ``csr_to_ell_matrix`` output bit for bit.
    """
    rows = (keys // key_base).astype(jnp.int32)
    cols = (keys % key_base).astype(jnp.int32)
    r, slot = _kept_row_slots(rows, keep, num_rows)
    rid = jnp.arange(num_rows, dtype=jnp.int32)
    out_cols = jnp.repeat(rid[:, None], max(1, width), axis=1)
    out_vals = jnp.zeros((num_rows, max(1, width)), jnp.float32)
    out_mask = jnp.zeros((num_rows, max(1, width)), bool)
    s = jnp.clip(slot, 0, max(1, width) - 1)
    out_cols = out_cols.at[r, s].set(cols, mode="drop")
    out_vals = out_vals.at[r, s].set(sums.astype(jnp.float32), mode="drop")
    out_mask = out_mask.at[r, s].set(True, mode="drop")
    diag = jnp.sum(jnp.where((out_cols == rid[:, None]) & out_mask,
                             out_vals, 0.0), axis=1)
    return out_cols, out_vals, out_mask, diag


# ---------------------------------------------------------------------------
# dense-accumulator device backend (sort-free).
#
# For moderate coarse sizes the product accumulates into a flat dense
# buffer (`scatter-add in candidate order` — the SAME accumulation
# sequence per output entry as the sorted-run path, so the f64 values are
# bit-identical either way) and the sparse rows are extracted with an
# integer cumsum + searchsorted compaction instead of a comparator sort.
# On CPU this is several times faster than sort-based runs; the sorted
# path remains the fallback when ``rows*cols`` would not fit a dense
# accumulator (see DENSE_ACCUM_LIMIT).
# ---------------------------------------------------------------------------

DENSE_ACCUM_LIMIT = 1 << 26          # max dense accumulator elements (f64)


@functools.partial(jax.jit, static_argnames=("num_cols",))
def _spgemm_stage1_dense_device(a_cols, a_vals64, p_cols, p_vals64, *,
                                num_cols: int):
    """``Q = A P`` into a dense ``[V, num_cols]`` accumulator; returns the
    flat dense buffer, its nonzero mask cumsum, and the Q width/nnz
    scalars the extraction dispatch needs."""
    v, d = a_cols.shape
    w = a_cols
    vals = (a_vals64[:, :, None] * xla_ops.gather_rows(p_vals64, w)).reshape(-1)
    vids = jnp.arange(v, dtype=jnp.int32)
    idx = (vids[:, None, None] * num_cols + xla_ops.gather_rows(p_cols, w)
           + jnp.zeros(a_vals64.shape + (p_cols.shape[1],),
                       dtype=jnp.int32)).reshape(-1)
    dense = jnp.zeros(v * num_cols, jnp.float64).at[idx].add(vals)
    mask = dense != 0.0
    csum = xla_ops.cumsum(mask.astype(jnp.int32))
    row_nnz = csum.reshape(v, num_cols)[:, -1]
    row_nnz = jnp.diff(row_nnz, prepend=jnp.int32(0))
    return dense, csum, jnp.max(row_nnz), csum[-1]


@functools.partial(jax.jit, static_argnames=("num_cols", "width",
                                             "nnz_bucket"))
def _dense_rows_extract_device(dense, csum, nnz, *, num_cols: int,
                               width: int, nnz_bucket: int):
    """Extract the nonzero entries of a flat dense ``[R, num_cols]``
    buffer into padded f64 rows ``(cols[R, W], vals64[R, W])`` (padding
    col 0 / val 0) without any comparator sort: the k-th nonzero's flat
    position is ``searchsorted(csum, k+1)``.

    ``nnz`` is traced; ``nnz_bucket`` is its pow2 padding (the repo's
    worklist bucket discipline) so the compilation is reused across
    builds with nearby nnz.
    """
    r = dense.shape[0] // num_cols
    k = max(1, nnz_bucket)
    pos = jnp.searchsorted(csum, jnp.arange(1, k + 1, dtype=jnp.int32))
    pos = jnp.clip(pos, 0, dense.shape[0] - 1)
    rows = (pos // num_cols).astype(jnp.int32)
    cols = (pos % num_cols).astype(jnp.int32)
    vals = dense[pos]
    slot = jnp.arange(k, dtype=jnp.int32) \
        - (csum[rows * num_cols] - (dense[rows * num_cols] != 0.0)
           ).astype(jnp.int32)
    out_cols = jnp.zeros((r, max(1, width)), jnp.int32)
    out_vals = jnp.zeros((r, max(1, width)), jnp.float64)
    s = jnp.clip(slot, 0, max(1, width) - 1)
    rr = jnp.where(jnp.arange(k) < nnz, rows, r)
    out_cols = out_cols.at[rr, s].set(cols, mode="drop")
    out_vals = out_vals.at[rr, s].set(vals, mode="drop")
    return out_cols, out_vals


@functools.partial(jax.jit, static_argnames=("num_cols",))
def _spgemm_stage2_dense_device(p_cols, p_vals64, q_cols, q_vals64, *,
                                num_cols: int):
    """``A_c = P^T Q`` into a dense ``[num_cols, num_cols]`` accumulator
    (coarse rows/cols); returns the flat buffer + extraction scalars."""
    v, dp = p_cols.shape
    dq = q_cols.shape[1]
    vals = (p_vals64[:, :, None] * q_vals64[:, None, :]).reshape(-1)
    idx = (p_cols[:, :, None].astype(jnp.int32) * num_cols
           + q_cols[:, None, :]
           + jnp.zeros((v, dp, dq), dtype=jnp.int32)).reshape(-1)
    dense = jnp.zeros(num_cols * num_cols, jnp.float64).at[idx].add(vals)
    mask = dense != 0.0
    csum = xla_ops.cumsum(mask.astype(jnp.int32))
    row_nnz = csum.reshape(num_cols, num_cols)[:, -1]
    row_nnz = jnp.diff(row_nnz, prepend=jnp.int32(0))
    return dense, csum, jnp.max(row_nnz), csum[-1]


@functools.partial(jax.jit, static_argnames=("num_cols", "num_rows",
                                             "width", "nnz_bucket"))
def _dense_to_ell_device(dense, csum, nnz, *, num_cols: int, num_rows: int,
                         width: int, nnz_bucket: int):
    """Extract a flat dense ``[num_rows(+pad), num_cols]`` coarse buffer
    into the float32 ELL convention (padding col=row, val 0, mask off) +
    the diagonal."""
    k = max(1, nnz_bucket)
    pos = jnp.searchsorted(csum, jnp.arange(1, k + 1, dtype=jnp.int32))
    pos = jnp.clip(pos, 0, dense.shape[0] - 1)
    rows = (pos // num_cols).astype(jnp.int32)
    cols = (pos % num_cols).astype(jnp.int32)
    vals = dense[pos].astype(jnp.float32)
    slot = jnp.arange(k, dtype=jnp.int32) \
        - (csum[rows * num_cols] - (dense[rows * num_cols] != 0.0)
           ).astype(jnp.int32)
    rid = jnp.arange(num_rows, dtype=jnp.int32)
    out_cols = jnp.repeat(rid[:, None], max(1, width), axis=1)
    out_vals = jnp.zeros((num_rows, max(1, width)), jnp.float32)
    out_mask = jnp.zeros((num_rows, max(1, width)), bool)
    s = jnp.clip(slot, 0, max(1, width) - 1)
    rr = jnp.where(jnp.arange(k) < nnz, rows, num_rows)
    out_cols = out_cols.at[rr, s].set(cols, mode="drop")
    out_vals = out_vals.at[rr, s].set(vals, mode="drop")
    out_mask = out_mask.at[rr, s].set(True, mode="drop")
    diag = jnp.sum(jnp.where((out_cols == rid[:, None]) & out_mask,
                             out_vals, 0.0), axis=1)
    return out_cols, out_vals, out_mask, diag


# ---------------------------------------------------------------------------
# host backend (numpy; same canonical order — np.add.at accumulates the
# sorted runs sequentially exactly like the device segment_sum)
# ---------------------------------------------------------------------------

def _run_sums_host(keys, vals):
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    vals = vals[order]
    head = np.ones(len(keys), dtype=bool)
    if len(keys):
        head[1:] = keys[1:] != keys[:-1]
    rid = np.cumsum(head) - 1
    totals = np.zeros(int(rid[-1]) + 1 if len(rid) else 0, dtype=np.float64)
    np.add.at(totals, rid, vals)
    hkeys = keys[head]
    keep = totals != 0.0
    return hkeys[keep], totals[keep]


def galerkin_coo_host(a_ell: ELLMatrix, p_cols: np.ndarray,
                      p_vals64: np.ndarray, num_aggregates: int):
    """Host-backend canonical two-stage Galerkin product.

    ``p_cols``/``p_vals64`` is the padded P row form (any width; padded
    slots ``col 0, val 0.0``).  Returns ``(rows, cols, vals_f64)`` of the
    kept (nonzero) coarse entries, sorted by (row, col).
    """
    v = a_ell.num_rows
    key_base = max(1, v, int(num_aggregates))
    a_cols = np.asarray(a_ell.cols)
    a_vals = np.where(np.asarray(a_ell.mask),
                      np.asarray(a_ell.vals, dtype=np.float64), 0.0)
    # stage 1: Q = A P
    w = a_cols
    vals1 = (a_vals[:, :, None] * p_vals64[w]).reshape(-1)
    vids = np.arange(v, dtype=np.int64)
    keys1 = np.broadcast_to(
        vids[:, None, None] * key_base + p_cols.astype(np.int64)[w],
        (v, a_cols.shape[1], p_cols.shape[1])).reshape(-1)
    qkeys, qvals = _run_sums_host(keys1, vals1)
    q_cols, q_vals = _pad_p_rows(qkeys // key_base, qkeys % key_base,
                                 qvals, v)
    # stage 2: A_c = P^T Q
    vals2 = (p_vals64[:, :, None] * q_vals[:, None, :]).reshape(-1)
    keys2 = np.broadcast_to(
        p_cols.astype(np.int64)[:, :, None] * key_base
        + q_cols.astype(np.int64)[:, None, :],
        (v, p_cols.shape[1], q_cols.shape[1])).reshape(-1)
    ckeys, cvals = _run_sums_host(keys2, vals2)
    return (ckeys // key_base).astype(np.int64), \
        (ckeys % key_base).astype(np.int64), cvals


# ---------------------------------------------------------------------------
# public entry (device path) — the property-test surface
# ---------------------------------------------------------------------------

def _pad_p_rows(p_rows: np.ndarray, p_cols: np.ndarray, p_vals: np.ndarray,
                nrows: int, width: int | None = None):
    """COO prolongator -> padded row form (f64 vals; padding col 0/val 0),
    rows sorted by (row, col) like ``scipy.sparse.csr_matrix``."""
    order = np.lexsort((p_cols, p_rows))
    rows, cols = p_rows[order], p_cols[order]
    vals = np.asarray(p_vals, dtype=np.float64)[order]
    counts = np.bincount(rows, minlength=nrows)
    d = int(width) if width is not None else max(1, int(counts.max()) if
                                                 len(counts) else 1)
    cmat = np.zeros((nrows, d), dtype=np.int32)
    vmat = np.zeros((nrows, d), dtype=np.float64)
    slot = np.arange(len(rows)) - np.repeat(np.cumsum(counts) - counts, counts)
    cmat[rows, slot] = cols
    vmat[rows, slot] = vals
    return cmat, vmat


def galerkin(a: CSRMatrix, p_rows: np.ndarray, p_cols: np.ndarray,
             p_vals: np.ndarray, num_aggregates: int) -> CSRMatrix:
    """Device-computed ``A_c = P^T A P`` with P in COO (rectangular ok).

    Drop-in counterpart of :func:`repro.graphs.ops.galerkin_coarse_matrix`
    (scipy): same signature, same result to float32 rounding — the
    property tests in ``tests/test_multilevel.py`` compare the two on
    random CSR matrices with empty rows, singleton aggregates and
    rectangular P.
    """
    from ..graphs.handle import as_graph
    from .hierarchy import x64_context

    nagg = max(1, int(num_aggregates))
    v = a.num_rows
    key_base = max(1, v, nagg)
    indptr = np.zeros(nagg + 1, dtype=np.int64)
    if a.num_entries == 0 or v == 0:       # empty matrix -> empty product
        return CSRMatrix(jnp.asarray(indptr.astype(np.int32)),
                         jnp.asarray(np.zeros(0, np.int32)),
                         jnp.asarray(np.zeros(0, np.float32)))
    a_ell = as_graph(a).ell_matrix
    pc, pv = _pad_p_rows(np.asarray(p_rows), np.asarray(p_cols),
                         np.asarray(p_vals), v)
    with x64_context():
        a_vals64 = jnp.where(a_ell.mask, a_ell.vals.astype(jnp.float64), 0.0)
        k1, s1, kp1, dq = _spgemm_stage1_device(
            a_ell.cols, a_vals64, jnp.asarray(pc), jnp.asarray(pv),
            key_base=key_base)
        q_cols, q_vals = _coo_rows_repack_device(
            k1, s1, kp1, key_base=key_base, num_rows=v, width=int(dq))
        keys, sums, keep, _, _ = _spgemm_stage2_device(
            jnp.asarray(pc), jnp.asarray(pv), q_cols, q_vals,
            key_base=key_base)
        keys, sums, keep = (np.asarray(keys), np.asarray(sums),
                            np.asarray(keep))
    rows = (keys[keep] // key_base).astype(np.int64)
    cols = (keys[keep] % key_base).astype(np.int64)
    vals = sums[keep]
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    return CSRMatrix(jnp.asarray(indptr),
                     jnp.asarray(cols.astype(np.int32)),
                     jnp.asarray(vals.astype(np.float32)))


# ---------------------------------------------------------------------------
# coarse graph structure (labels -> coarse adjacency), device backend
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("key_base",))
def _coarse_graph_keys_device(neighbors, mask, labels, *, key_base: int):
    """Unique sorted coarse-edge keys ``la * K + lb`` (+ the diagonal),
    the device counterpart of ``graphs.ops.coarse_graph_from_labels``."""
    la = labels.astype(jnp.int64)
    lb = xla_ops.gather_rows(labels, neighbors).astype(jnp.int64)
    keys = jnp.where(mask, la[:, None] * key_base + lb, jnp.int64(-1))
    diag = la * key_base + la
    keys = jnp.concatenate([keys.reshape(-1), diag])
    keys = jnp.sort(keys)
    head = jnp.concatenate([jnp.ones(1, bool), keys[1:] != keys[:-1]])
    keep = head & (keys >= 0)
    rows = jnp.where(keep, keys // key_base, key_base).astype(jnp.int32)
    counts = jnp.zeros(key_base + 1, jnp.int32).at[rows].add(1)[:-1]
    return keys, keep, counts, jnp.max(counts)


@functools.partial(jax.jit, static_argnames=("key_base", "num_rows", "width"))
def _coarse_graph_ell_device(keys, keep, *, key_base: int, num_rows: int,
                             width: int):
    """Repack kept coarse-edge keys into an ELL graph (padding = self)."""
    rows = jnp.where(keep, keys // key_base, num_rows).astype(jnp.int32)
    cols = (keys % key_base).astype(jnp.int32)
    r, slot = _kept_row_slots(rows, keep, num_rows)
    rid = jnp.arange(num_rows, dtype=jnp.int32)
    out_nbrs = jnp.repeat(rid[:, None], max(1, width), axis=1)
    out_mask = jnp.zeros((num_rows, max(1, width)), bool)
    s = jnp.clip(slot, 0, max(1, width) - 1)
    out_nbrs = out_nbrs.at[r, s].set(cols, mode="drop")
    out_mask = out_mask.at[r, s].set(True, mode="drop")
    return out_nbrs, out_mask
