"""Tentative + damped-Jacobi-smoothed prolongator assembly.

Host backend: the legacy scipy path (moved verbatim from
``solvers/amg.py``): ``P0[v, agg(v)] = 1/sqrt(|agg|)``, then
``P = (I - omega D^-1 A) P0`` in f64 COO.

Device backend: the same P assembled from the aggregation labels with
fixed-shape sort/segment arithmetic — no scipy, no host round-trip.  The
per-entry f64 value is accumulated in exactly scipy's SMMP order (A-row
slot order within each prolongator column), so the two backends produce
**bit-identical** f64 values; exact-zero entries are dropped like scipy's
binop does.  The device rows come out sorted by column, matching the
canonical CSR layout of the host path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops
from ..graphs.csr import ELLMatrix

INT32_MAX = np.int32(2**31 - 1)


# ---------------------------------------------------------------------------
# host backend (scipy; the reference)
# ---------------------------------------------------------------------------

def smoothed_prolongator_host(a, labels: np.ndarray, nagg: int,
                              omega: float):
    """``P = (I - omega D^-1 A) P0`` in COO (host scipy, f64)."""
    import scipy.sparse as sp

    from ..graphs.ops import matrix_to_scipy

    asp = matrix_to_scipy(a)
    v = a.num_rows
    sizes = np.bincount(labels, minlength=nagg).astype(np.float64)
    p0 = sp.csr_matrix(
        (1.0 / np.sqrt(sizes[labels]), (np.arange(v), labels)), shape=(v, nagg)
    )
    d_inv = 1.0 / asp.diagonal()
    p = p0 - omega * sp.diags(d_inv) @ (asp @ p0)
    p = p.tocoo()
    return p.row, p.col, p.data


def rect_ell(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
             nrows: int) -> ELLMatrix:
    """Rectangular ELL from COO (for P and R; padding col 0, val 0)."""
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    counts = np.bincount(rows, minlength=nrows)
    d = max(1, int(counts.max()) if len(counts) else 1)
    cmat = np.zeros((nrows, d), dtype=np.int32)
    vmat = np.zeros((nrows, d), dtype=np.float32)
    mmat = np.zeros((nrows, d), dtype=bool)
    slot = np.arange(len(rows)) - np.repeat(np.cumsum(counts) - counts, counts)
    cmat[rows, slot] = cols
    vmat[rows, slot] = vals
    mmat[rows, slot] = True
    return ELLMatrix(jnp.asarray(cmat), jnp.asarray(vmat), jnp.asarray(mmat))


# ---------------------------------------------------------------------------
# device backend (jitted, x64)
# ---------------------------------------------------------------------------

@jax.jit
def _prolongator_scan_device(a_cols, a_vals, a_mask, labels, omega):
    """First prolongator dispatch: per-row column-sorted candidate slots
    of ``X = A @ P0``, the smoothed term ``(omega*D^-1) * X`` and the
    tentative term.

    The smoothed term is a *function output* on purpose: scipy rounds the
    product ``(omega d_inv) * x`` to f64 before subtracting it from
    ``P0``, but inside one XLA computation LLVM contracts ``tent - w*s``
    into an FMA (skipping that rounding; ``lax.optimization_barrier``
    does not prevent it).  Materializing the product at a dispatch
    boundary forces the rounding, keeping the values bit-identical to the
    host path.
    """
    v, d = a_cols.shape
    # slot-major [D, V] internally (outputs transposed back): per-slot
    # steps are then lane-dense [V] vectors, which the TPU compiler turns
    # around in seconds instead of minutes
    cols_t, vals_t, mask_t = a_cols.T, a_vals.T, a_mask.T
    rid = jnp.arange(v, dtype=jnp.int32)
    # aggregate sizes + tentative scaling (labels are all >= 0 here)
    aggsize = jnp.zeros(v, jnp.int32).at[labels].add(1)
    # the barrier keeps XLA from rewriting 1/sqrt into rsqrt, which is not
    # correctly rounded (numpy/scipy divide by the rounded sqrt)
    inv_sqrt = 1.0 / jax.lax.optimization_barrier(
        jnp.sqrt(aggsize.astype(jnp.float64)))
    # diagonal: canonical rows hold at most one self entry
    diag = jnp.sum(jnp.where((cols_t == rid[None, :]) & mask_t,
                             vals_t, jnp.float32(0)), axis=0)
    dinv = 1.0 / diag.astype(jnp.float64)
    # per-slot candidates of X = A @ P0 (term order = CSR slot order)
    lab_n = labels[cols_t]
    cand_col = jnp.where(mask_t, lab_n, INT32_MAX)
    contrib = jnp.where(mask_t, vals_t.astype(jnp.float64) * inv_sqrt[lab_n],
                        0.0)
    # stable sort by column keeps equal-column terms in slot order
    order = jnp.argsort(cand_col, axis=0, stable=True)
    col_s = jnp.take_along_axis(cand_col, order, axis=0)
    con_s = jnp.take_along_axis(contrib, order, axis=0)
    # sequential run sums (SMMP accumulation order): s_i = sum of the
    # following same-column slots, added one shift at a time
    pad_c = jnp.pad(col_s, ((0, d), (0, 0)), constant_values=-1)
    pad_v = jnp.pad(con_s, ((0, d), (0, 0)), constant_values=0.0)
    s = con_s
    for off in range(1, d):
        s = s + jnp.where(pad_c[off:off + d] == col_s, pad_v[off:off + d],
                          0.0)
    tent = jnp.where(col_s == labels[None, :], inv_sqrt[labels][None, :],
                     0.0)
    # scipy's `omega * sp.diags(d_inv) @ X` binds as (omega*d_inv) @ X —
    # same association here keeps the f64 values bit-identical
    smoothed = (omega * dinv)[None, :] * s
    return col_s.T, tent.T, smoothed.T, diag


@jax.jit
def _prolongator_finish_device(col_s, tent, smoothed):
    """Second prolongator dispatch: ``P = P0 - smoothed`` on the run
    heads, zero-dropping like scipy's csr binop, plus the P/R width
    scalars the packing dispatch needs.

    Returns ``(p_cols[V, D], p_vals64[V, D], p_keep[V, D], dp_real, dr)``:
    slot ``i`` of row ``v`` is a run head carrying the full f64 value of
    ``P[v, p_cols[v, i]]`` iff ``p_keep[v, i]``; dead slots hold ``col 0,
    val 0.0`` so they are inert inside the Galerkin expansion.
    """
    v = col_s.shape[0]
    col_t = col_s.T                             # slot-major, as in the scan
    head = jnp.concatenate(
        [jnp.ones((1, v), bool), col_t[1:] != col_t[:-1]], axis=0)
    real = col_t != INT32_MAX
    pval = tent.T - smoothed.T
    keep = head & real & (pval != 0.0)          # scipy binop drops exact 0s
    p_cols = jnp.where(keep, col_t, 0)
    p_vals = jnp.where(keep, pval, 0.0)
    dp_real = jnp.max(jnp.sum(keep, axis=0))
    rcounts = jnp.zeros(v + 1, jnp.int32).at[
        jnp.where(keep, p_cols, v)].add(1)[:-1]
    return p_cols.T, p_vals.T, keep.T, dp_real, jnp.max(rcounts)


def _prolongator_device(a_cols, a_vals, a_mask, labels, omega):
    """Smoothed prolongator in padded row form, on device (2 dispatches:
    see :func:`_prolongator_scan_device` for why the smoothed product
    must cross a dispatch boundary)."""
    col_s, tent, smoothed, diag = _prolongator_scan_device(
        a_cols, a_vals, a_mask, labels, omega)
    p_cols, p_vals, keep, dp_real, dr = _prolongator_finish_device(
        col_s, tent, smoothed)
    return p_cols, p_vals, keep, diag, dp_real, dr


@functools.partial(jax.jit, static_argnames=("num_aggregates", "p_width",
                                             "r_width"))
def _prolongator_pack_device(p_cols, p_vals64, p_keep, *,
                             num_aggregates: int, p_width: int, r_width: int):
    """Pack the padded row form into the hierarchy's P and R ELL matrices
    (``rect_ell`` convention: padding col 0, val 0, mask False; rows
    sorted by column — bitwise the host layout)."""
    v, d = p_cols.shape
    pw, rw = max(1, p_width), max(1, r_width)
    # slot-major [D, V] scatter indices (see _prolongator_scan_device)
    cols_t, keep_t = p_cols.T, p_keep.T
    vals32 = p_vals64.T.astype(jnp.float32)
    # P: within-row compaction of the kept heads (already column-sorted)
    slot = jnp.cumsum(keep_t.astype(jnp.int32), axis=0) - 1
    rows = jnp.where(keep_t, jnp.arange(v, dtype=jnp.int32)[None, :], v)
    sl = jnp.clip(slot, 0, pw - 1)
    pe_cols = jnp.zeros((v, pw), jnp.int32).at[rows, sl].set(
        cols_t, mode="drop")
    pe_vals = jnp.zeros((v, pw), jnp.float32).at[rows, sl].set(
        vals32, mode="drop")
    pe_mask = jnp.zeros((v, pw), bool).at[rows, sl].set(True, mode="drop")
    # R = P^T: entries sorted by (coarse row, fine col) via one stable sort
    # (keys are unique per kept entry, so the flattening order is free)
    vids = jnp.arange(v, dtype=jnp.int64)[None, :]
    keys = jnp.where(keep_t, cols_t.astype(jnp.int64) * v + vids,
                     jnp.int64(num_aggregates) * v + v).reshape(-1)
    order = xla_ops.argsort_int64(keys)
    keys_s = keys[order]
    vals_s = vals32.reshape(-1)[order]
    kept = keys_s < jnp.int64(num_aggregates) * v
    crow = jnp.where(kept, (keys_s // v).astype(jnp.int32), num_aggregates)
    ccol = (keys_s % v).astype(jnp.int32)
    counts = jnp.zeros(num_aggregates + 1, jnp.int32).at[crow].add(1)[:-1]
    starts = xla_ops.cumsum(counts) - counts
    rank = jnp.arange(keys_s.shape[0], dtype=jnp.int32)
    rslot = jnp.clip(rank - starts[jnp.clip(crow, 0, num_aggregates - 1)],
                     0, rw - 1)
    re_cols = jnp.zeros((num_aggregates, rw), jnp.int32).at[crow, rslot].set(
        ccol, mode="drop")
    re_vals = jnp.zeros((num_aggregates, rw), jnp.float32).at[
        crow, rslot].set(vals_s, mode="drop")
    re_mask = jnp.zeros((num_aggregates, rw), bool).at[crow, rslot].set(
        True, mode="drop")
    return (pe_cols, pe_vals, pe_mask), (re_cols, re_vals, re_mask)
