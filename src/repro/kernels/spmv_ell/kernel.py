"""Pallas TPU ELL SpMV — the AMG smoother / Gauss-Seidel hot loop.

Same tiling story as minprop_ell: column-major ``[D, BLOCK_ROWS]`` tiles
of (vals, gathered x) per grid step — the D row slots down the sublanes,
the rows across the lanes — and a fused multiply-reduce on the VPU with
fp32 accumulation.  The ``x[cols]`` gather runs in XLA in front of the
kernel (Mosaic gathers only within one vreg).  Padding slots carry
``val == 0`` so no mask load is needed — the ELL format itself encodes
the paper's "no divergence" property.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._interpret import resolve_interpret

BLOCK_ROWS = 512


def _spmv_kernel(vals_ref, xg_ref, y_ref, *, num_rows: int):
    """Rows past ``num_rows`` (the ragged final block) are masked to zero:
    compiled Pallas pads partial blocks with unspecified values, unlike
    interpret mode's zero padding."""
    i = pl.program_id(0)
    block = y_ref.shape[1]
    valid = i * block + jax.lax.broadcasted_iota(
        jnp.int32, vals_ref.shape, 1) < num_rows
    prod = vals_ref[...].astype(jnp.float32) * xg_ref[...].astype(jnp.float32)
    y_ref[...] = jnp.sum(jnp.where(valid, prod, 0.0), axis=0,
                         keepdims=True).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def spmv_ell_pallas(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray, *,
                    interpret=None,
                    block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """y = A @ x for ELL ``A`` (padding: col=row, val=0)."""
    v, d = cols.shape
    block = v if v <= block_rows else block_rows
    return pl.pallas_call(
        functools.partial(_spmv_kernel, num_rows=v),
        grid=(pl.cdiv(v, block),),
        in_specs=[pl.BlockSpec((d, block), lambda i: (0, i))] * 2,
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, v), x.dtype),
        interpret=resolve_interpret(interpret),
    )(vals.T, x[cols.T])[0]


def _spmv_t_kernel(vals_ref, x_ref, c_ref, *, num_rows: int):
    """Transposed SpMV grid step: the per-entry contributions
    ``vals[r, j] * x[r]`` of one row block; rows past ``num_rows`` (the
    ragged final block) are masked to zero."""
    i = pl.program_id(0)
    block = c_ref.shape[1]
    valid = i * block + jax.lax.broadcasted_iota(
        jnp.int32, vals_ref.shape, 1) < num_rows
    contrib = vals_ref[...].astype(jnp.float32) \
        * x_ref[...].astype(jnp.float32)
    c_ref[...] = jnp.where(valid, contrib, 0.0).astype(c_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_out", "interpret",
                                             "block_rows"))
def spmv_ell_t_pallas(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray,
                      *, num_out: int, interpret=None,
                      block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """y = A^T @ x for rectangular ELL A (restriction without an explicit
    R matrix).  The kernel forms the ``[D, rows]`` contributions tile by
    tile; the scatter-add into the ``[num_out]`` output runs in XLA
    (Mosaic has no in-kernel scatter)."""
    v, d = cols.shape
    block = v if v <= block_rows else block_rows
    contrib = pl.pallas_call(
        functools.partial(_spmv_t_kernel, num_rows=v),
        grid=(pl.cdiv(v, block),),
        in_specs=[pl.BlockSpec((d, block), lambda i: (0, i)),
                  pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((d, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((d, v), x.dtype),
        interpret=resolve_interpret(interpret),
    )(vals.T, x[None, :])
    return jnp.zeros(num_out, x.dtype).at[cols.T].add(contrib)
