"""Formulations of two XLA ops that compile quickly for the TPU.

Both give exactly the values of the plain ``jnp`` spelling; they differ
only in the shapes XLA sees.  Times are compiles for a described TPU v5e.

* :func:`gather_rows` — ``x[idx]`` for an ``[R, D]`` index block with a
  narrow minor ``D`` (ELL neighbor lists: D = 7 at Laplace3D) takes the
  TPU compiler 50-90 s at R = 125k-1M; gathering through the transposed
  ``[D, R]`` indices, which puts the R rows on the 128-lane axis, and
  transposing the result back takes about a second.
  :func:`slot_major_rows` gathers worklist rows of an ELL table straight
  into that ``[D, W]`` order for code that reduces over axis 0.
* :func:`cumsum` — a 1-D ``jnp.cumsum`` lowers to one reduce-window over
  the whole vector, ~20 s at 1M elements; blocking it into
  ``[n / BLOCK, BLOCK]`` rows plus a (recursive) prefix sum of the row
  totals takes about a second.  Integer inputs only: a floating-point sum
  would round differently.
* :func:`argsort_int64` — the TPU has no 64-bit integers, and a stable
  argsort of 7M emulated int64 keys takes ~106 s to compile; sorting the
  two 32-bit words of each key as a two-key stable sort takes ~58 s and
  gives the same permutation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 1024


def gather_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``x[idx]`` for a 2-D index block ``idx [R, D]``; ``x`` is ``[N]``
    (result ``[R, D]``) or ``[N, K]`` (result ``[R, D, K]``)."""
    if x.ndim == 1:
        return x[idx.T].T
    return jnp.transpose(x.T[:, idx.T], (2, 1, 0))


def slot_major_rows(table: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """``table[clip(rows)].T`` — rows of an ``[N, D]`` table as a ``[D, W]``
    block, gathered directly in that order (out-of-range rows, such as the
    worklist sentinel ``N``, read the last row)."""
    n, d = table.shape
    r = jnp.clip(rows, 0, n - 1)
    return table[r[None, :], jnp.arange(d, dtype=jnp.int32)[:, None]]


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a 1-D integer array (``jnp.cumsum``)."""
    n = x.shape[0]
    if n <= BLOCK:
        return jnp.cumsum(x)
    rows = jnp.pad(x, (0, (-n) % BLOCK)).reshape(-1, BLOCK)
    inner = jnp.cumsum(rows, axis=1)
    totals = inner[:, -1]
    offsets = cumsum(totals) - totals
    return (inner + offsets[:, None]).reshape(-1)[:n]


def argsort_int64(keys: jnp.ndarray) -> jnp.ndarray:
    """Stable ``jnp.argsort`` of a 1-D non-negative int64 array (x64 mode),
    as int32 indices."""
    hi = (keys >> 32).astype(jnp.int32)
    lo = (keys & 0xFFFFFFFF).astype(jnp.uint32)
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    return jax.lax.sort((hi, lo, iota), num_keys=2, is_stable=True)[2]
