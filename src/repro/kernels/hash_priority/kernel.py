"""Pallas TPU kernel: xorshift64* priorities + tuple packing (paper §V-A/C).

Elementwise over a block of vertex ids.  The interesting TPU detail: there
is no native 64-bit integer lane, so the xorshift* state is a pair of
uint32 VREGs and the multiply is four 16-bit partial products — the limb
emulation from core/hashing.py runs unchanged *inside* the kernel (it is
pure jnp), demonstrating that the production hash lowers to plain VPU ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.hashing import priorities_xorshift_star
from ...core.tuples import pack
from .._interpret import resolve_interpret

BLOCK = 1024


# repro-lint: ignore[RL106] elementwise, no gathered indexing; tail lanes drop at BlockSpec write
def _hash_pack_kernel(it_ref, ids_ref, out_ref, *, b: int):
    ids = ids_ref[...]
    prio = priorities_xorshift_star(it_ref[0], ids)
    out_ref[...] = pack(prio, ids, b)


@functools.partial(jax.jit, static_argnames=("b", "interpret", "block"))
def hash_pack_pallas(iteration, vertex_ids: jnp.ndarray, b: int, *,
                     interpret=None, block: int = BLOCK) -> jnp.ndarray:
    n = vertex_ids.shape[0]
    blk = min(block, n)
    grid = pl.cdiv(n, blk)
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        functools.partial(_hash_pack_kernel, b=b),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[pl.BlockSpec((blk,), lambda i, *_: (i,))],
            out_specs=pl.BlockSpec((blk,), lambda i, *_: (i,)),
        ),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(iteration, jnp.uint32).reshape(1), vertex_ids)
