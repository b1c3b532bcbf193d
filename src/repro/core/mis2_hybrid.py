"""Device-resident MIS-2 over the hybrid (sliced-ELL + COO spill) layout.

This is the ``mis2: pallas_hybrid`` engine: the PR 4 resident
``lax.while_loop`` (one dispatch, zero in-loop host syncs, on-device
worklist compaction), re-plumbed for the degree-aware layout of
``graphs.hybrid``.  Each round unrolls statically over the layout's
degree-bucket slices — one fused Pallas pass per slice per phase, the
slice worklist compacted on device from the global live/undecided masks —
and finishes the heavy-hitter rows with XLA segment reductions over the
sorted-COO spill.  Because every vertex lives in exactly one slice or the
spill, the per-partition scatters into the global ``[V]`` T/M state are
disjoint, and because refresh/decide of a row depend only on global state
reads plus that row's own adjacency, the final T is **bit-identical** to
the monolithic engines (``dense``, ``pallas_resident``) for equal options
— the standing digest-parity gate extends over adversarial degree
distributions in ``tests/test_hybrid.py``.

Traffic accounting: the loop state carries one int32 counter per slice
(live worklist rows processed, both phases), and the spill contributes
two segment sweeps per round.  The ``ELL_ROW_TRAFFIC``-style model
(``kernels.minprop_ell.ops.hybrid_row_traffic_bytes``) converts those
counts to bytes; the engine mirrors the total into the ``repro.obs``
registry (``mis2.hybrid_row_bytes``) and onto the result, and the
``hybrid_traffic`` gate in ``tools/check_shape.py`` asserts all three
agree.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs.handle import as_graph
from ..obs import metrics as _OBS
from ..obs import span as _obs_span
from .mis2 import (
    U32MAX,
    HotLoopStats,
    Mis2Options,
    Mis2Result,
    compact_worklist,
)
from .tuples import IN, id_bits, is_undecided

HYBRID_ROW_BYTES = "mis2.hybrid_row_bytes"


@functools.partial(jax.jit, static_argnames=(
    "priority", "max_iters", "b", "interpret"))
def _hybrid_fixed_point(slices, spill_rows, spill_seg, spill_cols, active,
                        *, priority: str, max_iters: int, b: int,
                        interpret: bool):
    """One jitted while_loop over global [V] state; rounds unroll over the
    slices (static: one compiled Pallas body per slice shape) and close
    with the spill segment passes.  Returns ``(t, iterations, undecided,
    slice_rows_processed)``."""
    from ..kernels.minprop_ell import ops as minprop_ops

    v = active.shape[0]
    num_slices = len(slices)
    h = spill_rows.shape[0]

    t0 = jnp.where(active, jnp.uint32(1), U32MAX)
    m0 = jnp.full(v, U32MAX, dtype=jnp.uint32)
    und0 = jnp.asarray(active)
    live0 = jnp.ones(v, dtype=bool)          # iteration 0: refresh every row
    acc0 = jnp.zeros(max(num_slices, 1), dtype=jnp.int32)
    state0 = (t0, m0, und0, live0, jnp.sum(und0, dtype=jnp.int32),
              jnp.uint32(0), acc0)

    def cond(state):
        _, _, _, _, n1, it, _ = state
        return (n1 > 0) & (it < max_iters)

    def body(state):
        t, m, und, live, _, it, acc = state
        # phase 1: M <- poisoned closed min, per slice then spill.  All
        # refresh passes read the same pre-round T (only M is written), so
        # partition order is immaterial.
        for i, sl in enumerate(slices):
            wl2, n2 = compact_worklist(live[sl.rows])
            m = minprop_ops.sliced_refresh_columns(
                t, m, sl.rows, sl.neighbors, wl2, n2, it,
                priority=priority, b=b, interpret=interpret)
            acc = acc.at[i].add(n2)
        if h > 0:
            m = minprop_ops.spill_refresh_columns(
                t, m, spill_rows, spill_seg, spill_cols, live, it,
                priority=priority, b=b)
        # phase 2: T <- IN/OUT decision.  Decide reads T only at its own
        # partition's rows and writes the same rows, so the per-slice
        # scatters never observe each other.
        for i, sl in enumerate(slices):
            wl1, n1_i = compact_worklist(und[sl.rows])
            t = minprop_ops.sliced_decide(
                t, m, active, sl.rows, sl.neighbors, wl1, n1_i, it,
                priority=priority, b=b, interpret=interpret)
            acc = acc.at[i].add(n1_i)
        if h > 0:
            t = minprop_ops.spill_decide(
                t, m, active, spill_rows, spill_seg, spill_cols, it,
                priority=priority, b=b)
        und = is_undecided(t)
        live = m != U32MAX
        return (t, m, und, live, jnp.sum(und, dtype=jnp.int32),
                it + jnp.uint32(1), acc)

    t, _, _, _, n1, it, acc = jax.lax.while_loop(cond, body, state0)
    return t, it, n1, acc


def _mis2_hybrid_impl(graph, active: Optional[np.ndarray] = None,
                      options: Optional[Mis2Options] = None, *,
                      interpret: Optional[bool] = None) -> Mis2Result:
    """Engine entry for ``pallas_hybrid``: one dispatch per solve over the
    degree-aware layout; works where the monolithic padded ELL cannot even
    be allocated."""
    from ..kernels._interpret import resolve_interpret
    from ..kernels.minprop_ell.ops import hybrid_row_traffic_bytes

    options = Mis2Options() if options is None else options
    if not options.worklists:
        raise ValueError(
            "pallas_hybrid implements §V-B worklist compaction by "
            "construction; use engine='dense' for the no-worklist ablation")
    if not (options.packed and options.layout == "ell"):
        raise ValueError(
            "pallas_hybrid requires packed tuples + the ELL-family layout "
            "(the hybrid format is a degree-bucketed ELL)")

    gh = as_graph(graph)
    hyb = gh.hybrid()
    v = hyb.num_vertices
    active_j = jnp.ones(v, dtype=bool) if active is None \
        else jnp.asarray(active)
    b = id_bits(v)
    interp = resolve_interpret(interpret)

    with _obs_span("mis2.hybrid_fixed_point", layout="hybrid",
                   num_slices=hyb.num_slices,
                   spill_rows=hyb.num_spill_rows, v=v) as sp:
        t, it, n1, acc = _hybrid_fixed_point(
            hyb.slices, hyb.spill_rows, hyb.spill_seg, hyb.spill_cols,
            active_j, priority=options.priority, max_iters=options.max_iters,
            b=b, interpret=interp)
        _OBS.counter(HotLoopStats._DISPATCHES).inc()
        jax.block_until_ready(t)    # span duration covers device execution
        sp.annotate(iterations=int(it))

    iterations = int(it)
    rows_processed = [int(x) for x in np.asarray(acc)[:hyb.num_slices]]
    spill_passes = 2 * iterations if hyb.num_spill_rows else 0
    row_bytes = hybrid_row_traffic_bytes(
        hyb.slice_widths, rows_processed, hyb.num_spill_entries, spill_passes)
    _OBS.counter(HYBRID_ROW_BYTES).inc(row_bytes)

    t_np = np.asarray(t)
    return Mis2Result(
        t_np == np.uint32(IN), iterations, int(n1) == 0,
        collectives={
            "variant": "hybrid",
            "row_bytes_total": row_bytes,
            "slice_widths": list(hyb.slice_widths),
            "slice_rows_processed": rows_processed,
            "spill_entries": hyb.num_spill_entries,
            "spill_passes": spill_passes,
        },
        num_compiles=1)
