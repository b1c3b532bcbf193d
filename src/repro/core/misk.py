"""General distance-k MIS (Bell/Dalton/Olson formulation) — the paper's
baseline computes MIS-k for arbitrary k>=1 by k-fold min-propagation; the
paper's Algorithm 1 is the k=2 specialization.  We provide the general
version for completeness (k=1 gives Luby-style MIS-1; k=2 must agree with
Algorithm 1's *invariants*, asserted in tests).

Semantics per iteration (fresh priorities, like Alg. 1):
  M^0 = T;  M^j_v = min_{w in N[v]} M^(j-1)_w  (j = 1..k)
  v IN  if T_v == M^k_v  (v is the minimum of its distance-k neighborhood)
  v OUT if M^k_v is IN-adjacent (an IN vertex within distance k)
The IN-poisoning trick generalizes: after deciding IN vertices, propagate
OUT-ness k hops so every vertex within distance k of an IN is removed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import xla_ops
from .._compat import warn_deprecated
from ..graphs.handle import as_ell_graph
from .hashing import PRIORITY_FNS
from .mis2 import Mis2Result
from .tuples import IN, OUT, id_bits, is_undecided, pack


@functools.partial(jax.jit, static_argnames=("k", "priority", "max_iters"))
def _misk_fixpoint(neighbors, k: int, priority: str, max_iters: int):
    v = neighbors.shape[0]
    b = id_bits(v)
    vids = jnp.arange(v, dtype=jnp.uint32)
    prio_fn = PRIORITY_FNS[priority]
    t0 = jnp.full((v,), jnp.uint32(1))

    def cond(state):
        t, it = state
        return jnp.any(is_undecided(t)) & (it < max_iters)

    def body(state):
        t, it = state
        und = is_undecided(t)
        t = jnp.where(und, pack(prio_fn(it, vids), vids, b), t)
        # k-fold closed-neighborhood min
        m = t
        for _ in range(k):
            m = jnp.min(xla_ops.gather_rows(m, neighbors), axis=1)
        new_in = und & (m == t)
        t = jnp.where(new_in, IN, t)
        # propagate OUT-ness k hops from IN vertices
        near_in = (t == IN)
        for _ in range(k):
            near_in = jnp.any(xla_ops.gather_rows(near_in, neighbors),
                              axis=1) | near_in
        t = jnp.where(is_undecided(t) & near_in, OUT, t)
        return t, it + 1

    t, iters = jax.lax.while_loop(cond, body, (t0, jnp.uint32(0)))
    return t, iters


def _mis_k_impl(graph, k: int = 2, priority: str = "xorshift_star",
                max_iters: int = 256) -> Mis2Result:
    if k < 1:
        raise ValueError("k >= 1")
    ell = as_ell_graph(graph)
    t, iters = _misk_fixpoint(ell.neighbors, k, priority, max_iters)
    t_np = np.asarray(t)
    und = (t_np != np.uint32(IN)) & (t_np != np.uint32(OUT))
    return Mis2Result(t_np == np.uint32(IN), int(iters), not und.any())


# ---------------------------------------------------------------------------
# resident engine (the PR-4 hot-loop pattern applied to distance-k): the
# same per-round arithmetic, but the row refresh runs through an on-device
# compacted worklist (sentinel-V scatter-drop) and the whole fixed point is
# one jitted dispatch accounted in HOTLOOP_STATS — bit-identical to the
# dense engine (the refresh scatter touches exactly the undecided set).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "priority", "max_iters"))
def _misk_resident_fixpoint(neighbors, k: int, priority: str, max_iters: int):
    from .mis2 import compact_worklist

    v = neighbors.shape[0]
    b = id_bits(v)
    prio_fn = PRIORITY_FNS[priority]
    t0 = jnp.full((v,), jnp.uint32(1))
    wl0, n0 = compact_worklist(is_undecided(t0))

    def cond(state):
        _, _, n, it = state
        return (n > 0) & (it < max_iters)

    def body(state):
        t, wl, _, it = state
        rows = jnp.clip(wl, 0, v - 1)
        ids = rows.astype(jnp.uint32)
        told = t[rows]
        newt = pack(prio_fn(it, ids), ids, b)
        newt = jnp.where(is_undecided(told), newt, told)
        t = t.at[wl].set(newt, mode="drop")
        # k-fold closed-neighborhood min
        m = t
        for _ in range(k):
            m = jnp.min(xla_ops.gather_rows(m, neighbors), axis=1)
        new_in = is_undecided(t) & (m == t)
        t = jnp.where(new_in, IN, t)
        # propagate OUT-ness k hops from IN vertices
        near_in = (t == IN)
        for _ in range(k):
            near_in = jnp.any(xla_ops.gather_rows(near_in, neighbors),
                              axis=1) | near_in
        t = jnp.where(is_undecided(t) & near_in, OUT, t)
        wl, n = compact_worklist(is_undecided(t))
        return t, wl, n, it + jnp.uint32(1)

    t, _, n, iters = jax.lax.while_loop(cond, body, (t0, wl0, n0,
                                                     jnp.uint32(0)))
    return t, iters, n


def _misk_resident_impl(graph, k: int = 2, priority: str = "xorshift_star",
                        max_iters: int = 256) -> Mis2Result:
    """Engine entry for ``misk: resident`` — one jitted dispatch per solve
    (counted in ``mis2.resident_dispatches``)."""
    from ..obs import metrics as _obs
    from .mis2 import HotLoopStats

    if k < 1:
        raise ValueError("k >= 1")
    ell = as_ell_graph(graph)
    t, iters, n = _misk_resident_fixpoint(ell.neighbors, k, priority,
                                          max_iters)
    _obs.counter(HotLoopStats._DISPATCHES).inc()
    t_np = np.asarray(t)
    return Mis2Result(t_np == np.uint32(IN), int(iters), int(n) == 0,
                      num_compiles=1)


def mis_k(graph, k: int = 2, priority: str = "xorshift_star",
          max_iters: int = 256) -> Mis2Result:
    """Distance-k maximal independent set (deterministic, jitted).

    Deprecated entry point — use :func:`repro.api.misk`."""
    warn_deprecated("repro.core.misk.mis_k", "repro.api.misk")
    return _mis_k_impl(graph, k, priority, max_iters)
