"""Distributed MIS-2 and coarsening under shard_map (beyond-paper: the paper
is single device; we vertex-partition across a device mesh axis).

Layout: vertices are block-partitioned over the flattened mesh axis; each
device owns a contiguous row block of the ELL adjacency ``[V/P, D]`` and the
local slice of the tuple vector ``T``.  Neighbor ids are *global*, so every
iteration all-gathers the 4-byte/vertex tuple vectors ``T`` and ``M`` —
exactly 2·V·4 bytes of collective traffic per iteration, independent of |E|
(the compressed-tuple optimization §V-C is also a *communication*
optimization here: unpacked tuples would triple the collective bytes).  The
``single_gather`` variant halves that to V·4 bytes by recomputing the
distance-1 minima locally from the gathered T.
:func:`collective_bytes_per_iteration` is the analytic form of this model;
:func:`write_mis2_dryrun_record` persists it as the
``artifacts/dryrun_graph/mis2_*.json`` records that
``benchmarks/figs4_5_scaling.py`` axis B consumes (the HLO-derived
equivalent is ``repro.launch.graph_dryrun``).

A halo-exchange variant (send only boundary tuples) is sketched in §Perf;
for the paper's mesh-like graphs with bandwidth-reducing orderings the halo
is O(V^(2/3)) per device, but the all-gather version is the robust default
for arbitrary vertex orderings.

Determinism: priorities depend only on (iteration, global vertex id) and are
packed with ``b = id_bits(V_real)`` — the *real* vertex count, NOT the
device-padded one.  Packing with the padded count silently changed the
truncated priority bits whenever padding crossed a power-of-two boundary
(e.g. V=1022 on 8 devices pads to 1024: b jumps 10 -> 11), breaking bit
identity with the single-device dense engine — exactly the cross-platform
determinism the paper demonstrates.  The real V is threaded through every
entry point here; tested in tests/test_distributed.py via subprocess with 8
host devices.

Coarsening: the sharded helpers (:func:`join_adjacent_root_distributed`,
:func:`count_unagg_neighbors_distributed`, :func:`phase3_join_distributed`)
run the paper Alg. 2/3 label-propagation rounds as one label all-gather +
local rowwise joins per round, sharing the exact rowwise arithmetic with
``core.aggregation`` so distributed labels are bit-identical to the
single-device engines.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import xla_ops
from ..graphs.csr import ELLGraph
from ..graphs.handle import as_ell_graph, as_graph
from ..obs import metrics as _OBS
from .hashing import PRIORITY_FNS
from .mis2 import Mis2Options, Mis2Result
from .tuples import IN, OUT, id_bits, is_undecided, pack

TUPLE_BYTES = 4                        # one packed §V-C tuple

DRYRUN_GRAPH_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun_graph"


def _shard_map(fn, *, mesh, in_specs, out_specs):
    # the while_loop fixed point carries no varying-manual-axes rule
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _resolve_mesh(mesh: Optional[Mesh], axis):
    """Default mesh = every device on one flat axis; returns (mesh, axis, P)."""
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), ("x",))
        axis = "x"
    if axis is None:
        names = mesh.axis_names
        axis = names[0] if len(names) == 1 else tuple(names)
    axes = axis if isinstance(axis, tuple) else (axis,)
    nd = int(np.prod([mesh.shape[a] for a in axes]))
    return mesh, axis, nd


def pad_graph_for_mesh(ell: ELLGraph, num_devices: int):
    """Pad V to a multiple of num_devices with isolated, inactive vertices."""
    v = ell.num_vertices
    vp = ((v + num_devices - 1) // num_devices) * num_devices
    if vp == v:
        return ell, v
    neighbors = np.asarray(ell.neighbors)
    mask = np.asarray(ell.mask)
    extra = vp - v
    pad_nbrs = np.repeat(np.arange(v, vp, dtype=neighbors.dtype)[:, None],
                         ell.width, axis=1)
    pad_mask = np.zeros((extra, ell.width), dtype=bool)
    return ELLGraph(
        jnp.asarray(np.concatenate([neighbors, pad_nbrs])),
        jnp.asarray(np.concatenate([mask, pad_mask])),
    ), v


def prepare_padded(graph, mesh: Optional[Mesh] = None, axis=None):
    """Pad once and place the row-sharded adjacency on the mesh.

    Multi-call pipelines (distributed coarsening: 2 MIS-2 runs + up to ~6
    label-propagation rounds) pass the result through every sharded call so
    the O(V·D) host padding and the host->device upload happen exactly once
    — ``jax.device_put`` of an already-placed array is a no-op.
    """
    ell = as_graph(graph).ell
    mesh, axis, nd = _resolve_mesh(mesh, axis)
    padded, v = pad_graph_for_mesh(ell, nd)
    spec = NamedSharding(mesh, P(axis))
    return ELLGraph(jax.device_put(padded.neighbors, spec),
                    jax.device_put(padded.mask, spec)), v


def _mis2_local_fixpoint(neighbors_local, active_local, axis: str,
                         num_vertices: int, priority: str, max_iters: int,
                         single_gather: bool = False,
                         neighbors_global=None):
    """shard_map body: each device owns a row block; T (and M) all-gathered.

    ``num_vertices`` is the REAL vertex count — the packing bit width is
    ``b = id_bits(num_vertices)``, matching the single-device dense engine
    regardless of how much device padding the mesh forced (padded vertices
    are inactive and never pack a tuple, so ids >= num_vertices never hit
    the packer).

    ``single_gather=True`` (§Perf beyond-paper optimization): gather T once
    per iteration and recompute the distance-1 minima ``M`` for the whole
    graph locally from the gathered T (requires the full ELL adjacency
    ``neighbors_global`` replicated).  Trades O(V*D) redundant VPU mins —
    essentially free on mesh graphs — for HALF the collective bytes per
    iteration.
    """
    vp = neighbors_local.shape[0]
    b = id_bits(num_vertices)
    idx = jax.lax.axis_index(axis)
    vids = (idx * vp + jnp.arange(vp, dtype=jnp.uint32)).astype(jnp.uint32)
    prio_fn = PRIORITY_FNS[priority]

    t0 = jnp.where(active_local, jnp.uint32(1), OUT)
    # the active mask is loop-invariant: gather it ONCE, outside the
    # fixed-point body, so steady-state traffic is exactly the T (+ M)
    # gathers that collective_bytes_per_iteration() models
    a_global = jax.lax.all_gather(active_local, axis, tiled=True)
    an = xla_ops.gather_rows(a_global, neighbors_local)                     # [Vp, D]

    def cond(state):
        t_local, it = state
        n_und = jnp.sum((is_undecided(t_local) & active_local).astype(jnp.int32))
        n_und = jax.lax.psum(n_und, axis)
        return (n_und > 0) & (it < max_iters)

    def body(state):
        t_local, it = state
        und = is_undecided(t_local) & active_local
        t_local = jnp.where(und, pack(prio_fn(it, vids), vids, b), t_local)
        # collective 1: global tuple vector for the distance-1 min
        t_global = jax.lax.all_gather(t_local, axis, tiled=True)   # [V]
        if single_gather:
            # recompute M for ALL vertices locally: no second gather
            tn_all = xla_ops.gather_rows(t_global, neighbors_global)        # [V, D]
            m_global = jnp.min(tn_all, axis=1)
            m_global = jnp.where(m_global == IN, OUT, m_global)
        else:
            tn = xla_ops.gather_rows(t_global, neighbors_local)             # [Vp, D]
            m_local = jnp.min(tn, axis=1)
            m_local = jnp.where(m_local == IN, OUT, m_local)
            # collective 2: global M for the distance-2 decision
            m_global = jax.lax.all_gather(m_local, axis, tiled=True)
        mn = xla_ops.gather_rows(m_global, neighbors_local)
        any_out = jnp.any(jnp.where(an, mn, IN) == OUT, axis=1)
        all_eq = jnp.all(jnp.where(an, mn, t_local[:, None]) == t_local[:, None],
                         axis=1)
        t_local = jnp.where(und & any_out, OUT, t_local)
        t_local = jnp.where(und & ~any_out & all_eq, IN, t_local)
        return t_local, it + 1

    t_local, iters = jax.lax.while_loop(cond, body, (t0, jnp.uint32(0)))
    return t_local, jnp.full((1,), iters, jnp.uint32)


# ===========================================================================
# collective-traffic accounting (the §V-C communication model, per iteration)
# ===========================================================================

def collective_bytes_per_iteration(num_vertices: int, num_devices: int,
                                   single_gather: bool = False) -> dict:
    """Analytic per-iteration collective volume of the sharded fixed point.

    Each iteration all-gathers the packed tuple vector T (``two_gather``
    also gathers the distance-1 minima M): result bytes = Vp * 4 per gather,
    ring wire bytes per device = result * (P-1)/P.  The loop-invariant
    active-mask gather is hoisted out of the fixed point and excluded.
    """
    vp = ((num_vertices + num_devices - 1) // num_devices) * num_devices
    gathers = 1 if single_gather else 2
    result_bytes = vp * TUPLE_BYTES * gathers
    wire = result_bytes * (num_devices - 1) / max(1, num_devices)
    return {
        "gathers_per_iteration": gathers,
        "result_bytes_per_iteration": result_bytes,
        "wire_bytes_per_device_per_iteration": wire,
    }


def write_mis2_dryrun_record(v: int, d: int, num_devices: int,
                             single_gather: bool, max_iters: int = 16,
                             mesh_shape: Optional[str] = None,
                             out_dir=None) -> Path:
    """Write one analytic ``artifacts/dryrun_graph/mis2_*.json`` record in
    the schema ``benchmarks/figs4_5_scaling.py`` axis B consumes (same
    headline keys as the HLO-derived ``launch.graph_dryrun`` records;
    ``wire_bytes_per_device`` totals ``max_iters`` iterations).  The
    default mesh tag is ``p<N>`` so analytic files never collide with the
    ``AxB``-tagged HLO records; ``source`` records the provenance."""
    variant = "single_gather" if single_gather else "two_gather"
    mesh = mesh_shape or f"p{num_devices}"
    per = collective_bytes_per_iteration(v, num_devices, single_gather)
    rec = {
        "variant": variant, "V": v, "D": d, "mesh": mesh,
        "num_devices": num_devices, "max_iters": max_iters,
        "source": "analytic_model",
        "per_iteration": per,
        "wire_bytes_per_device":
            per["wire_bytes_per_device_per_iteration"] * max_iters,
    }
    out = Path(out_dir) if out_dir is not None else DRYRUN_GRAPH_DIR
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"mis2_{variant}__{mesh}.json"
    path.write_text(json.dumps(rec, indent=2) + "\n")
    return path


# ===========================================================================
# distributed MIS-2 (production engines: 'distributed', 'distributed_single_gather')
# ===========================================================================

def _mis2_distributed_impl(graph, active=None,
                           options: Optional[Mis2Options] = None, *,
                           mesh: Optional[Mesh] = None, axis=None,
                           single_gather: bool = False,
                           padded: Optional[ELLGraph] = None,
                           neighbors_replicated=None) -> Mis2Result:
    """Sharded MIS-2 returning a core :class:`Mis2Result` — bit-identical
    to ``engine="dense"`` for any device count (equal determinism digest).
    ``result.collectives`` carries the per-run collective-byte accounting.
    ``padded`` short-circuits the mesh padding (see :func:`prepare_padded`);
    ``neighbors_replicated`` short-circuits the ``single_gather`` variant's
    fully-replicated adjacency upload the same way."""
    options = Mis2Options() if options is None else options
    ell = as_ell_graph(graph)
    v = ell.num_vertices
    mesh, axis, nd = _resolve_mesh(mesh, axis)

    if padded is None:
        padded, _ = pad_graph_for_mesh(ell, nd)
    vp_total = padded.num_vertices
    if active is None:
        active_arr = jnp.arange(vp_total) < v
    else:
        active_arr = jnp.concatenate(
            [jnp.asarray(active), jnp.zeros(vp_total - v, bool)])

    spec_rows = P(axis)
    in_specs = [spec_rows, spec_rows]
    args = [jax.device_put(padded.neighbors, NamedSharding(mesh, spec_rows)),
            jax.device_put(active_arr, NamedSharding(mesh, spec_rows))]
    if single_gather:
        fn_core = lambda nbrs, act, nbrs_g: _mis2_local_fixpoint(  # noqa: E731
            nbrs, act, axis=axis, num_vertices=v, priority=options.priority,
            max_iters=options.max_iters, single_gather=True,
            neighbors_global=nbrs_g)
        in_specs.append(P())
        args.append(neighbors_replicated if neighbors_replicated is not None
                    else jax.device_put(padded.neighbors,
                                        NamedSharding(mesh, P())))
    else:
        fn_core = functools.partial(
            _mis2_local_fixpoint, axis=axis, num_vertices=v,
            priority=options.priority, max_iters=options.max_iters)
    fn = _shard_map(fn_core, mesh=mesh, in_specs=tuple(in_specs),
                    out_specs=(spec_rows, P(axis)))
    t, iters = fn(*args)
    t_np = np.asarray(t)[:v]
    act_np = np.asarray(active_arr)[:v]
    iterations = int(np.asarray(iters)[0])
    undecided = is_undecided(t_np) & act_np
    per = collective_bytes_per_iteration(v, nd, single_gather)
    variant = "single_gather" if single_gather else "two_gather"
    collectives = {
        "variant": variant,
        "num_devices": nd,
        "iterations": iterations,
        **per,
        "result_bytes_total": per["result_bytes_per_iteration"] * iterations,
        "wire_bytes_per_device":
            per["wire_bytes_per_device_per_iteration"] * iterations,
    }
    # mirror the analytic accounting into the process-wide registry so one
    # obs.snapshot() carries collective volume next to dispatches/compiles
    _OBS.counter("dist.collective_bytes", labels={"variant": variant}).inc(
        collectives["result_bytes_total"])
    _OBS.counter("dist.rounds", labels={"variant": variant}).inc(iterations)
    return Mis2Result(t_np == np.uint32(IN), iterations,
                      not undecided.any(), collectives)


def mis2_distributed(graph, mesh: Mesh | None = None, axis: str | None = None,
                     active=None, priority: str = "xorshift_star",
                     max_iters: int = 128, single_gather: bool = False):
    """Legacy tuple-returning entry point; prefer
    ``repro.api.mis2(g, engine="distributed")``.

    Returns (in_set bool [V], iterations). Bit-identical to mis2_dense.
    """
    r = _mis2_distributed_impl(
        graph, active, Mis2Options(priority=priority, max_iters=max_iters),
        mesh=mesh, axis=axis, single_gather=single_gather)
    return r.in_set, r.iterations


def lower_mis2_distributed(ell_spec, mesh: Mesh, axis: str, *,
                           num_vertices: int,
                           priority: str = "xorshift_star",
                           max_iters: int = 128):
    """Dry-run hook: lower+compile the distributed fixpoint from
    ShapeDtypeStructs (no allocation). Returns the lowered object.

    ``num_vertices`` is REQUIRED and must be the REAL vertex count — the
    id_bits packing width; ``ell_spec.shape[0]`` is the device-padded row
    count, and defaulting to it would re-introduce the padded-V
    determinism bug whenever padding crosses a power of two."""
    spec_rows = P(axis)
    fn = _shard_map(
        functools.partial(
            _mis2_local_fixpoint, axis=axis, num_vertices=num_vertices,
            priority=priority, max_iters=max_iters),
        mesh=mesh,
        in_specs=(spec_rows, spec_rows),
        out_specs=(spec_rows, P(axis)),
    )
    active_spec = jax.ShapeDtypeStruct((ell_spec.shape[0],), jnp.bool_)
    return jax.jit(fn).lower(ell_spec, active_spec)


# ===========================================================================
# distributed coarsening rounds (paper Alg. 2/3 label propagation, sharded)
# ===========================================================================
#
# Each helper is one shard_map call: all-gather the global label vector
# (V·4 bytes), then run the SAME rowwise join arithmetic as the
# single-device helpers in core.aggregation on the local row block — so
# the labels (and therefore the coarse graph) are bit-identical.

def _sharded_rows(body, mesh, axis, padded_ell, *row_arrays,
                  replicated=()):
    """Run ``body(neighbors_local, mask_local, row_ids, *locals, *reps)``
    over the row-sharded padded ELL; returns the gathered [Vp] result.

    Each call builds a fresh shard_map closure, so JAX re-traces per
    invocation (the padded adjacency upload IS cached via prepare_padded).
    A compile cache keyed on (mesh, axis, shapes) would amortize the ~8
    traces a distributed coarsen performs — follow-up work; at production
    graph sizes data movement, not tracing, dominates."""
    spec_rows = P(axis)
    vp = padded_ell.num_vertices

    def fn(nbrs_local, mask_local, *rest):
        vloc = nbrs_local.shape[0]
        idx = jax.lax.axis_index(axis)
        row_ids = (idx * vloc
                   + jnp.arange(vloc, dtype=nbrs_local.dtype))
        return body(nbrs_local, mask_local, row_ids, *rest)

    in_specs = [spec_rows, spec_rows] + [spec_rows] * len(row_arrays) \
        + [P()] * len(replicated)
    args = [jax.device_put(padded_ell.neighbors, NamedSharding(mesh, spec_rows)),
            jax.device_put(padded_ell.mask, NamedSharding(mesh, spec_rows))]
    for a in row_arrays:
        args.append(jax.device_put(jnp.asarray(a),
                                   NamedSharding(mesh, spec_rows)))
    for a in replicated:
        args.append(jax.device_put(jnp.asarray(a), NamedSharding(mesh, P())))
    sharded = _shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=spec_rows)
    out = sharded(*args)
    assert out.shape[0] == vp
    return out


def _pad_labels(arr: np.ndarray, vp: int, fill) -> np.ndarray:
    out = np.full(vp, fill, dtype=np.int32)
    out[: len(arr)] = arr
    return out


def join_adjacent_root_distributed(graph, root_label: np.ndarray,
                                   mesh: Optional[Mesh] = None,
                                   axis=None, padded=None) -> np.ndarray:
    """Sharded ``core.aggregation._join_adjacent_root``: one root-label
    all-gather + local rowwise min per call."""
    from .aggregation import INT32_MAX, _join_rows

    ell = as_graph(graph).ell
    v = ell.num_vertices
    mesh, axis, nd = _resolve_mesh(mesh, axis)
    if padded is None:
        padded, _ = pad_graph_for_mesh(ell, nd)
    rl = _pad_labels(np.asarray(root_label, dtype=np.int32),
                     padded.num_vertices, INT32_MAX)

    def body(nbrs_local, mask_local, row_ids, rl_local):
        rl_global = jax.lax.all_gather(rl_local, axis, tiled=True)
        return _join_rows(nbrs_local, rl_global)

    out = _sharded_rows(body, mesh, axis, padded, rl)
    return np.asarray(out)[:v]


def count_unagg_neighbors_distributed(graph, labels: np.ndarray,
                                      mesh: Optional[Mesh] = None,
                                      axis=None, padded=None) -> np.ndarray:
    """Sharded ``core.aggregation._count_unagg_neighbors``."""
    from .aggregation import _count_unagg_rows

    ell = as_graph(graph).ell
    v = ell.num_vertices
    mesh, axis, nd = _resolve_mesh(mesh, axis)
    if padded is None:
        padded, _ = pad_graph_for_mesh(ell, nd)
    lab = _pad_labels(np.asarray(labels, dtype=np.int32),
                      padded.num_vertices, 0)

    def body(nbrs_local, mask_local, row_ids, lab_local):
        lab_global = jax.lax.all_gather(lab_local, axis, tiled=True)
        return _count_unagg_rows(nbrs_local, mask_local, row_ids, lab_global)

    out = _sharded_rows(body, mesh, axis, padded, lab)
    return np.asarray(out)[:v]


def phase3_join_distributed(graph, labels: np.ndarray, aggsize: np.ndarray,
                            mesh: Optional[Mesh] = None,
                            axis=None, padded=None) -> np.ndarray:
    """Sharded ``core.aggregation._phase3_join`` (max-coupling leftover
    join against frozen tentative labels): label all-gather + local
    rowwise lexicographic argmin; aggregate sizes ride replicated."""
    from .aggregation import _phase3_rows

    ell = as_graph(graph).ell
    v = ell.num_vertices
    mesh, axis, nd = _resolve_mesh(mesh, axis)
    if padded is None:
        padded, _ = pad_graph_for_mesh(ell, nd)
    lab = _pad_labels(np.asarray(labels, dtype=np.int32),
                      padded.num_vertices, 0)

    def body(nbrs_local, mask_local, row_ids, lab_local, aggsize_rep):
        lab_global = jax.lax.all_gather(lab_local, axis, tiled=True)
        return _phase3_rows(nbrs_local, mask_local, row_ids, lab_global,
                            lab_local, aggsize_rep)

    out = _sharded_rows(body, mesh, axis, padded, lab,
                        replicated=(np.asarray(aggsize, dtype=np.int32),))
    return np.asarray(out)[:v]
