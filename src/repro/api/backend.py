"""Backend execution policy: one object that answers "where and how do the
hot loops run", threaded from the facade down to ``kernels/*/ops.py``.

Replaces the seed's scattered knobs — ``Mis2Options.use_pallas``, per-call
``interpret=True`` kwargs — with a single config:

* ``pallas``     route the measured hot loops through the Pallas kernels
  (``kernels/minprop_ell``); the XLA fallback otherwise.
* ``interpret``  tri-state.  ``None`` (default) = *auto*: run the Pallas
  interpreter only when no accelerator is attached (CPU hosts); compile
  for real on TPU/GPU.  The seed hard-coded ``interpret=True``, which
  silently ran the interpreter even on accelerators.
* ``device``     optional JAX device for graph/array placement.
* ``mesh`` / ``axis``  device mesh + partition axis for the distributed
  (shard_map) engines.  ``None`` (default) = one flat axis over every
  attached device; a multi-axis mesh with ``axis=None`` flattens all its
  axes into the vertex partition.

This module is import-cycle-safe by construction: it depends only on
``jax`` so both ``kernels/`` (below ``core``) and the facade (above it)
can consult the same policy.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, replace
from typing import Any, Optional

import jax


def accelerator_present() -> bool:
    """True iff the default JAX backend is an accelerator (TPU/GPU)."""
    return jax.default_backend() not in ("cpu",)


def backend_platform(backend: Optional["Backend"] = None) -> str:
    """The platform a request under ``backend`` actually executes on.

    ``Backend(device=...)`` pins placement per request, so engine
    auto-selection must consult *that* device's platform — not the
    process-global default backend.  A server coalescing requests from
    callers with different placements (the ``repro.serve`` dispatch path)
    would otherwise resolve every request against whatever platform the
    process booted with.
    """
    be = backend if backend is not None else _DEFAULT
    if be.device is not None:
        return be.device.platform
    return jax.default_backend()


def backend_accelerator(backend: Optional["Backend"] = None) -> bool:
    """True iff requests under ``backend`` run on an accelerator.

    With an explicit ``Backend(device=...)`` that device's platform
    decides; otherwise this defers to :func:`accelerator_present` (the
    process-global check — and the seam tests monkeypatch to simulate
    accelerators on the CPU CI runner).
    """
    be = backend if backend is not None else _DEFAULT
    if be.device is not None:
        return be.device.platform not in ("cpu",)
    return accelerator_present()


def default_interpret() -> bool:
    """The auto policy: interpret Pallas kernels only off-accelerator."""
    return not accelerator_present()


def default_mis2_engine(backend: Optional["Backend"] = None,
                        options=None, graph=None) -> str:
    """The facade's engine auto-selection rule (``engine=None``).

    On accelerators the fixed point runs device-resident — host-driven
    worklist rebuilds serialize the hot loop on dispatch + transfer
    latency, which is exactly the overhead §V-B exists to remove.  On CPU
    hosts the host-driven driver keeps the default (per-iteration numpy
    worklists are cheap there, and it is the Fig. 2 ablation baseline).
    ``Backend(pallas=True)`` upgrades either choice to its Pallas variant.
    All four engines produce bit-identical sets.

    ``options`` (a ``Mis2Options``) keeps the rule total: the resident
    engines implement §V-B worklists by construction, so the
    ``worklists=False`` ablation auto-selects the host-driven driver
    instead of raising even on accelerators.

    ``graph`` (a ``repro.Graph`` handle) enables the degree-aware rule:
    when the monolithic padded-ELL bytes estimate exceeds
    ``repro.graphs.hybrid.HYBRID_AUTO_BYTES`` (a skewed graph at paper
    scale), every ELL-monolith engine above is off the table — the rule
    returns ``'pallas_hybrid'`` (sliced-ELL + COO spill, O(E) memory,
    bit-identical results).  The threshold is read at call time so tests
    and operators can tune it.

    The platform is resolved **per request**: ``Backend(device=...)``
    selects by that device's platform, falling back to the process
    default backend only when no device is pinned (see
    :func:`backend_platform`).
    """
    be = backend if backend is not None else _DEFAULT
    resident_ok = options is None or getattr(options, "worklists", True)
    hybrid_ok = resident_ok and (
        options is None or (getattr(options, "packed", True)
                            and getattr(options, "layout", "ell") == "ell"))
    if hybrid_ok and graph is not None \
            and hasattr(graph, "ell_bytes_estimate"):
        from ..graphs import hybrid as _hybrid

        if graph.ell_bytes_estimate() > _hybrid.HYBRID_AUTO_BYTES:
            return "pallas_hybrid"
    if backend_accelerator(be) and resident_ok:
        return "pallas_resident" if be.pallas else "compacted_resident"
    return "pallas" if be.pallas else "compacted"


def default_misk_engine(backend: Optional["Backend"] = None) -> str:
    """``misk`` auto-selection (``engine=None``): always ``dense`` — the
    distance-k fixed point was born device-resident (one jitted
    ``while_loop``, zero in-loop host syncs), so unlike ``mis2`` there is
    no host-driven default to escape.  The ``resident`` engine (worklist
    compaction on the row refresh, the §V-B execution shape) exists for
    ablation and produces bit-identical sets."""
    return "dense"


#: platforms whose float64 arithmetic is not IEEE.  XLA emulates f64 on
#: the TPU: on a v5e, add, multiply, divide and sqrt of random operands
#: differ from numpy's correctly rounded results in over 70% of cases
#: (relative error up to 1.5e-10 for add).  The resident AMG setup does
#: its prolongator and Galerkin arithmetic in f64, so there it cannot
#: reproduce the host engine's hierarchy bit for bit.
INEXACT_F64_PLATFORMS = ("tpu",)


class InexactFloat64Error(NotImplementedError):
    """The resident AMG setup was asked to run on a platform whose f64
    arithmetic is not IEEE (:data:`INEXACT_F64_PLATFORMS`): its hierarchy
    would differ from the host engine's, so it is refused rather than
    returned as a different answer.  Use ``engine="host"`` (the
    ``engine=None`` choice there)."""


def check_exact_f64(engine: str, backend: Optional["Backend"] = None) -> None:
    """Raise :class:`InexactFloat64Error` for the resident AMG setup on a
    platform without IEEE float64."""
    platform = backend_platform(backend)
    if engine == "resident" and platform in INEXACT_F64_PLATFORMS:
        raise InexactFloat64Error(
            f"multilevel engine 'resident' cannot build a host-identical "
            f"AMG hierarchy on {platform!r}: XLA emulates float64 there "
            f"and does not round like IEEE; use engine='host'")


def default_multilevel_engine(backend: Optional["Backend"] = None,
                              kind: str = "amg") -> str:
    """``multilevel`` auto-selection (``engine=None``): the device-resident
    setup (on-device prolongator/Galerkin/packing, zero matrix-sized host
    syncs) on accelerators; the host scipy/numpy path on CPU hosts, where
    the round-trips are address-space copies.  Both engines produce
    digest-identical hierarchies — except the AMG setup (``kind="amg"``)
    on a platform without IEEE float64 (the TPU), where only ``host`` can,
    so ``host`` is chosen there.  Like :func:`default_mis2_engine`, the
    rule honors ``Backend(device=...)`` per request (the device's platform
    wins over the process default)."""
    if not backend_accelerator(backend):
        return "host"
    if kind == "amg" and backend_platform(backend) in INEXACT_F64_PLATFORMS:
        return "host"
    return "resident"


@dataclass(frozen=True)
class Backend:
    """Execution policy for one pipeline invocation (hashable, reusable)."""

    pallas: bool = False
    interpret: Optional[bool] = None   # None = auto (interpret iff no accel)
    device: Any = None                 # optional jax.Device for placement
    mesh: Any = None                   # optional jax.sharding.Mesh (sharding)
    axis: Any = None                   # mesh axis name (or tuple) to shard on

    def resolve_interpret(self) -> bool:
        """Interpret Pallas kernels iff requests under this backend run
        off-accelerator — a pinned ``device`` decides, like engine
        auto-selection (a CPU-pinned request on a TPU host interprets)."""
        if self.interpret is not None:
            return bool(self.interpret)
        return not backend_accelerator(self)

    def resolve_mesh(self):
        """(mesh, axis) for the distributed engines.

        ``Backend(mesh=..., axis=...)`` is honored as-is (``axis=None`` on
        a multi-axis mesh flattens every axis into the vertex partition);
        the default is one flat ``"x"`` axis over every attached device.
        The actual defaulting lives in ``core.dist._resolve_mesh`` so the
        facade path and direct core calls can never diverge.
        """
        from ..core.dist import _resolve_mesh

        mesh, axis, _ = _resolve_mesh(self.mesh, self.axis)
        return mesh, axis

    def with_(self, **changes) -> "Backend":
        return replace(self, **changes)


_DEFAULT = Backend()


def get_default_backend() -> Backend:
    return _DEFAULT


def set_default_backend(backend: Backend) -> Backend:
    """Install ``backend`` as the process-wide default; returns the old one."""
    global _DEFAULT
    old, _DEFAULT = _DEFAULT, backend
    return old


@contextmanager
def using_backend(backend: Backend):
    """Scoped default backend (restores the previous default on exit)."""
    old = set_default_backend(backend)
    try:
        yield backend
    finally:
        set_default_backend(old)


def resolve_backend(backend: Optional[Backend]) -> Backend:
    return backend if backend is not None else _DEFAULT
