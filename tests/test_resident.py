"""Device-resident MIS-2 hot loop (ISSUE 4): digest parity with the
host-driven engines across the full option matrix, zero host round-trips
inside the fixed point (one dispatch per solve), fused Pallas pass
bit-exactness, the fused passes' structure, and the jit-churn accounting."""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import verify_mis2
from repro import obs
from repro.api import Backend, Graph, Mis2Options, coarsen, color, mis2
from repro.core.mis2 import compact_worklist
from repro.graphs import csr_from_coo, laplace3d, random_uniform_graph

PRIORITIES = ("fixed", "xorshift", "xorshift_star")


def _torus(a, b):
    """4-regular a x b torus without self loops: every ELL row is full,
    so no row holds its own vertex, not even as padding."""
    i = np.arange(a * b)
    x, y = i % a, i // a
    cols = np.concatenate([(x + 1) % a + y * a, (x - 1) % a + y * a,
                           x + (y + 1) % b * a, x + (y - 1) % b * a])
    return csr_from_coo(np.tile(i, 4), cols, a * b)


def graph_cases():
    return {
        "laplace3d": Graph(laplace3d(8).graph),            # V = 512
        # varying degree: short rows are self-padded in the ELL layout
        "er_random": Graph(random_uniform_graph(600, 5.0, seed=21)),
        # PR 3's adversarial size: 1022 straddles the 1024 pow2 boundary
        "er_1022": Graph(random_uniform_graph(1022, 6.0, seed=9)),
        "no_self_loops": Graph(_torus(24, 25)),
        "single": Graph(csr_from_coo(np.array([0]), np.array([0]), 1)),
    }


def active_mask(kind, v):
    """``None`` (no caller mask), a partial mask, or no active vertex."""
    if kind == "all":
        return None
    if kind == "none":
        return np.zeros(v, dtype=bool)
    return np.random.default_rng(0).random(v) < 0.6


# ---------------------------------------------------------------------------
# digest-parity matrix: resident vs host-driven vs dense
# ---------------------------------------------------------------------------

# the first three keep their ids; the rest run the masked resident round
# (``compacted_resident``) against the host driver and ``dense`` on short
# self-padded rows, rows without their own vertex (not a MIS-2 input, but
# every engine must still agree) and V = 1, with no caller mask, a
# partial one and an empty one
PARITY_CASES = [pytest.param("laplace3d", "all", p, id=p)
                for p in PRIORITIES] + [
    pytest.param(g, kind, p, id=f"{g}-{kind}-{p}")
    for g, kinds in (("er_random", ("all", "partial", "none")),
                     ("no_self_loops", ("all", "partial", "none")),
                     ("single", ("all", "none")))
    for kind in kinds for p in PRIORITIES]


@pytest.mark.parametrize("gname,mask,priority", PARITY_CASES)
def test_resident_parity_priorities(gname, mask, priority):
    g = graph_cases()[gname]
    active = active_mask(mask, g.num_vertices)
    opts = Mis2Options(priority=priority)
    ref = mis2(g, active=active, options=opts, engine="compacted")
    if active is None and gname != "no_self_loops":
        verify_mis2(g.csr, ref.in_set)       # MIS-2 needs closed rows
    elif mask == "partial":                  # inactive slots of live rows
        nb = np.asarray(g.ell.neighbors)
        assert (active[:, None] & ~active[nb]).any()
    engines = ("compacted_resident", "pallas_resident", "dense") \
        if gname == "laplace3d" else ("compacted_resident", "dense")
    for eng in engines:
        r = mis2(g, active=active, options=opts, engine=eng)
        assert r.digest == ref.digest, (priority, eng)
        assert r.iterations == ref.iterations, (priority, eng)
        assert r.converged == ref.converged, (priority, eng)


@pytest.mark.parametrize("layout", ["ell", "csr_segment"])
@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("gname", ["er_random", "er_1022"])
def test_resident_parity_layout_packed(gname, layout, packed):
    g = graph_cases()[gname]
    opts = Mis2Options(layout=layout, packed=packed)
    a = mis2(g, options=opts, engine="compacted")
    b = mis2(g, options=opts, engine="compacted_resident")
    assert a.digest == b.digest, (gname, layout, packed)
    assert a.iterations == b.iterations, (gname, layout, packed)
    assert a.converged and b.converged


def test_resident_parity_active_mask():
    g = graph_cases()["er_random"]
    active = np.random.default_rng(0).random(600) < 0.6
    a = mis2(g, active=active, engine="compacted")
    for eng in ("compacted_resident", "pallas_resident"):
        r = mis2(g, active=active, engine=eng)
        assert r.digest == a.digest and r.iterations == a.iterations, eng
    assert not a.in_set[~active].any()


def test_resident_zero_active_vertices():
    g = graph_cases()["er_random"]
    active = np.zeros(600, dtype=bool)
    for eng in ("compacted", "compacted_resident", "pallas_resident"):
        r = mis2(g, active=active, engine=eng)
        assert r.iterations == 0 and r.converged and r.size == 0, eng


def test_resident_single_vertex():
    g = Graph(csr_from_coo(np.array([0]), np.array([0]), 1))
    ref = mis2(g, engine="compacted")
    for eng in ("compacted_resident", "pallas_resident", "dense"):
        r = mis2(g, engine=eng)
        assert r.digest == ref.digest and r.iterations == ref.iterations, eng
    assert ref.size == 1


def test_resident_rejects_no_worklist_ablation():
    g = graph_cases()["laplace3d"]
    with pytest.raises(ValueError, match="worklist"):
        mis2(g, options=Mis2Options(worklists=False),
             engine="compacted_resident")
    with pytest.raises(ValueError, match="packed"):
        mis2(g, options=Mis2Options(packed=False), engine="pallas_resident")


# ---------------------------------------------------------------------------
# execution shape: zero host round-trips, one dispatch per solve
# ---------------------------------------------------------------------------

def test_resident_zero_host_syncs_one_dispatch():
    g = graph_cases()["er_random"]
    mis2(g, engine="compacted_resident")        # warm the jit cache
    with obs.capture() as cap:
        r = mis2(g, engine="compacted_resident")
    assert r.iterations > 1                      # a real multi-round solve
    assert cap.value("mis2.host_syncs") == 0
    assert cap.value("mis2.resident_dispatches") == 1
    with obs.capture() as cap:
        mis2(g, engine="pallas_resident")
    assert cap.value("mis2.host_syncs") == 0
    assert cap.value("mis2.resident_dispatches") == 1


@pytest.mark.parametrize("caller_mask,gathers", [(False, 2), (True, 3)])
def test_masked_round_gathers_no_worklists(caller_mask, gathers):
    """The packed XLA resident program gathers ``t`` and ``m`` once each a
    round (plus the mask's per-slot table once per solve) and scatters
    nothing: no worklist indirection.  Its module keeps ``fixed_point`` in
    its name, which the benchmark's roofline reader matches."""
    import importlib
    import re

    from repro.graphs import csr_to_ell_graph

    mis2_mod = importlib.import_module("repro.core.mis2")
    nb = csr_to_ell_graph(laplace3d(6).graph).neighbors
    active = jnp.ones(nb.shape[0], dtype=bool) if caller_mask else None
    lowered = mis2_mod._resident_ell_fixed_point.lower(
        nb, active, priority="xorshift_star", packed=True, max_iters=128,
        b=8)
    ops = re.findall(r"=\s*\S+\s+(gather|scatter)\(",
                     lowered.compile().as_text())
    assert ops.count("gather") == gathers and "scatter" not in ops, ops
    module = lowered.compiler_ir("stablehlo").operation.attributes[
        "sym_name"].value
    assert "fixed_point" in module, module


def test_active_folds_counts_caller_masks():
    g = graph_cases()["laplace3d"]
    with obs.capture() as cap:
        mis2(g, engine="compacted_resident")
    assert cap.value("mis2.active_folds") == 0
    with obs.capture() as cap:
        r = coarsen(g, mis2_engine="compacted_resident")
    assert (r.phase == 2).any()                  # phase 2 ran, with a mask
    assert cap.value("mis2.active_folds") == 1
    assert cap.value("mis2.resident_dispatches") == 2


def test_host_driven_engine_pays_syncs_every_round():
    g = graph_cases()["er_random"]
    with obs.capture() as cap:
        r = mis2(g, engine="compacted")
    # 2 transfers (T and M) per fixed-point round to rebuild worklists
    assert cap.value("mis2.host_syncs") == 2 * r.iterations
    assert cap.value("mis2.resident_dispatches") == 0


def test_num_compiles_accounting():
    g = graph_cases()["er_random"]
    host = mis2(g, engine="compacted")
    res = mis2(g, engine="compacted_resident")
    # legacy driver: one specialization per distinct pow2 bucket pair
    assert host.num_compiles is not None and host.num_compiles >= 2
    assert res.num_compiles == 1
    # accounting is per solve, so it is stable across repeat solves
    assert mis2(g, engine="compacted").num_compiles == host.num_compiles


def test_compact_worklist_matches_flatnonzero():
    rng = np.random.default_rng(5)
    for frac in (0.0, 0.3, 1.0):
        mask = rng.random(777) < frac
        wl, n = compact_worklist(jnp.asarray(mask))
        wl, n = np.asarray(wl), int(n)
        idx = np.flatnonzero(mask)
        assert n == len(idx)
        assert (wl[:n] == idx).all()
        assert (wl[n:] == 777).all()             # sentinel-padded tail


# ---------------------------------------------------------------------------
# fused Pallas passes: bit-exact vs oracles, indices in (no row copies)
# ---------------------------------------------------------------------------

def _fused_inputs(v=700, deg=7.0, seed=3):
    from repro.graphs import csr_to_ell_graph

    ell = csr_to_ell_graph(random_uniform_graph(v, deg, seed=seed))
    rng = np.random.default_rng(seed)
    t = rng.integers(1, 2**32 - 2, size=v, dtype=np.uint32)
    t[rng.random(v) < 0.1] = 0                   # some IN
    t[rng.random(v) < 0.1] = np.uint32(0xFFFFFFFF)   # some OUT
    m = rng.integers(0, 2**32 - 1, size=v, dtype=np.uint32)
    active = rng.random(v) < 0.9
    wl = np.full(v, v, dtype=np.int32)
    live = rng.permutation(v)[: v // 2].astype(np.int32)
    wl[: len(live)] = live
    return ell, jnp.asarray(t), jnp.asarray(m), jnp.asarray(active), \
        jnp.asarray(wl), len(live)


@pytest.mark.parametrize("count_frac", [1.0, 0.4])
def test_fused_refresh_columns_bit_exact(count_frac):
    from repro.core.tuples import id_bits
    from repro.kernels.minprop_ell.kernel import fused_refresh_columns_pallas
    from repro.kernels.minprop_ell.ref import fused_refresh_columns_ref

    ell, t, m, active, wl, nlive = _fused_inputs()
    count = max(1, int(nlive * count_frac))
    b = id_bits(ell.num_vertices)
    it = jnp.uint32(4)
    out_k = fused_refresh_columns_pallas(
        t, jnp.asarray(ell.neighbors), wl,
        jnp.int32(count), it, priority="xorshift_star", b=b)
    out_r = fused_refresh_columns_ref(t, ell.neighbors, wl, count, it,
                                      "xorshift_star", b)
    assert (np.asarray(out_k)[:count] == np.asarray(out_r)[:count]).all()


@pytest.mark.parametrize("count_frac", [1.0, 0.4])
def test_fused_decide_bit_exact(count_frac):
    from repro.core.tuples import id_bits
    from repro.kernels.minprop_ell.kernel import fused_decide_pallas
    from repro.kernels.minprop_ell.ref import fused_decide_ref

    ell, t, m, active, wl, nlive = _fused_inputs(seed=8)
    count = max(1, int(nlive * count_frac))
    b = id_bits(ell.num_vertices)
    it = jnp.uint32(2)
    out_k = fused_decide_pallas(
        t, m, active, jnp.asarray(ell.neighbors), wl,
        jnp.int32(count), it, priority="xorshift_star", b=b)
    out_r = fused_decide_ref(t, m, active, ell.neighbors, wl, count, it,
                             "xorshift_star", b)
    assert (np.asarray(out_k)[:count] == np.asarray(out_r)[:count]).all()


def test_fused_wrappers_take_indices_not_gathered_rows():
    """Structural guarantee of the single row read: the fused wrappers
    consume worklist indices + the adjacency (the row gather traces into
    the same program as the kernel), not pre-gathered ``[W, D]`` row
    copies like the legacy pair."""
    import inspect

    from repro.kernels.minprop_ell import kernel

    legacy = inspect.signature(kernel.refresh_columns_pallas)
    fused = inspect.signature(kernel.fused_refresh_columns_pallas)
    assert "wl_neighbors" in legacy.parameters        # the [W, D] copy
    assert "wl_neighbors" not in fused.parameters
    assert {"neighbors", "wl"} <= set(fused.parameters)


# ---------------------------------------------------------------------------
# facade default selection + resident reuse in coloring/coarsening
# ---------------------------------------------------------------------------

def test_default_engine_rule(monkeypatch):
    from repro.api import backend as backend_mod

    monkeypatch.setattr(backend_mod, "accelerator_present", lambda: False)
    assert backend_mod.default_mis2_engine() == "compacted"
    assert backend_mod.default_mis2_engine(Backend(pallas=True)) == "pallas"
    monkeypatch.setattr(backend_mod, "accelerator_present", lambda: True)
    assert backend_mod.default_mis2_engine() == "compacted_resident"
    assert backend_mod.default_mis2_engine(
        Backend(pallas=True)) == "pallas_resident"


def test_default_engine_rule_is_total_over_options(monkeypatch):
    """The worklists=False ablation must auto-select the host-driven
    driver (which supports it) instead of raising, even on accelerators."""
    from repro.api import backend as backend_mod

    g = graph_cases()["laplace3d"]
    opts = Mis2Options(worklists=False)
    monkeypatch.setattr(backend_mod, "accelerator_present", lambda: True)
    assert backend_mod.default_mis2_engine(options=opts) == "compacted"
    r = mis2(g, options=opts)           # engine=None must not raise
    assert r.engine == "compacted" and r.converged


def test_legacy_worklists_reconverted_fresh_per_iteration():
    """The pad cache must never hand back an aliased staging buffer:
    wl1/wl2 of the same bucket size must be independent device arrays
    (jnp.asarray of an aligned numpy buffer can be zero-copy on CPU)."""
    from repro.core.mis2 import _WorklistPadCache

    pads = _WorklistPadCache(4096)
    a = pads.pad(np.arange(3000, dtype=np.int32))        # bucket 4096
    b = pads.pad(np.arange(4000, dtype=np.int32))        # same bucket
    assert (np.asarray(a)[:3000] == np.arange(3000)).all()
    assert (np.asarray(a)[3000:] == 4096).all()          # not b's contents
    assert (np.asarray(b)[:4000] == np.arange(4000)).all()


def test_facade_default_resolves_resident_on_accelerator(monkeypatch):
    from repro.api import backend as backend_mod

    g = graph_cases()["laplace3d"]
    base = mis2(g)                       # CPU host: host-driven default
    assert base.engine == "compacted"
    monkeypatch.setattr(backend_mod, "accelerator_present", lambda: True)
    r = mis2(g)
    assert r.engine == "compacted_resident"
    assert r.digest == base.digest       # the rule never changes results


def test_explicit_engine_still_honored():
    g = graph_cases()["laplace3d"]
    assert mis2(g, engine="dense").engine == "dense"
    assert mis2(g, engine="compacted_resident").engine == "compacted_resident"


def test_coarsen_inner_resident_engine_matches():
    g = graph_cases()["er_random"]
    a = coarsen(g, mis2_engine="compacted")
    b = coarsen(g, mis2_engine="compacted_resident")
    assert a.digest == b.digest
    assert (a.phase == b.phase).all() and (a.roots == b.roots).all()


def test_color_resident_loop_matches_legacy_rounds():
    """The coloring round loop is now one jitted while_loop; results and
    the do-while round count must match the old host-driven loop."""
    g = graph_cases()["er_random"]
    r = color(g)
    assert r.converged and r.num_colors > 0
    # rerun: deterministic, and at least one round always runs
    r2 = color(g)
    assert r2.digest == r.digest and r2.rounds == r.rounds >= 1
