"""Degree-aware hybrid layout (ISSUE 10): sliced-ELL + COO spill layout
invariants, digest parity of the ``pallas_hybrid`` MIS-2 engine (and the
hybrid coloring / coarsening paths) with the monolithic ELL engines across
priorities and adversarial degree distributions, the ELL byte-budget guard
and auto-selection rule, the work counts, and the serve-side
``LayoutInfeasible`` admission shed."""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import verify_mis2
from repro import obs
from repro.api import Graph, Mis2Options, coarsen, color, mis2
from repro.graphs import (
    HybridEllGraph,
    LayoutOverflowError,
    csr_from_coo,
    csr_to_hybrid_ell,
    laplace3d,
    powerlaw_graph,
    random_uniform_graph,
)
from repro.graphs import hybrid as hybrid_mod

PRIORITIES = ("fixed", "xorshift", "xorshift_star")


def graph_cases():
    return {
        "laplace3d": Graph(laplace3d(8).graph),            # bounded degree
        "er_random": Graph(random_uniform_graph(600, 5.0, seed=21)),
        "powerlaw": Graph(powerlaw_graph(900, 8.0, seed=4)),
    }


# ---------------------------------------------------------------------------
# layout invariants
# ---------------------------------------------------------------------------

def test_hybrid_partition_disjoint_and_covering():
    for name, g in graph_cases().items():
        hyb = g.hybrid()
        owned = np.concatenate(
            [np.asarray(sl.rows) for sl in hyb.slices]
            + [np.asarray(hyb.spill_rows)])
        assert len(owned) == g.num_vertices, name
        assert len(np.unique(owned)) == g.num_vertices, name


def test_hybrid_slab_content_matches_csr():
    g = graph_cases()["powerlaw"]
    indptr = np.asarray(g.csr.indptr)
    indices = np.asarray(g.csr.indices)
    hyb = g.hybrid()
    for sl in hyb.slices:
        rows = np.asarray(sl.rows)
        nbrs = np.asarray(sl.neighbors)
        mask = np.asarray(sl.mask)
        for j in (0, len(rows) // 2, len(rows) - 1):
            r = rows[j]
            want = indices[indptr[r]:indptr[r + 1]]
            assert np.array_equal(nbrs[j][mask[j]], want), (sl.width, r)
            # padding holds the row's own id (inert under closed reductions)
            assert (nbrs[j][~mask[j]] == r).all()
    # spill holds the heavy rows, CSR order
    seg = np.asarray(hyb.spill_seg)
    cols = np.asarray(hyb.spill_cols)
    for i, r in enumerate(np.asarray(hyb.spill_rows)):
        want = indices[indptr[r]:indptr[r + 1]]
        assert np.array_equal(cols[seg == i], want)
        assert len(want) > hyb.spill_cap


def test_hybrid_empty_buckets_skipped_and_widths_ascend():
    # bounded-degree mesh: exactly the buckets with rows, no spill
    g = graph_cases()["laplace3d"]
    hyb = g.hybrid()
    assert hyb.num_spill_rows == 0
    widths = hyb.slice_widths
    assert widths == tuple(sorted(widths))
    assert all(sl.num_rows > 0 for sl in hyb.slices)


def test_hybrid_forced_spill_lone_max_degree_row():
    g = graph_cases()["er_random"]
    deg = np.diff(np.asarray(g.csr.indptr))
    second = int(np.sort(deg)[-2])
    hyb = g.hybrid(spill_cap=max(second, hybrid_mod.MIN_SLICE_WIDTH))
    if deg.max() > max(second, hybrid_mod.MIN_SLICE_WIDTH):
        assert hyb.num_spill_rows == 1
        assert int(np.asarray(hyb.spill_rows)[0]) == int(deg.argmax())
    r = mis2(g, engine="pallas_hybrid")
    ref = mis2(g, engine="dense")
    assert r.digest == ref.digest


def test_hybrid_explicit_widths_must_cover():
    g = graph_cases()["er_random"]
    with pytest.raises(ValueError, match="do not cover"):
        csr_to_hybrid_ell(g.csr, widths=(4,), spill_cap=10_000)


def test_hybrid_single_vertex_graph():
    g = Graph(csr_from_coo(np.array([0]), np.array([0]), 1))
    hyb = g.hybrid()
    assert isinstance(hyb, HybridEllGraph)
    r = mis2(g, engine="pallas_hybrid")
    assert r.in_set.tolist() == [True]
    assert r.digest == mis2(g, engine="dense").digest


def test_hybrid_handle_caches_conversion():
    g = graph_cases()["er_random"]
    assert g.hybrid() is g.hybrid()
    assert g.hybrid(spill_cap=64) is not g.hybrid()


# ---------------------------------------------------------------------------
# digest-parity matrix: pallas_hybrid vs dense
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("priority", PRIORITIES)
@pytest.mark.parametrize("gname", ["laplace3d", "er_random", "powerlaw"])
def test_hybrid_parity(gname, priority):
    g = graph_cases()[gname]
    opts = Mis2Options(priority=priority)
    ref = mis2(g, options=opts, engine="dense")
    r = mis2(g, options=opts, engine="pallas_hybrid")
    assert r.digest == ref.digest, (gname, priority)
    assert r.iterations == ref.iterations, (gname, priority)
    assert r.converged
    verify_mis2(g.csr, r.in_set)


def test_hybrid_parity_active_mask():
    g = graph_cases()["powerlaw"]
    active = np.random.default_rng(2).random(g.num_vertices) < 0.6
    a = mis2(g, active=active, engine="dense")
    b = mis2(g, active=active, engine="pallas_hybrid")
    assert a.digest == b.digest
    assert not b.in_set[~active].any()


def test_hybrid_zero_active():
    g = graph_cases()["er_random"]
    active = np.zeros(g.num_vertices, dtype=bool)
    r = mis2(g, active=active, engine="pallas_hybrid")
    assert not r.in_set.any()
    assert r.converged


def test_hybrid_rejects_incompatible_options():
    g = graph_cases()["er_random"]
    with pytest.raises(ValueError, match="worklist"):
        mis2(g, options=Mis2Options(worklists=False), engine="pallas_hybrid")
    with pytest.raises(ValueError, match="packed"):
        mis2(g, options=Mis2Options(packed=False), engine="pallas_hybrid")


# ---------------------------------------------------------------------------
# coloring + coarsening over the hybrid layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gname", ["laplace3d", "powerlaw"])
def test_hybrid_coloring_parity(gname):
    g = graph_cases()[gname]
    a = color(g, engine="luby")
    b = color(g, engine="luby_hybrid")
    assert np.array_equal(a.colors, b.colors), gname
    assert a.num_colors == b.num_colors
    assert a.rounds == b.rounds


@pytest.mark.parametrize("method", ["basic", "two_phase"])
def test_hybrid_coarsen_parity(method):
    g = graph_cases()["powerlaw"]
    a = coarsen(g, method=method, mis2_engine="dense")
    b = coarsen(g, method=method, mis2_engine="pallas_hybrid")
    assert np.array_equal(a.labels, b.labels), method
    assert a.num_aggregates == b.num_aggregates
    assert np.array_equal(a.roots, b.roots)
    assert np.array_equal(a.phase, b.phase)


# ---------------------------------------------------------------------------
# byte budget, typed overflow, auto-selection
# ---------------------------------------------------------------------------

def test_ell_bytes_estimate():
    g = graph_cases()["powerlaw"]
    assert g.ell_bytes_estimate() == (g.num_vertices * g.max_degree
                                      * hybrid_mod.ELL_BYTES_PER_SLOT)


def test_layout_overflow_error(monkeypatch):
    g = graph_cases()["powerlaw"]
    monkeypatch.setattr(hybrid_mod, "ELL_BYTE_LIMIT",
                        g.ell_bytes_estimate() - 1)
    fresh = Graph(g.csr)                   # uncached handle
    with pytest.raises(LayoutOverflowError, match="pallas_hybrid") as ei:
        fresh.ell
    assert ei.value.estimate == g.ell_bytes_estimate()
    with pytest.raises(LayoutOverflowError):
        fresh.padded_ell(g.num_vertices, g.max_degree)
    # the degree-aware path still works on the same handle
    r = mis2(fresh, engine="pallas_hybrid")
    assert r.converged


def test_auto_selection_prefers_hybrid(monkeypatch):
    g = graph_cases()["powerlaw"]
    monkeypatch.setattr(hybrid_mod, "HYBRID_AUTO_BYTES",
                        g.ell_bytes_estimate() - 1)
    r = mis2(g)                            # engine=None -> auto
    assert r.engine == "pallas_hybrid"
    assert r.digest == mis2(g, engine="dense").digest
    # worklists=False ablation must keep the host-driven default
    r2 = mis2(g, options=Mis2Options(worklists=False))
    assert r2.engine != "pallas_hybrid"


def test_auto_selection_keeps_default_below_threshold(monkeypatch):
    g = graph_cases()["er_random"]
    monkeypatch.setattr(hybrid_mod, "HYBRID_AUTO_BYTES",
                        g.ell_bytes_estimate() + 1)
    assert mis2(g).engine != "pallas_hybrid"


# ---------------------------------------------------------------------------
# power-law generator
# ---------------------------------------------------------------------------

def test_powerlaw_deterministic_and_canonical():
    a = powerlaw_graph(2000, 8.0, seed=13)
    b = powerlaw_graph(2000, 8.0, seed=13)
    assert np.array_equal(np.asarray(a.indptr), np.asarray(b.indptr))
    assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
    c = powerlaw_graph(2000, 8.0, seed=14)
    assert not np.array_equal(np.asarray(a.indices), np.asarray(c.indices))
    # symmetric with a full diagonal (the repo-wide self-loop invariant)
    import scipy.sparse as sp
    ip, ix = np.asarray(a.indptr), np.asarray(a.indices)
    m = sp.csr_matrix((np.ones(len(ix)), ix, ip), shape=(2000, 2000))
    assert (m != m.T).nnz == 0
    assert (m.diagonal() == 1).all()


def test_powerlaw_degree_skew():
    g = powerlaw_graph(5000, 8.0, exponent=2.5, seed=3)
    deg = np.diff(np.asarray(g.indptr))
    # hub far above the mean: the regime where padded ELL explodes
    assert deg.max() > 20 * deg.mean()
    # ...but most rows stay near the mean (sliced ELL stays compact)
    assert np.percentile(deg, 95) < 8 * deg.mean()


# ---------------------------------------------------------------------------
# work counts + execution shape
# ---------------------------------------------------------------------------

def test_hybrid_traffic_registry_matches_model():
    """The registry's rounds and the result's work counts agree with the
    rounds run: two spill passes a round, one resident dispatch."""
    g = graph_cases()["powerlaw"]
    mis2(g, engine="pallas_hybrid")        # warm
    with obs.capture() as cap:
        r = mis2(g, engine="pallas_hybrid")
    c = r.collectives
    assert r.iterations > 1
    assert cap.value("mis2.rounds", {"layout": "hybrid"}) == r.iterations
    assert c["spill_entries"] > 0
    assert c["spill_passes"] == 2 * r.iterations
    assert len(c["slice_rows_processed"]) == len(c["slice_widths"])
    assert cap.value("mis2.resident_dispatches") == 1
    assert cap.value("mis2.host_syncs") == 0
    assert cap.value("jit.compiles") == 0
    assert r.num_compiles == 1


# ---------------------------------------------------------------------------
# serve: layout-infeasible admission shed
# ---------------------------------------------------------------------------

def test_serve_sheds_layout_infeasible(monkeypatch):
    from repro.serve import LayoutInfeasible, Server

    g = graph_cases()["powerlaw"]
    monkeypatch.setattr(hybrid_mod, "ELL_BYTE_LIMIT",
                        g.ell_bytes_estimate() - 1)
    monkeypatch.setattr(hybrid_mod, "HYBRID_AUTO_BYTES",
                        g.ell_bytes_estimate() // 2)
    srv = Server()
    try:
        with obs.capture() as cap:
            fut = srv.submit("mis2", g, engine="dense")
            with pytest.raises(LayoutInfeasible) as ei:
                fut.result(timeout=30)
        assert ei.value.reason == "layout"
        assert not ei.value.retryable
        assert cap.value("serve.shed", {"reason": "layout"}) == 1
        # degree-aware engines pass admission and serve correctly
        r = srv.request("mis2", g)
        assert r.engine == "pallas_hybrid"
        r2 = srv.request("mis2", g, engine="pallas_hybrid")
        assert r.digest == r2.digest
    finally:
        srv.stop()
