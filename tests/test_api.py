"""The `repro.api` facade: cached-format Graph handle, engine registry,
cross-engine determinism, the common Result protocol, and the deprecation
shims at the legacy entry points."""
import warnings

import numpy as np
import pytest

from conftest import verify_mis2
from repro import obs
from repro.api import (
    Backend,
    Graph,
    Mis2Options,
    amg,
    coarsen,
    color,
    get_engine,
    list_engines,
    mis2,
    misk,
    partition,
)
from repro.graphs import laplace3d, random_uniform_graph

ENGINES = ("dense", "compacted", "pallas")
PRIORITIES = ("fixed", "xorshift", "xorshift_star")


def graph_cases():
    return {
        "laplace3d": Graph(laplace3d(8).graph),
        "er_random": Graph(random_uniform_graph(1200, 6.0, seed=7)),
    }


# ---------------------------------------------------------------------------
# cross-engine determinism (the paper's portability claim, per engine pair)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("priority", PRIORITIES)
@pytest.mark.parametrize("gname", ["laplace3d", "er_random"])
def test_cross_engine_determinism(gname, priority):
    g = graph_cases()[gname]
    opts = Mis2Options(priority=priority)
    results = {e: mis2(g, options=opts, engine=e) for e in ENGINES}
    ref = results["compacted"]
    verify_mis2(g.csr, ref.in_set)
    for name, r in results.items():
        assert (r.in_set == ref.in_set).all(), (gname, priority, name)
        assert r.digest == ref.digest, (gname, priority, name)
        assert r.iterations == ref.iterations, (gname, priority, name)


# ---------------------------------------------------------------------------
# Graph handle: conversion caching
# ---------------------------------------------------------------------------

def test_graph_ell_conversion_runs_exactly_once():
    g = Graph(laplace3d(6).graph)
    with obs.capture() as cap:
        a = g.ell
        b = g.ell
        assert a is b
        assert cap.value("graph.conversions", {"kind": "csr_to_ell"}) == 1
        # three engines + coloring + coarsening share that single conversion
        mis2(g)
        mis2(g, engine="dense")
        mis2(g, engine="pallas")
        color(g)
        coarsen(g)
    assert cap.value("graph.conversions", {"kind": "csr_to_ell"}) == 1
    # the per-handle view agrees with the registry, and the work was timed
    assert g.conversions["csr_to_ell"] == 1
    assert g.conversion_timings["csr_to_ell"] >= 0.0


def test_graph_handle_of_handle_shares_cache():
    g = Graph(laplace3d(5).graph)
    g2 = Graph(g)
    _ = g.ell
    assert g2.conversions["csr_to_ell"] == 1
    assert g2.ell is g.ell


def test_graph_round_trip_and_stats():
    m = laplace3d(5)
    g = Graph(m)
    assert g.has_values
    assert g.num_vertices == m.num_rows
    assert g.ell_matrix.num_rows == m.num_rows
    s = g.stats()
    assert s["max_degree"] == 7 and s["has_values"]
    # ELL-seeded handles can go back to CSR
    h = Graph(g.ell)
    assert h.csr.num_vertices == g.num_vertices
    assert h.conversions["ell_to_csr"] == 1


def test_graph_structure_only_rejects_matrix_access():
    g = Graph(laplace3d(4).graph)
    with pytest.raises(ValueError):
        _ = g.csr_matrix


# ---------------------------------------------------------------------------
# engine registry
# ---------------------------------------------------------------------------

def test_registry_lists_and_aliases():
    eng = list_engines()
    assert set(ENGINES) <= set(eng["mis2"])
    assert {"basic", "two_phase", "serial"} <= set(eng["aggregation"])
    # legacy AGGREGATORS spellings stay routable as aliases
    assert get_engine("aggregation", "mis2_agg") is get_engine(
        "aggregation", "two_phase")
    assert get_engine("aggregation", "mis2_basic") is get_engine(
        "aggregation", "basic")


def test_registry_unknown_engine_lists_available():
    with pytest.raises(ValueError, match="compacted"):
        get_engine("mis2", "warp")
    with pytest.raises(ValueError, match="unknown"):
        mis2(Graph(laplace3d(4).graph), engine="nope")


# ---------------------------------------------------------------------------
# Result protocol: host-numpy payloads, digests, wall time
# ---------------------------------------------------------------------------

def test_result_protocol_payloads_are_host_numpy():
    g = graph_cases()["er_random"]
    results = [mis2(g), mis2(g, engine="dense"), color(g), coarsen(g),
               partition(g, 4), misk(g, k=2)]
    for r in results:
        assert type(r.payload) is np.ndarray, type(r.payload)
        assert r.digest and len(r.digest) == 16
        assert r.wall_time_s >= 0.0
    assert results[0].payload.dtype == np.bool_
    assert results[2].payload.dtype == np.int32


def test_digest_distinguishes_different_outputs():
    g = graph_cases()["er_random"]
    a = mis2(g, options=Mis2Options(priority="fixed"))
    b = mis2(g, options=Mis2Options(priority="xorshift_star"))
    assert a.digest != b.digest  # different priorities, different sets


def test_amg_setup_result():
    h = amg(Graph(laplace3d(10)), aggregation="two_phase", coarse_size=64)
    assert h.num_levels >= 2
    assert h.level_sizes[0][0] == 1000
    assert h.converged and h.hierarchy is not None
    from repro.solvers import cg
    from repro.graphs.ops import spmv_ell

    g = Graph(laplace3d(10))
    b = np.random.default_rng(0).standard_normal(1000).astype(np.float32)
    import jax.numpy as jnp

    res = cg(lambda x: spmv_ell(g.ell_matrix, x), jnp.asarray(b),
             precond=h.as_precond(), tol=1e-8, maxiter=100)
    assert res.converged


# ---------------------------------------------------------------------------
# Backend policy
# ---------------------------------------------------------------------------

def test_backend_interpret_auto_matches_device():
    import jax

    auto = Backend()
    assert auto.resolve_interpret() == (jax.default_backend() == "cpu")
    assert Backend(interpret=True).resolve_interpret() is True
    assert Backend(interpret=False).resolve_interpret() is False


def test_backend_interpret_follows_pinned_device(monkeypatch):
    """A request pinned to the CPU device interprets even when the
    process default backend is an accelerator."""
    import jax

    from repro.api import backend as backend_mod

    monkeypatch.setattr(backend_mod, "accelerator_present", lambda: True)
    assert Backend().resolve_interpret() is False
    cpu = Backend(device=jax.devices("cpu")[0])
    assert cpu.resolve_interpret() is True


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache sits at the fixed in-checkout path."""
    import jax

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.setup_compile_cache()
        assert got == str(compile_cache.DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert (compile_cache.DEFAULT_CACHE_DIR.parent / "src" / "repro"
                ).is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_backend_threads_through_pallas_engine():
    g = graph_cases()["laplace3d"]
    base = mis2(g)
    pal = mis2(g, engine="pallas", backend=Backend(interpret=True))
    assert pal.digest == base.digest


# ---------------------------------------------------------------------------
# deprecation shims: old names work, but warn
# ---------------------------------------------------------------------------

def _assert_warns_deprecated(fn):
    with warnings.catch_warnings(record=True) as log:
        warnings.simplefilter("always")
        out = fn()
    assert any(issubclass(w.category, DeprecationWarning) for w in log)
    return out


def test_legacy_entry_points_warn_and_agree():
    from repro.core.aggregation import aggregate_two_phase
    from repro.core.coloring import color_graph
    from repro.core.mis2 import mis2 as old_mis2

    g = laplace3d(6).graph
    old = _assert_warns_deprecated(lambda: old_mis2(g))
    assert (old.in_set == mis2(Graph(g)).in_set).all()
    oldc = _assert_warns_deprecated(lambda: color_graph(g))
    assert (oldc.colors == color(Graph(g)).colors).all()
    olda = _assert_warns_deprecated(lambda: aggregate_two_phase(g))
    assert (olda.labels == coarsen(Graph(g)).labels).all()


def test_legacy_use_pallas_flag_warns_and_matches_pallas_engine():
    g = laplace3d(6).graph
    opts = _assert_warns_deprecated(lambda: Mis2Options(use_pallas=True))
    from repro.core.mis2 import _mis2_compacted_impl

    r = _mis2_compacted_impl(Graph(g), options=opts)
    assert (r.in_set == mis2(Graph(g), engine="pallas").in_set).all()


def test_legacy_aggregators_mapping_warns():
    from repro.solvers.amg import AGGREGATORS

    fn = _assert_warns_deprecated(lambda: AGGREGATORS["mis2_agg"])
    out = fn(laplace3d(5).graph)
    assert out.num_aggregates > 0
