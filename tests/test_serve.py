"""repro.serve: served results must be bit-identical to direct facade
calls — batching, caching, warm executables, and incremental repair are
throughput machinery, never semantics.

The digest/parity tests here are the serving analogue of the engine
digest-parity matrix in test_resident.py and are named so the CI serve
gate (`-k "digest or parity"`) picks them up.
"""
import importlib
import sys

import numpy as np
import pytest

import repro
from repro.api.backend import (
    Backend,
    default_mis2_engine,
    default_multilevel_engine,
)
from repro.core.mis2 import Mis2Options
from repro.graphs import er_laplacian, laplace3d, random_uniform_graph
from repro.serve import (
    Batcher,
    CacheParityError,
    PendingRequest,
    ResultCache,
    Server,
    ServerConfig,
    StreamSession,
    warm_buckets_for,
)

from conftest import verify_mis2


def _fleet():
    """Mixed-size workload: three bucket shapes, structure + matrix."""
    return [repro.Graph(laplace3d(4)),
            repro.Graph(laplace3d(5)),
            repro.Graph(random_uniform_graph(200, 5.0, seed=1)),
            repro.Graph(random_uniform_graph(150, 4.0, seed=2)),
            repro.Graph(random_uniform_graph(60, 3.0, seed=3))]


@pytest.fixture(scope="module")
def fleet():
    return _fleet()


# ---------------------------------------------------------------------------
# served digest == direct facade digest (the acceptance gate)
# ---------------------------------------------------------------------------

def test_served_mis2_digest_matches_facade_mixed_workload(fleet):
    cfg = ServerConfig(max_batch=4, warm_buckets=warm_buckets_for(fleet))
    srv = Server(cfg)
    futs = [srv.submit("mis2", g) for g in fleet]
    assert srv.flush() > 0
    for g, fut in zip(fleet, futs):
        served = fut.result()
        direct = repro.mis2(g)
        assert served.digest == direct.digest
        verify_mis2(g.csr, np.asarray(served.payload))


def test_served_color_coarsen_digest_matches_facade(fleet):
    srv = Server(ServerConfig(max_batch=4))
    color_futs = [srv.submit("color", g) for g in fleet]
    coarsen_futs = [srv.submit("coarsen", g) for g in fleet]
    srv.flush()
    for g, fut in zip(fleet, color_futs):
        assert fut.result().digest == repro.color(g).digest
    for g, fut in zip(fleet, coarsen_futs):
        assert fut.result().digest == repro.coarsen(g).digest


def test_served_amg_setup_digest_matches_facade():
    m = repro.Graph(er_laplacian(300, 6.0, seed=4))
    srv = Server(ServerConfig())
    served = srv.request("amg_setup", m, coarse_size=50)
    direct = repro.amg_setup(repro.Graph(er_laplacian(300, 6.0, seed=4)),
                             coarse_size=50)
    assert served.digest == direct.digest
    assert served.level_digests == direct.level_digests


def test_single_fast_path_digest_matches_facade(fleet):
    g = fleet[2]
    srv = Server(ServerConfig(single_fast_path=True))
    served = srv.request("mis2", g)
    assert srv.server_stats()["single_dispatches"] == 1
    assert served.digest == repro.mis2(g).digest


def test_explicit_engine_honored_digest(fleet):
    g = fleet[0]
    srv = Server(ServerConfig())
    served = srv.request("mis2", g, engine="dense")
    assert served.engine == "dense"
    assert served.digest == repro.mis2(g, engine="dense").digest


# ---------------------------------------------------------------------------
# cache: bitwise hits, parity assertions, byte-budget LRU
# ---------------------------------------------------------------------------

def test_cache_hit_returns_same_payload_bitwise(fleet):
    g = fleet[1]
    srv = Server(ServerConfig())
    first = srv.request("mis2", g)
    # a fresh handle over the same structure shares the canonical digest
    clone = repro.Graph(laplace3d(5))
    fut = srv.submit("mis2", clone)
    assert fut.done(), "identical resubmission must hit the cache"
    hit = fut.result()
    assert hit.digest == first.digest
    assert np.array_equal(np.asarray(hit.payload),
                          np.asarray(first.payload))
    assert hit.payload.tobytes() == first.payload.tobytes()
    stats = srv.server_stats()["cache"]
    assert stats["hits"] == 1 and stats["misses"] >= 1


def test_cache_parity_mode_recomputes_and_asserts(fleet):
    g = fleet[0]
    srv = Server(ServerConfig(parity_fraction=1.0))
    srv.request("mis2", g)
    srv.request("mis2", g)          # hit -> parity recompute
    stats = srv.server_stats()["cache"]
    assert stats["parity_checks"] == 1
    assert stats["parity_failures"] == 0


def test_cache_parity_failure_raises():
    cache = ResultCache(max_bytes=1 << 20, parity_fraction=1.0)

    class FakeResult:
        def __init__(self, digest):
            self.digest = digest
            self.payload = np.zeros(4)

    cache.insert(("k",), FakeResult("aaaa"))
    with pytest.raises(CacheParityError):
        cache.lookup(("k",), recompute=lambda: FakeResult("bbbb"))
    assert cache.stats.parity_failures == 1


def test_cache_eviction_respects_byte_budget():
    cache = ResultCache(max_bytes=2000)

    class R:
        def __init__(self, i):
            self.digest = f"{i:016x}"
            self.payload = np.zeros(100, dtype=np.float64)  # 800 B each

    for i in range(5):
        cache.insert(("g", i), R(i))
    assert cache.stats.bytes_used <= 2000
    assert cache.stats.evictions >= 3
    assert cache.lookup(("g", 0)) is None       # LRU: oldest evicted
    assert cache.lookup(("g", 4)) is not None   # newest survives


def test_cache_disabled_by_zero_budget(fleet):
    srv = Server(ServerConfig(cache_bytes=0))
    srv.request("mis2", fleet[0])
    fut = srv.submit("mis2", fleet[0])
    assert not fut.done()           # no cache -> queued, not resolved
    srv.flush()
    assert fut.result().digest == repro.mis2(fleet[0]).digest


# ---------------------------------------------------------------------------
# batcher: deadline-or-full dispatch with a manual clock
# ---------------------------------------------------------------------------

def _req(g, kind="mis2"):
    return PendingRequest(kind=kind, graph=repro.Graph(g),
                          params={"options": Mis2Options()}, engine=None,
                          backend=None, cache_key=(kind, id(g)))


def test_batcher_full_group_dispatches_immediately():
    b = Batcher(max_batch=3, max_delay_s=10.0)
    for _ in range(3):
        b.add(_req(laplace3d(3)), now=0.0)
    groups = b.due(now=0.0)
    assert len(groups) == 1 and len(groups[0][1]) == 3
    assert len(b) == 0


def test_batcher_partial_group_waits_for_deadline():
    b = Batcher(max_batch=8, max_delay_s=0.5)
    b.add(_req(laplace3d(3)), now=0.0)
    b.add(_req(laplace3d(3)), now=0.1)
    assert b.due(now=0.2) == []                 # budget not exhausted
    assert b.next_deadline(now=0.2) == pytest.approx(0.3)
    groups = b.due(now=0.5)                     # oldest waited 0.5s
    assert len(groups) == 1 and len(groups[0][1]) == 2


def test_batcher_force_flush_dispatches_everything():
    b = Batcher(max_batch=8, max_delay_s=100.0)
    b.add(_req(laplace3d(3)), now=0.0)
    b.add(_req(laplace3d(3), kind="color"), now=0.0)
    groups = b.due(now=0.0, force=True)
    assert len(groups) == 2                     # kinds never coalesce
    assert len(b) == 0


# ---------------------------------------------------------------------------
# per-request engine auto-selection (Backend honored at dispatch time)
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


def test_engine_resolution_honors_request_backend_platform():
    cpu_req = Backend(device=_FakeDevice("cpu"))
    tpu_req = Backend(device=_FakeDevice("tpu"))
    assert default_mis2_engine(cpu_req) == "compacted"
    assert default_mis2_engine(tpu_req) == "compacted_resident"
    assert default_mis2_engine(tpu_req.with_(pallas=True)) == \
        "pallas_resident"
    assert default_multilevel_engine(cpu_req) == "host"
    # XLA emulates f64 on the TPU (not IEEE): the AMG setup stays on the
    # host engine there; the integer-only cluster-GS setup goes resident
    assert default_multilevel_engine(tpu_req) == "host"
    assert default_multilevel_engine(tpu_req, kind="cluster_gs") == \
        "resident"
    gpu_req = Backend(device=_FakeDevice("gpu"))
    assert default_multilevel_engine(gpu_req) == "resident"
    # the worklists=False ablation still forces the host-driven driver
    assert default_mis2_engine(
        tpu_req, Mis2Options(worklists=False)) == "compacted"


def test_resident_amg_setup_refused_without_ieee_f64():
    """The resident AMG setup would build a different hierarchy on the
    TPU's emulated f64, so it is refused with a typed error before any
    placement or compile (and the serve path must not degrade it)."""
    from repro.api import InexactFloat64Error
    from repro.serve.faults import is_compile_error

    tpu_req = Backend(device=_FakeDevice("tpu"))
    g = repro.Graph(laplace3d(3))
    with pytest.raises(InexactFloat64Error, match="resident"):
        repro.amg_setup(g, engine="resident", backend=tpu_req)
    with pytest.raises(InexactFloat64Error):
        repro.amg_setup_batch([g], engine="resident", backend=tpu_req)
    try:
        repro.amg_setup(g, engine="resident", backend=tpu_req)
    except InexactFloat64Error as err:
        assert is_compile_error(err)


def test_server_resolves_engine_per_request(fleet):
    srv = Server(ServerConfig())
    req = PendingRequest(kind="mis2", graph=fleet[0],
                         params={"options": Mis2Options()}, engine=None,
                         backend=Backend(device=_FakeDevice("tpu")),
                         cache_key=())
    assert srv._resolve_engine(req) == "compacted_resident"
    req.backend = Backend(device=_FakeDevice("cpu"))
    assert srv._resolve_engine(req) == "compacted"
    req.engine = "dense"
    assert srv._resolve_engine(req) == "dense"


# ---------------------------------------------------------------------------
# warm-executable registry: jit churn accounting
# ---------------------------------------------------------------------------

def test_warm_registry_configured_shapes_cost_no_runtime_compiles(fleet):
    cfg = ServerConfig(max_batch=4, warm_buckets=warm_buckets_for(fleet))
    srv = Server(cfg)
    comp = srv.server_stats()["compiles"]
    assert comp["startup_aot"] == len(cfg.warm_buckets)
    for g in fleet:
        srv.submit("mis2", g)
    srv.flush()
    comp = srv.server_stats()["compiles"]
    assert comp["runtime_cold"] == 0


def test_warm_registry_counts_cold_shapes_once():
    # dedup=False: the pairs below are digest-equal on purpose (they must
    # form a real batch of 2 to exercise the warm-bucket path; with dedup
    # they would coalesce to a single fast-path dispatch)
    srv = Server(ServerConfig(max_batch=2, warm_buckets=(), dedup=False))
    g = repro.Graph(laplace3d(4))
    for _ in range(2):
        srv.submit("mis2", g)
        srv.submit("mis2", repro.Graph(laplace3d(4)))
        srv.flush()
        srv.cache.clear()           # force recomputation next round
    comp = srv.server_stats()["compiles"]
    assert comp["runtime_cold"] == 1        # same cold shape, counted once
    srv.reset_window()
    assert srv.server_stats()["compiles"]["runtime_cold_window"] == 0


# ---------------------------------------------------------------------------
# streaming: incremental-repair digest == from-scratch digest
# ---------------------------------------------------------------------------

def _random_delta(session, rng, n=3):
    v = session.graph.num_vertices
    adds = rng.integers(0, v, size=(n, 2))
    adds = adds[adds[:, 0] != adds[:, 1]]
    rows, cols = session._rows, session._cols
    offd = np.flatnonzero(rows != cols)
    pick = rng.choice(offd, size=min(n, len(offd)), replace=False)
    removes = np.stack([rows[pick], cols[pick]], axis=1)
    return adds, removes


@pytest.mark.parametrize("maker", [
    lambda: laplace3d(6),
    lambda: random_uniform_graph(300, 5.0, seed=11),
], ids=["laplace3d", "er"])
def test_incremental_repair_digest_matches_scratch(maker):
    rng = np.random.default_rng(5)
    session = StreamSession(maker(), check_fraction=1.0)
    v = session.graph.num_vertices
    localized = 0
    for _ in range(3):              # >= 3 delta sequences per graph family
        adds, removes = _random_delta(session, rng)
        repaired = session.apply_delta(adds, removes)
        scratch = repro.mis2(session.graph,
                             options=Mis2Options(priority="fixed"))
        assert repaired.digest == scratch.digest
        verify_mis2(session.graph.csr, np.asarray(repaired.payload))
        st = session.last_repair
        assert st.mode == "repair" and st.checked
        assert st.reactivated <= v
        localized += st.reactivated < v
    assert localized >= 1, "repair never localized below full recompute"


def test_streaming_nonfixed_priority_falls_back_to_recompute():
    session = StreamSession(laplace3d(4), options=Mis2Options())
    r = session.apply_delta([[0, 7]], None)
    assert session.last_repair.mode == "recompute"
    assert r.digest == repro.mis2(session.graph).digest


def test_server_open_stream_uses_config_check_fraction():
    srv = Server(ServerConfig(delta_check_fraction=1.0))
    session = srv.open_stream(laplace3d(4))
    session.apply_delta([[0, 9]], None)
    assert session.last_repair.checked


# ---------------------------------------------------------------------------
# threaded pump + shim + graph digest plumbing
# ---------------------------------------------------------------------------

def test_threaded_server_serves_without_explicit_flush(fleet):
    cfg = ServerConfig(max_batch=4, max_delay_s=0.005)
    with Server(cfg) as srv:
        futs = [srv.submit("mis2", g) for g in fleet]
        results = [f.result(timeout=60) for f in futs]
    for g, r in zip(fleet, results):
        assert r.digest == repro.mis2(g).digest


def test_launch_serve_shim_warns_and_reexports():
    sys.modules.pop("repro.launch.serve", None)
    with pytest.warns(DeprecationWarning, match="repro.serve"):
        mod = importlib.import_module("repro.launch.serve")
    assert mod.Server is Server
    assert mod.ServerConfig is ServerConfig


def test_graph_digest_canonical_and_cached():
    g1 = repro.Graph(laplace3d(4))
    g2 = repro.Graph(laplace3d(4))
    g3 = repro.Graph(laplace3d(5))
    assert g1.digest == g2.digest
    assert g1.digest != g3.digest
    _ = g1.digest
    assert g1.conversions.get("digest") == 1    # second access is cached
    # structure-only vs matrix handles differ (values are hashed)
    s = repro.Graph(laplace3d(4).graph)
    assert s.digest != g1.digest
