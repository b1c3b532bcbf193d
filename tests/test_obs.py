"""repro.obs (ISSUE 7): metrics registry semantics, context-scoped
capture, cardinality bounding, exporters, span tracing, facade
provenance, legacy-stats shims, clock monotonicity, and the one-snapshot
whole-process view a mixed workload must produce."""
import json
import os
import re
import sys
import time

import numpy as np
import pytest

from repro import obs
from repro.api import Graph, amg_setup, coarsen, color, mis2
from repro.graphs import laplace3d, random_uniform_graph
from repro.obs import CardinalityError, MetricsRegistry, Snapshot

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("x.calls").inc()
    reg.counter("x.calls").inc(2)
    reg.gauge("x.level").set(7)
    reg.gauge("x.level").add(-2)
    h = reg.histogram("x.seconds", labels={"phase": "a"})
    h.observe(0.5)
    h.observe(1.5)
    snap = reg.snapshot()
    assert snap.value("x.calls") == 3
    assert snap.value("x.level") == 5
    assert snap.value("x.seconds", {"phase": "a"}) == 2.0   # sum
    assert snap.count("x.seconds", {"phase": "a"}) == 2
    assert h.stats["mean"] == 1.0
    assert h.stats["min"] == 0.5 and h.stats["max"] == 1.5


def test_registry_kind_conflict_rejected():
    reg = MetricsRegistry()
    reg.counter("x.thing")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x.thing")


def test_registry_labels_order_insensitive():
    reg = MetricsRegistry()
    reg.counter("x.c", labels={"a": 1, "b": 2}).inc()
    reg.counter("x.c", labels={"b": 2, "a": 1}).inc()
    assert reg.snapshot().value("x.c", {"a": 1, "b": 2}) == 2


def test_registry_total_sums_across_label_sets():
    reg = MetricsRegistry()
    reg.counter("x.c", labels={"k": "a"}).inc(2)
    reg.counter("x.c", labels={"k": "b"}).inc(3)
    assert reg.snapshot().total("x.c") == 5


def test_reset_zeros_in_place_and_handles_stay_valid():
    reg = MetricsRegistry()
    c = reg.counter("x.calls")
    c.inc(5)
    reg.reset()
    assert reg.snapshot().value("x.calls") == 0
    c.inc()                                  # cached handle still writes
    assert reg.snapshot().value("x.calls") == 1


def test_reset_prefix_scopes():
    reg = MetricsRegistry()
    reg.counter("a.one").inc()
    reg.counter("b.two").inc()
    reg.reset("a.")
    snap = reg.snapshot()
    assert snap.value("a.one") == 0
    assert snap.value("b.two") == 1


def test_capture_is_delta_scoped_not_global():
    reg = MetricsRegistry()
    reg.counter("x.calls").inc(100)          # pre-existing traffic
    with reg.capture() as outer:
        reg.counter("x.calls").inc()
        with reg.capture() as inner:         # concurrent capture: no clobber
            reg.counter("x.calls").inc(2)
        reg.counter("x.calls").inc(4)
    assert inner.value("x.calls") == 2
    assert outer.value("x.calls") == 7
    assert reg.snapshot().value("x.calls") == 107


def test_snapshot_delta_drops_zero_series_keeps_gauges():
    reg = MetricsRegistry()
    reg.counter("x.a").inc(5)
    reg.gauge("x.g").set(3)
    before = reg.snapshot()
    reg.counter("x.b").inc()
    after = reg.snapshot()
    d = after.delta(before)
    assert d.value("x.b") == 1
    assert d.value("x.a") == 0               # unchanged counter dropped
    assert d.value("x.g") == 3               # gauge keeps current reading
    assert all(s.name != "x.a" for s in d)


# ---------------------------------------------------------------------------
# cardinality bounding (satellite: reject unbounded label values)
# ---------------------------------------------------------------------------

def test_label_cardinality_rejects_long_values():
    reg = MetricsRegistry()
    digest64 = "a" * 64                      # a raw sha256 hexdigest
    with pytest.raises(CardinalityError, match="span attrs"):
        reg.counter("x.c", labels={"digest": digest64})


def test_label_cardinality_rejects_unboundedly_many_series():
    reg = MetricsRegistry()
    with pytest.raises(CardinalityError, match="label sets"):
        for i in range(reg.max_series_per_metric + 1):
            reg.counter("x.c", labels={"i": i}).inc()
    # other metrics are unaffected by one metric hitting its cap
    reg.counter("y.ok").inc()


def test_label_value_token_charset():
    reg = MetricsRegistry()
    reg.counter("x.c", labels={"k": "csr_to_ell"}).inc()     # fine
    reg.counter("x.c", labels={"k": "a/b:c+d-e.f"}).inc()    # fine
    with pytest.raises(CardinalityError):
        reg.counter("x.c", labels={"k": "has spaces"})


# ---------------------------------------------------------------------------
# exporters (satellite: Prometheus parses, JSON round-trips)
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(# TYPE [a-zA-Z_][a-zA-Z0-9_]* (counter|gauge|summary)"
    r"|[a-zA-Z_][a-zA-Z0-9_]*(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? -?[0-9.e+-]+)$")


def test_prometheus_exposition_parses_line_by_line():
    reg = MetricsRegistry()
    reg.counter("mis2.resident_dispatches").inc(3)
    reg.gauge("serve.cache.bytes_used").set(1024)
    reg.histogram("span.seconds", labels={"span": "api.mis2"}).observe(0.25)
    text = obs.to_prometheus(reg.snapshot())
    lines = text.strip().split("\n")
    assert lines, "empty exposition"
    for line in lines:
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"
    assert "# TYPE repro_mis2_resident_dispatches counter" in lines
    assert "repro_mis2_resident_dispatches 3" in lines
    assert 'repro_span_seconds_count{span="api.mis2"} 1' in lines


def test_json_export_round_trips_exactly():
    reg = MetricsRegistry()
    reg.counter("x.c", labels={"k": "v"}).inc(2)
    reg.gauge("x.g").set(1.5)
    reg.histogram("x.h").observe(3.0)
    snap = reg.snapshot()
    back = obs.from_json(obs.to_json(snap))
    assert isinstance(back, Snapshot)
    assert back.to_json() == snap.to_json()
    assert back.flat() == snap.flat()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_metric_attribution():
    with obs.span("outer", job="t") as outer:
        obs.metrics.counter("spantest.outer_work").inc()
        assert obs.current_span() is outer
        with obs.span("inner") as inner:
            obs.metrics.counter("spantest.inner_work").inc(2)
    assert obs.current_span() is None
    assert [c.name for c in outer.children] == ["inner"]
    assert outer.metrics["spantest.outer_work"] == 1
    assert outer.metrics["spantest.inner_work"] == 2     # nested included
    # gauges report level (not delta) in span metrics, so ambient gauges
    # set by earlier tests may appear — assert on counters only
    inner_counters = {k: v for k, v in inner.metrics.items()
                      if k.startswith("spantest.")}
    assert inner_counters == {"spantest.inner_work": 2.0}
    assert outer.duration_s >= inner.duration_s >= 0.0
    d = outer.to_dict()
    json.dumps(d)                                        # serializable
    assert d["attrs"] == {"job": "t"}
    assert obs.snapshot().count("span.seconds", {"span": "inner"}) >= 1
    assert outer in obs.recent_spans(5)


def test_span_annotate_coerces_non_scalars():
    with obs.span("anno", arr=np.arange(3)) as sp:
        sp.annotate(n=np.int64(7))
    assert isinstance(sp.attrs["arr"], str)
    assert isinstance(sp.attrs["n"], str)
    json.dumps(sp.to_dict())


# ---------------------------------------------------------------------------
# facade provenance (acceptance: every facade Result carries it)
# ---------------------------------------------------------------------------

def test_every_facade_result_carries_provenance():
    g = Graph(laplace3d(6).graph)
    m = Graph(laplace3d(6))
    calls = {
        "mis2": lambda: mis2(g),
        "color": lambda: color(g),
        "coarsen": lambda: coarsen(g),
        "amg_setup": lambda: amg_setup(m, coarse_size=24, max_levels=3),
    }
    for kind, call in calls.items():
        r = call()
        p = r.provenance
        assert p is not None, kind
        assert p.kind == kind
        assert p.digest == r.digest
        assert p.backend in ("cpu", "gpu", "tpu")
        assert p.span["name"] == f"api.{kind}"
        assert p.wall_time_s > 0.0
        # round-trips through JSON without loss
        back = obs.Provenance.from_json(p.to_json())
        assert back.as_dict() == p.as_dict()


def test_mis2_provenance_metrics_show_execution_shape():
    g = Graph(random_uniform_graph(500, 5.0, seed=4))
    mis2(g, engine="compacted_resident")     # warm
    r = mis2(g, engine="compacted_resident")
    assert r.provenance.metrics.get("mis2.resident_dispatches") == 1
    assert "mis2.host_syncs" not in r.provenance.metrics   # zero => dropped


def test_batch_results_share_batch_provenance():
    from repro.api import mis2_batch

    gs = [Graph(random_uniform_graph(200, 4.0, seed=s)) for s in (1, 2, 3)]
    batch = mis2_batch(gs)
    assert batch.provenance is not None
    assert batch.provenance.kind == "mis2_batch"
    for r in batch.results:
        assert r.provenance is batch.provenance


def test_streaming_repair_provenance_and_counters():
    g = Graph(random_uniform_graph(300, 5.0, seed=6))
    from repro.serve import StreamSession

    sess = StreamSession(g)
    with obs.capture() as cap:
        r = sess.apply_delta(edge_adds=[(0, 7), (11, 23)])
    assert r.provenance is not None
    assert r.provenance.span["name"] == "serve.repair"
    assert r.provenance.engine == "stream_repair"
    assert cap.value("serve.repair.deltas", {"mode": "repair"}) == 1
    assert cap.value("serve.repair.reactivated") == sess.last_repair.reactivated


# ---------------------------------------------------------------------------
# legacy shims: same numbers on both surfaces
# ---------------------------------------------------------------------------

def test_hotloop_stats_is_a_registry_view():
    from repro.core.mis2 import HOTLOOP_STATS

    base = obs.snapshot().value("mis2.resident_dispatches")
    assert HOTLOOP_STATS.resident_dispatches == base
    HOTLOOP_STATS.resident_dispatches += 1
    assert obs.snapshot().value("mis2.resident_dispatches") == base + 1
    obs.metrics.counter("mis2.resident_dispatches").inc()
    assert HOTLOOP_STATS.resident_dispatches == base + 2


def test_setup_stats_is_a_registry_view():
    from repro.multilevel import SETUP_STATS

    base = obs.snapshot().value("multilevel.host_syncs")
    SETUP_STATS.host_syncs += 3
    assert obs.snapshot().value("multilevel.host_syncs") == base + 3


def test_cache_stats_mirror_into_registry():
    from repro.serve.cache import ResultCache

    cache = ResultCache(max_bytes=1 << 20)
    with obs.capture() as cap:
        assert cache.lookup(("k",)) is None
        r = mis2(Graph(laplace3d(4).graph))
        cache.insert(("k",), r)
        assert cache.lookup(("k",)) is r
    assert cap.value("serve.cache.misses") == 1
    assert cap.value("serve.cache.hits") == 1
    assert cap.value("serve.cache.inserts") == 1
    # per-instance truth preserved alongside the process aggregate
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert obs.snapshot().value("serve.cache.bytes_used") >= \
        cache.stats.bytes_used


# ---------------------------------------------------------------------------
# clock monotonicity (satellite: one clock, perf_counter, everywhere)
# ---------------------------------------------------------------------------

def test_server_intervals_are_perf_counter_monotone():
    from repro.serve import Server, ServerConfig

    srv = Server(ServerConfig(max_batch=2, max_delay_s=0.0))
    s1 = srv.server_stats()
    time.sleep(0.01)
    s2 = srv.server_stats()
    assert 0.0 <= s1["uptime_s"] <= s2["uptime_s"]
    assert s2["compiles"]["window_s"] <= s2["uptime_s"]
    srv.reset_window()
    s3 = srv.server_stats()
    assert s3["compiles"]["window_s"] <= s2["compiles"]["window_s"]
    assert s3["uptime_s"] >= s2["uptime_s"]              # never jumps back


def test_serve_stats_clock_is_perf_counter():
    import inspect

    from repro.serve import server as server_mod

    src = inspect.getsource(server_mod)
    assert "time.monotonic" not in src
    src = inspect.getsource(sys.modules["repro.serve.cache"])
    assert "time.monotonic" not in src


# ---------------------------------------------------------------------------
# the acceptance snapshot: one obs.snapshot() sees every subsystem
# ---------------------------------------------------------------------------

def test_mixed_workload_single_snapshot_covers_all_subsystems():
    from repro.serve import Server, ServerConfig

    with obs.capture() as cap:
        g = Graph(random_uniform_graph(400, 5.0, seed=8))
        mis2(g, engine="compacted_resident")             # device-resident
        mis2(g, engine="compacted")                      # host-driven syncs
        amg_setup(Graph(laplace3d(6)), engine="resident",
                  coarse_size=24, max_levels=3)          # multilevel
        mis2(g, engine="distributed")                    # collective bytes
        srv = Server(ServerConfig(max_batch=2, max_delay_s=0.0))
        f1 = srv.submit("mis2", g)
        f2 = srv.submit("mis2", g)                       # same digest
        srv.flush()
        f1.result(timeout=60)
        f2.result(timeout=60)
    d = cap.delta()
    assert d.value("mis2.resident_dispatches") >= 1
    assert d.value("mis2.host_syncs") >= 1
    assert d.value("multilevel.resident_dispatches") >= 1
    assert d.total("dist.collective_bytes") > 0
    assert d.value("serve.requests") == 2
    assert d.value("serve.cache.misses") + d.value("serve.cache.hits") == 2
    assert d.total("graph.conversions") >= 1
    assert d.total("span.seconds") > 0
    # and the whole thing exports cleanly
    text = obs.to_prometheus(d)
    assert "repro_serve_requests 2" in text.split("\n")


def test_graph_conversion_timings_via_snapshot():
    with obs.capture() as cap:
        g = Graph(laplace3d(5).graph)
        _ = g.ell
        _ = g.ell                                        # cache hit
        _ = g.digest
    assert cap.value("graph.conversions", {"kind": "csr_to_ell"}) == 1
    assert cap.count("graph.conversion_seconds", {"kind": "csr_to_ell"}) == 1
    assert cap.value("graph.conversions", {"kind": "digest"}) == 1
    assert g.conversion_timings["csr_to_ell"] >= 0.0


# ---------------------------------------------------------------------------
# spans where the work happens: layout, fixed-point launch / wait / pull,
# the Alg. 3 joins; the rounds and compile counters
# ---------------------------------------------------------------------------

def _children(span: dict) -> list:
    return [c["name"] for c in span["children"]]


def _find(span: dict, name: str) -> list:
    found = [span] if span["name"] == name else []
    for c in span["children"]:
        found += _find(c, name)
    return found


@pytest.mark.parametrize("engine,layout,fixed_point", [
    ("compacted_resident", "ell", "mis2.resident_fixed_point"),
    ("pallas_hybrid", "hybrid", "mis2.hybrid_fixed_point"),
])
def test_coarsen_provenance_span_tree(engine, layout, fixed_point):
    from repro.graphs.generators import powerlaw_graph

    g = powerlaw_graph(700, 8.0, seed=5)
    coarsen(Graph(g), mis2_engine=engine)            # warm
    r = coarsen(Graph(g), mis2_engine=engine)
    root = r.provenance.span
    conversion = "graph.csr_to_ell" if layout == "ell" \
        else "graph.csr_to_hybrid"
    names = _children(root)
    assert names[names.index(conversion):] == [
        conversion,
        fixed_point, "mis2.pull", "coarsen.root_join",
        fixed_point, "mis2.pull", "coarsen.phase2_join",
        "coarsen.phase3_join", "coarsen.finalize"]
    (conv,) = _find(root, conversion)
    assert _children(conv) == ["graph.to_device"]
    phases = _find(root, fixed_point)
    for fp in phases:
        assert _children(fp) == ["mis2.launch", "mis2.wait"]
    rounds = [fp["metrics"][f"mis2.rounds{{layout={layout}}}"]
              for fp in phases]
    assert rounds == [fp["attrs"]["iterations"] for fp in phases]
    assert sum(rounds) == r.iterations
    assert root["metrics"][f"mis2.rounds{{layout={layout}}}"] == r.iterations


def test_jit_compiles_counted_in_the_launch_span():
    # a vertex count no other test uses: its fixed point compiles here
    g = random_uniform_graph(613, 5.0, seed=13)
    first = mis2(Graph(g), engine="compacted_resident")
    (launch,) = _find(first.provenance.span, "mis2.launch")
    assert launch["metrics"].get("jit.compiles", 0) >= 1
    again = mis2(Graph(g), engine="compacted_resident")
    (launch,) = _find(again.provenance.span, "mis2.launch")
    assert launch["metrics"].get("jit.compiles", 0) == 0
    assert "jit.compiles" not in again.provenance.metrics


def test_every_facade_entry_stays_under_the_span_series_cap():
    from repro.api import (
        amg,
        amg_setup_batch,
        cluster_gs_setup,
        coarsen_batch,
        color_batch,
        mis2_batch,
        misk,
        partition,
    )
    from repro.obs.registry import MAX_SERIES_PER_METRIC

    g = Graph(laplace3d(6).graph)
    m = Graph(laplace3d(6))
    small = dict(coarse_size=24, max_levels=3)
    mis2(g)
    mis2(g, engine="compacted_resident")
    mis2(g, engine="pallas_hybrid")
    misk(g, k=3)
    color(g)
    coarsen(g)
    partition(g, 4)
    amg_setup(m, **small)
    amg(m, **small)
    cluster_gs_setup(m)
    mis2_batch([g, g])
    color_batch([g, g])
    coarsen_batch([g, g])
    amg_setup_batch([m, m], **small)
    names = {dict(s.labels)["span"] for s in obs.snapshot()
             if s.name == "span.seconds"}
    assert len(names) < MAX_SERIES_PER_METRIC
    # the program's own spans (this file's tests open a few more) leave
    # half the cap free
    program = {n for n in names if n.startswith(
        ("api.", "graph.", "mis2.", "coarsen.", "multilevel.", "serve."))}
    assert {"api.mis2", "mis2.launch", "graph.to_device"} <= program
    assert len(program) <= MAX_SERIES_PER_METRIC // 2, sorted(program)


def record_cpu_trace(logdir, body) -> dict:
    """``body()`` inside a ``bench.window`` annotation, profiled as the
    harness profiles a window (no Python tracer), as in
    ``bench/tests/test_bench_trace.py``; the loaded planes."""
    import jax

    from bench import trace as tr

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            body()
    finally:
        jax.profiler.stop_trace()
    return tr.load(logdir)


def test_spans_are_profiler_annotations_on_the_harness_clock(tmp_path):
    from bench import harness
    from bench import trace as tr

    g = random_uniform_graph(400, 5.0, seed=14)
    mis2(Graph(g), engine="compacted_resident")      # warm
    t0 = []

    def call():
        t0.append(time.perf_counter())
        mis2(Graph(g), engine="compacted_resident")

    planes = record_cpu_trace(tmp_path, call)
    (root,) = [s for s in obs.recent_spans(8) if s.start_s >= t0[0]]
    mapped = harness.span_intervals([root], t0[0], tr.window_of(planes)[0])
    native = {}
    for lines in planes.values():
        for evs in lines.values():
            for name, start, _ in evs:
                native.setdefault(name, []).append(start)
    assert {"api.mis2", "graph.to_device", "mis2.launch", "mis2.wait",
            "mis2.pull"} <= {name for name, _, _ in mapped}
    for name, start, _ in mapped:
        assert name in native, name
        off = min(abs(s - start) for s in native[name])
        assert off < 1_000_000, f"{name}: {off} ns off the trace clock"


# ---------------------------------------------------------------------------
# benchmark trajectory contract (satellite: records embed the snapshot)
# ---------------------------------------------------------------------------

def test_emit_trajectory_embeds_metrics_snapshot(tmp_path, monkeypatch):
    from benchmarks import common

    monkeypatch.setattr(common, "REPO_ROOT", tmp_path)
    monkeypatch.setattr(common, "ARTIFACTS", tmp_path / "bench")
    obs.metrics.counter("benchtest.calls").inc()
    common.emit_trajectory("obs_unit", {"graphs_per_s": 12.5})
    history = json.loads((tmp_path / "BENCH_obs_unit.json").read_text())
    rec = history[-1]
    assert rec["graphs_per_s"] == 12.5
    assert "metrics" in rec
    assert rec["metrics"]["benchtest.calls"] >= 1
    # caller-supplied snapshots are respected, not overwritten
    common.emit_trajectory("obs_unit", {"graphs_per_s": 1.0,
                                        "metrics": {"mine": 1}})
    history = json.loads((tmp_path / "BENCH_obs_unit.json").read_text())
    assert history[-1]["metrics"] == {"mine": 1}
