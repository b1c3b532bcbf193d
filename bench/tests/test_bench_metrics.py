"""The readers of the program's spans and counters on a made-up window,
and the names the device-trace readers find the program's modules by."""
from types import SimpleNamespace

import pytest

from . import _paths  # noqa: F401
from .. import harness


def window(spans=None, rounds=None, calls=4):
    """A ``ctx`` whose window saw ``spans`` ({name: [seconds, ...]}) and
    ``rounds`` ({layout: n}) in ``calls`` calls."""
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    before = reg.snapshot()
    for name, seconds in (spans or {}).items():
        for s in seconds:
            reg.histogram("span.seconds", labels={"span": name}).observe(s)
    for layout, n in (rounds or {}).items():
        reg.counter("mis2.rounds", labels={"layout": layout}).inc(n)
    return SimpleNamespace(calls=calls, obs=reg.snapshot().delta(before))


def read(metric, ctx):
    return harness.module("metrics", metric).read(ctx)


@pytest.mark.parametrize("metric,span", [
    ("copy_ms", "graph.to_device"), ("launch_ms", "mis2.launch")])
def test_span_per_call(metric, span):
    ctx = window({span: [0.010, 0.030], "api.mis2": [9.0]}, calls=4)
    assert read(metric, ctx) == pytest.approx(10.0)


def test_round_ms_is_wait_over_rounds_of_every_layout():
    ctx = window({"mis2.wait": [3.0, 2.5]}, {"ell": 10, "hybrid": 1})
    assert read("round_ms", ctx) == pytest.approx(500.0)


def test_round_ms_needs_rounds():
    assert read("round_ms", window({"mis2.wait": [1.0]})) is None


def test_join_ms_sums_the_three_joins():
    ctx = window({"coarsen.root_join": [0.05, 0.05],
                  "coarsen.phase2_join": [0.1, 0.1],
                  "coarsen.phase3_join": [0.2],
                  "coarsen.finalize": [1.0]}, calls=2)
    assert read("join_ms", ctx) == pytest.approx(250.0)


@pytest.mark.parametrize("metric",
                         ["copy_ms", "launch_ms", "round_ms", "join_ms"])
def test_none_where_the_spans_never_opened(metric):
    ctx = window({"api.mis2": [3.0], "mis2.resident_fixed_point": [2.9]},
                 {"ell": 10})
    assert read(metric, ctx) is None


PROGRAM_METRICS = ("copy_ms", "launch_ms", "round_ms", "join_ms")


def check_traced_run(monkeypatch, cell, spec):
    """A whole traced run on the CPU at the cell's tiny size, on the
    engine the chip runs: every metric of the program's spans that the
    cell lists is read (the device-trace metrics find no chip here)."""
    from .test_bench_faults import on_chip_path, run, tiny

    entry, cfg = tiny(cell, spec)
    on_chip_path(monkeypatch, cfg)
    out = run(monkeypatch, cell, cfg, entry, trace=True, spec=spec)
    assert out["correct"]
    want = {m["name"] for m in harness.cell_metrics(spec, cell, "per_layer")
            if m["name"] in PROGRAM_METRICS}
    assert want >= {"copy_ms", "launch_ms", "round_ms"}
    assert want <= set(out["metrics"])
    assert all(out["metrics"][m]["value"] > 0 for m in want)


@pytest.mark.parametrize("cell", [c["name"] for c in harness.benchmark()[
    "workloads"]])
def test_traced_run_reports_the_program_metrics(monkeypatch, cell):
    check_traced_run(monkeypatch, cell, harness.benchmark())


@pytest.mark.parametrize("name", ["_resident_ell_fixed_point",
                                  "_resident_csr_fixed_point",
                                  "_hybrid_fixed_point"])
def test_fixed_point_modules_keep_their_name(name):
    """``fixpoint_roofline`` finds the fixed points' XLA modules by
    ``fixed_point`` in their name; a rename would leave it empty."""
    import importlib

    import jax.numpy as jnp
    import numpy as np

    from repro.graphs import laplace3d
    from repro.graphs.hybrid import csr_to_hybrid_ell

    mis2 = importlib.import_module("repro.core.mis2")
    hybrid = importlib.import_module("repro.core.mis2_hybrid")
    g = laplace3d(4).graph
    v = g.num_vertices
    active = jnp.ones(v, dtype=bool)
    opts = dict(priority="xorshift_star", max_iters=8, b=12)
    if name == "_hybrid_fixed_point":
        h = csr_to_hybrid_ell(g)
        lowered = hybrid._hybrid_fixed_point.lower(
            h.slices, h.spill_rows, h.spill_seg, h.spill_cols, active,
            interpret=True, **opts)
    elif name == "_resident_csr_fixed_point":
        indptr = np.asarray(g.indptr)
        rows = np.repeat(np.arange(v, dtype=np.int32), np.diff(indptr))
        lowered = mis2._resident_csr_fixed_point.lower(
            jnp.asarray(rows), jnp.asarray(g.indices), active, packed=True,
            v=v, **opts)
    else:
        from repro.graphs.csr import csr_to_ell_graph

        lowered = mis2._resident_ell_fixed_point.lower(
            csr_to_ell_graph(g).neighbors, active, packed=True, **opts)
    module = lowered.compiler_ir("stablehlo").operation.attributes[
        "sym_name"].value
    assert "fixed_point" in module, module
