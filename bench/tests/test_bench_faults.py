"""A run's ``correct`` against a broken program and against the control.

Each test drives ``harness.run_cell`` on the CPU at the tiny size that a
cell's generator states (its ``TINY``; the look for a chip stepped over
by monkeypatch), on the engine that ``engine=None`` picks on the chip
for the configuration's full-size graph, with the fixed point broken
underneath: the answer altered where it is produced, the state returned
unchanged, or half of the vertices' answers left out.  ``correct`` must
come out false for each, and true for the program as it is.  The
control (the reference with one guarantee broken) must fail the check.
The bodies are helpers taking the spec, so that a cell added to a copy
of it (``test_bench_extend.py``) runs the same checks.
"""
import functools
import importlib
import time

import numpy as np
import pytest

from . import _paths  # noqa: F401
from .. import harness

SPEC = harness.benchmark()
IN, OUT = np.uint32(0), np.uint32(0xFFFFFFFF)


def altered(t):
    """One vertex's answer flipped."""
    return t.at[0].set(np.where(t[0] == IN, OUT, IN))


def unchanged(t):
    """The fixed point's starting state: nothing decided."""
    return np.where(t == OUT, OUT, np.uint32(1))


def half_left_out(t):
    """The answers of the second half of the vertices dropped."""
    return t.at[t.shape[0] // 2:].set(OUT)


FAULTS = {"altered": altered, "unchanged": unchanged,
          "half_left_out": half_left_out}


def tiny(cell, spec=SPEC):
    """The cell's entry and its configuration at its generator's
    ``TINY`` size."""
    entry = harness.find_cell(spec, cell)
    cfg = harness.config(entry["config"])
    return entry, dict(cfg, **harness.module("data", cfg["generator"]).TINY)


@functools.cache
def chip_layout(config):
    """``"hybrid"`` where ``engine=None`` picks ``pallas_hybrid`` for the
    full-size graph of ``config`` (data seed 1), else ``"ell"``: the
    program's own rule, which weighs the padded ELL's bytes before the
    platform."""
    import repro
    import repro.api.backend as backend
    from repro.graphs import CSRGraph

    csr = harness.generate(harness.config(config), 1)
    engine = backend.default_mis2_engine(graph=repro.Graph(CSRGraph(*csr)))
    return "hybrid" if engine == "pallas_hybrid" else "ell"


def on_chip_path(monkeypatch, cfg):
    """Steer ``engine=None`` on the CPU, at ``cfg``'s tiny size, to the
    layout it picks on the chip for the configuration's full size."""
    import repro.api.backend as backend
    import repro.graphs.hybrid as hybrid

    steer = {"hybrid": (hybrid, "HYBRID_AUTO_BYTES", 0),
             "ell": (backend, "accelerator_present", lambda: True)}
    monkeypatch.setattr(*steer[chip_layout(cfg["name"])])


def break_fixed_point(monkeypatch, fault):
    # by import_module: ``repro.core.mis2`` is also a function's name
    mis2 = importlib.import_module("repro.core.mis2")
    mis2_hybrid = importlib.import_module("repro.core.mis2_hybrid")

    resident = mis2._resident_ell_fixed_point
    hybrid = mis2_hybrid._hybrid_fixed_point

    def broken_resident(*a, **k):
        t, it, n1 = resident(*a, **k)
        return fault(t), it, n1

    def broken_hybrid(*a, **k):
        t, it, n1, acc = hybrid(*a, **k)
        return fault(t), it, n1, acc

    monkeypatch.setattr(mis2, "_resident_ell_fixed_point", broken_resident)
    monkeypatch.setattr(mis2_hybrid, "_hybrid_fixed_point", broken_hybrid)


def run(monkeypatch, cell, cfg, entry, seed=2**31 + 11, trace=False,
        spec=SPEC):
    """A whole run on the CPU: the look for a chip, its peaks and the
    compile cache are stepped over, everything else runs as on the chip."""
    import jax

    monkeypatch.setattr(harness, "find_devices", lambda chips: jax.devices())
    monkeypatch.setattr(harness, "peaks", lambda kind: None)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    return harness.run_cell(cell, cfg, harness.traffic(entry["traffic"]),
                            seed, 0.2, trace, time.perf_counter(), spec=spec)


def check_control(cell, spec=SPEC):
    """The control fails the check on each graph of the pool; the
    reference itself passes it."""
    entry, cfg = tiny(cell, spec)
    mix = harness.traffic(entry["traffic"])
    call = harness.module("calls", mix["call"])
    kwargs = mix.get("kwargs", {})
    for seed in (1, 2, 3):
        csr = harness.generate(cfg, seed)
        ref = call.reference(*csr, kwargs)
        failed, worst = harness.judge(call, csr, ref,
                                      [call.control(*csr, kwargs)])
        assert failed == 1 and worst["rounds_diff"] >= 1
        assert harness.judge(call, csr, ref, [ref]) == (
            0, {k: 0 for k in call.LIMITS})


def check_as_it_is(monkeypatch, cell, spec=SPEC):
    import repro.obs

    entry, cfg = tiny(cell, spec)
    on_chip_path(monkeypatch, cfg)
    with repro.obs.capture() as seen:
        out = run(monkeypatch, cell, cfg, entry, spec=spec)
    # the rounds ran in the layout the chip runs, and in no other
    assert [k for k in ("ell", "hybrid") if seen.value(
        "mis2.rounds", {"layout": k})] == [chip_layout(cfg["name"])]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {m["name"] for m in harness.cell_metrics(
        spec, cell, "end_to_end")}


def check_fault(monkeypatch, cell, fault, spec=SPEC):
    entry, cfg = tiny(cell, spec)
    on_chip_path(monkeypatch, cfg)
    break_fixed_point(monkeypatch, FAULTS[fault])
    out = run(monkeypatch, cell, cfg, entry, spec=spec)
    assert not out["correct"]
    assert out["failed"] == out["attempted"]
    assert any(c["value"] > c["limit"] for c in out["compared"].values())


def check_drawn_graph_fault(monkeypatch, cell, spec=SPEC):
    """A fault that shows on one labelling only, the graph drawn from the
    run's seed, and on none of the timed pool's graphs."""
    entry, cfg = tiny(cell, spec)
    on_chip_path(monkeypatch, cfg)
    pool = {harness.generate(cfg, i + 1)[1].tobytes() for i in range(3)}
    generate, drawn = harness.generate, []

    def marking(cfg_, seed):
        csr = generate(cfg_, seed)
        drawn.append(csr[1].tobytes() not in pool)
        return csr

    monkeypatch.setattr(harness, "generate", marking)
    fault = FAULTS["altered"]
    break_fixed_point(monkeypatch, lambda t: fault(t) if drawn[-1] else t)
    out = run(monkeypatch, cell, cfg, entry, spec=spec)
    assert drawn[-1] and not out["correct"]
    assert out["failed"] == 1 and out["attempted"] >= 2


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_program_as_it_is_is_correct(monkeypatch, cell):
    check_as_it_is(monkeypatch, cell)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_broken_fixed_point_is_not_correct(monkeypatch, cell, fault):
    check_fault(monkeypatch, cell, fault)


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_fault_on_the_drawn_graph_alone_is_not_correct(monkeypatch, cell):
    check_drawn_graph_fault(monkeypatch, cell)


def test_altered_join_is_not_correct(monkeypatch):
    """Coarsening: one label altered where the phase-3 join makes it."""
    import repro.core.aggregation as aggregation

    cell = "coarsen.laplace3d_100"
    entry, cfg = tiny(cell)
    on_chip_path(monkeypatch, cfg)
    phase3 = aggregation._phase3_resident

    def broken(*a):
        labels, phase = phase3(*a)
        return labels.at[0].set(labels[0] + 1), phase

    monkeypatch.setattr(aggregation, "_phase3_resident", broken)
    out = run(monkeypatch, cell, cfg, entry)
    assert not out["correct"] and out["compared"]["label_mismatch"]["value"]


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_control_fails_the_check(cell):
    check_control(cell)
