"""A deployment that joins the benchmark without an edit to a file under
``bench/``.

A stub generator (a 2-D 5-point grid) stands for a new ``data/<gen>.py``,
registered as a module under ``bench.data`` without a file; a stub
configuration stands for a new ``configs/<c>.json``; one cell on the
existing ``coarsen`` traffic is added to a copy of ``BENCHMARK.json``,
and its name to the ``workloads`` lists of the existing per-layer
metrics, as the recipe in ``bench/__init__.py`` says.  The cell then
goes through the same helpers, and so the same checks and
``harness.run_cell``, that every cell of the benchmark is tested with,
and no file under ``bench/`` is written.
"""
import copy
import sys
import types

import numpy as np
import pytest

from . import _paths  # noqa: F401
from .. import harness
from ..data.csr import csr_from_pairs
from .test_bench_faults import (
    FAULTS,
    SPEC,
    check_as_it_is,
    check_control,
    check_drawn_graph_fault,
    check_fault,
)
from .test_bench_metrics import check_traced_run

GEN = "grid2d_stub"
CONFIG = {"name": "grid2d_stub_200", "generator": GEN, "nx": 200,
          "ny": 200, "reduced": []}
TRAFFIC = "coarsen"
CELL = f"{TRAFFIC}.{CONFIG['name']}"


def grid2d(cfg, seed):
    """The 5-point stencil on an ``nx x ny`` grid with the diagonal; the
    seed permutes the vertex ids (``None`` keeps the grid order)."""
    nx, ny = cfg["nx"], cfg["ny"]
    v = nx * ny
    ids = np.arange(v, dtype=np.int64).reshape(nx, ny)
    a, b = ids[:, :-1].ravel(), ids[:, 1:].ravel()     # along y
    c, d = ids[:-1].ravel(), ids[1:].ravel()           # along x
    diag = ids.ravel()
    rows = np.concatenate([a, b, c, d, diag])
    cols = np.concatenate([b, a, d, c, diag])
    perm = np.arange(v, dtype=np.int64) if seed is None \
        else np.random.default_rng(seed).permutation(v)
    return csr_from_pairs(perm[rows], perm[cols], v)


def snapshot():
    """Every file under ``bench/`` but the bytecode caches, with its size
    and modification time."""
    return {p: (p.stat().st_size, p.stat().st_mtime_ns)
            for p in harness.BENCH.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def added(monkeypatch):
    """A copy of the spec with the stub deployment's entries; the stub
    generator and configuration found by name as the harness finds
    every other."""
    before = snapshot()
    stub = types.ModuleType(f"bench.data.{GEN}", grid2d.__doc__)
    stub.generate = grid2d
    stub.TINY = {"nx": 12, "ny": 10}
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    config = harness.config
    monkeypatch.setattr(harness, "config", lambda name: dict(CONFIG)
                        if name == CONFIG["name"] else config(name))
    spec = copy.deepcopy(SPEC)
    spec["configs"].append({
        "name": CONFIG["name"], "source": "a 2-D Poisson stencil",
        "file": f"bench/configs/{CONFIG['name']}.json", "reduced": [],
        "why": "a deployment added by new files alone"})
    spec["workloads"].append({
        "name": CELL, "config": CONFIG["name"], "traffic": TRAFFIC,
        "chips": 1, "why": "Alg. 3 on a 2-D grid"})
    # the cell opens the spans of the cell it shares its traffic with
    like = next(c["name"] for c in SPEC["workloads"]
                if c["traffic"] == TRAFFIC)
    for m in spec["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(CELL)
    yield spec
    assert not (harness.BENCH / "data" / f"{GEN}.py").exists()
    assert snapshot() == before


def test_added_cell_as_it_is_is_correct(monkeypatch, added):
    check_as_it_is(monkeypatch, CELL, added)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_added_cell_broken_fixed_point_is_not_correct(monkeypatch, added,
                                                      fault):
    check_fault(monkeypatch, CELL, fault, added)


def test_added_cell_fault_on_the_drawn_graph_alone_is_not_correct(
        monkeypatch, added):
    check_drawn_graph_fault(monkeypatch, CELL, added)


def test_added_cell_control_fails_the_check(added):
    check_control(CELL, added)


def test_added_cell_traced_run_reports_the_program_metrics(monkeypatch,
                                                           added):
    check_traced_run(monkeypatch, CELL, added)
