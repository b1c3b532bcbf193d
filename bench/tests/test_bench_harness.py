"""``BENCHMARK.json`` against the benchmark's contract, and the harness's
refusals: no accelerator, no program beside it."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from . import _paths  # noqa: F401
from .. import harness

SPEC = harness.benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
WIDTHS = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                    r"projection|head|expansion|per_token|edge_factor")
CELLS = [c["name"] for c in SPEC["workloads"]]


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    groups = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
        + SPEC["per_layer"]
    for entry in groups:
        assert NAME.fullmatch(entry["name"]), entry["name"]
    for cell in SPEC["workloads"]:
        assert NAME.fullmatch(cell["config"]) and NAME.fullmatch(cell["traffic"])
        assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve_by_name(cell):
    entry = harness.find_cell(SPEC, cell)
    cfg = harness.config(entry["config"])
    mix = harness.traffic(entry["traffic"])
    call = harness.module("calls", mix["call"])
    assert set(call.LIMITS) and all(v == 0 for v in call.LIMITS.values())
    harness.module("data", cfg["generator"])
    e2e = harness.cell_metrics(SPEC, cell, "end_to_end")
    layer = harness.cell_metrics(SPEC, cell, "per_layer")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2 and layer
    for m in e2e + layer:
        assert callable(harness.module("metrics", m["name"]).read)


def test_configs():
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and not WIDTHS.search(key), key
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])


def test_per_layer_metrics():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def run_bench(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_accelerator():
    p = run_bench(harness.ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no accelerator" in p.stderr


def test_run_refuses_without_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
