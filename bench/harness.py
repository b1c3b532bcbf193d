"""The benchmark's one driver: a closed loop of facade calls on one
deployment, timed on the host clock, with the answers of every timed
call compared to a plain reference once the window has closed.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run finds its cell in ``BENCHMARK.json``, its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the call that mix makes in
``bench/calls/<call>.py`` and each metric's reader in
``bench/metrics/<metric>.py``.  It refuses to run without an
accelerator, with fewer chips than the cell asks for, or outside a
checkout of the repository.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` a ``breakdown``, and last ``compared``,
every number compared beside its limit.
"""
from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import tempfile
import time
import traceback
from contextlib import nullcontext
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


class Refused(RuntimeError):
    """The run cannot be made here; nothing is printed on stdout."""


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, group: str) -> list:
    """The ``group`` metrics (``end_to_end`` / ``per_layer``) this cell
    reports: those without a ``workloads`` list, and those naming it."""
    return [m for m in spec[group] if cell in m.get("workloads", [cell])]


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def module(kind: str, name: str):
    return importlib.import_module(f"bench.{kind}.{name}")


def generate(cfg: dict, seed):
    """Host CSR ``(indptr, indices)`` of the configuration's graph."""
    return module("data", cfg["generator"]).generate(cfg, seed)


def import_program():
    """``repro`` from this checkout's ``src`` and nowhere else."""
    if not (SRC / "repro" / "__init__.py").is_file():
        raise Refused(f"no repro package under {SRC}")
    sys.path.insert(0, str(SRC))
    import repro

    if Path(repro.__file__).resolve().parent != SRC / "repro":
        raise Refused(f"repro imported from {repro.__file__}, not {SRC}")
    return repro


def find_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise Refused("JAX finds no accelerator; the benchmark never runs "
                      "on the CPU")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds "
                      f"{len(devices)}")
    return devices


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def enable_compile_cache() -> str:
    """The program's persistent cache (``JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``), holding every program however fast it
    compiled, so only a checkout's first run compiles."""
    import jax

    from repro.compile_cache import setup_compile_cache

    path = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def pool(cfg: dict, mix: dict) -> list:
    """The traffic's graphs: the configuration's generator at data seeds
    1..``graphs``, the same for every run, so every seed times the same
    work (the run's seed only orders it; :func:`run_cell` checks one more
    graph drawn from it)."""
    return [generate(cfg, i + 1) for i in range(mix.get("graphs", 1))]


class Window:
    """A closed loop of one caller over a pool of graphs: each call
    builds a fresh ``repro.Graph`` from one graph's host CSR and makes
    the traffic's facade call.  Calls go in cycles, each cycle every
    graph once in an order drawn from the run's seed, so every window
    times whole cycles: the same work whatever the seed.  Another cycle
    starts while one more of the last cycle's length still ends within
    ``seconds``; the first always runs."""

    def __init__(self, repro, graphs, call, kwargs, annotate):
        self.repro, self.graphs, self.call = repro, graphs, call
        self.kwargs, self.annotate = kwargs, annotate

    def one(self, csr):
        """The answer of one call on host CSR ``csr``, or None and the
        error where it raised: a failed call counts, it does not end the
        run."""
        try:
            with self.annotate("bench.build"):
                graph = self.repro.Graph(csr)
            with self.annotate("bench.call"):
                return self.call.invoke(self.repro, graph, self.kwargs), None
        except Exception as err:  # noqa: BLE001 - judged as failed
            return None, f"{type(err).__name__}: {err}"

    def run(self, seconds: float, rng):
        """``(t0, window seconds, [(graph, answer or None)], errors,
        seconds of each call)``."""
        calls, errors, each = [], [], []
        t0 = time.perf_counter()
        while True:
            start = time.perf_counter()
            for i in rng.permutation(len(self.graphs)).tolist():
                t = time.perf_counter()
                answer, err = self.one(self.graphs[i])
                calls.append((i, answer))
                if err:
                    errors.append(err)
                each.append(time.perf_counter() - t)
            now = time.perf_counter()
            if 2 * now - start - t0 > seconds:
                return t0, now - t0, calls, errors, each


def judge(call, csr, ref, answers) -> tuple:
    """``(failed, worst)``: the answers (None for a call that raised)
    that break a limit, and each number's largest reading."""
    import numpy as np

    from .data.ref_mis2 import closed_adjacency

    adj = []

    def adjacency():
        if not adj:
            adj.append(closed_adjacency(*csr))
        return adj[0]

    ctx = SimpleNamespace(adjacency=adjacency)
    worst = {k: 0 for k in call.LIMITS}
    failed, seen = 0, {}
    for ans in answers:
        if ans is None:
            failed += 1
            continue
        key = tuple((k, np.asarray(v).tobytes()) for k, v in sorted(ans.items()))
        if key not in seen:
            seen[key] = call.compare(ans, ref, ctx)
        nums = seen[key]
        failed += any(nums[k] > call.LIMITS[k] for k in call.LIMITS)
        for k in worst:
            worst[k] = max(worst[k], nums[k])
    return failed, worst


def check(call, csrs, kwargs, calls) -> tuple:
    """Compare every timed answer with the reference of its graph:
    ``(failed, compared, refs, seconds)``; ``compared`` holds each
    number's largest reading beside its limit."""
    t0 = time.perf_counter()
    refs, failed = {}, 0
    worst = {k: 0 for k in call.LIMITS}
    for i in sorted({i for i, _ in calls}):
        refs[i] = call.reference(*csrs[i], kwargs)
        f, w = judge(call, csrs[i], refs[i], [a for j, a in calls if j == i])
        failed += f
        worst = {k: max(worst[k], w[k]) for k in worst}
    compared = {k: {"value": worst[k], "limit": call.LIMITS[k]}
                for k in call.LIMITS}
    return failed, compared, refs, time.perf_counter() - t0


def memory_peak(devices):
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def run_cell(cell_name: str, cfg: dict, mix: dict, seed: int,
             seconds: float, trace: bool, t_process: float, *,
             spec: dict | None = None) -> dict:
    """One run of a cell; returns the result line as a dict.  The trace
    of a ``trace`` run goes to a temporary directory, removed once it is
    read.  After the window one more graph, drawn from ``seed``, is
    called once and judged with the window's answers, so the data
    checked changes from run to run while the timed work does not."""
    spec = benchmark() if spec is None else spec
    cell = find_cell(spec, cell_name)
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise Refused(f"traffic {cell['traffic']!r}: only a closed loop "
                      "of one client is driven")
    repro = import_program()
    import jax

    marks = {"imported": time.perf_counter() - t_process}
    devices = find_devices(cell["chips"])
    dev = devices[0]
    peak = peaks(dev.device_kind)
    marks["devices"] = time.perf_counter() - t_process
    enable_compile_cache()
    call = module("calls", mix["call"])
    kwargs = mix.get("kwargs", {})

    import numpy as np
    from repro.graphs import CSRGraph

    marks["start"] = time.perf_counter() - t_process
    csrs = pool(cfg, mix)
    marks["generated"] = time.perf_counter() - t_process
    annotate = jax.profiler.TraceAnnotation if trace \
        else (lambda name: nullcontext())
    window = Window(repro, [CSRGraph(*c) for c in csrs], call, kwargs,
                    annotate)
    _, err = window.one(window.graphs[0])   # warm-up: one shape for all
    if err:
        raise RuntimeError(f"the warm-up call failed: {err}")
    setup_s = time.perf_counter() - t_process

    compiles = count_compiles()
    before = repro.obs.snapshot()
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace.")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        win_annot = jax.profiler.TraceAnnotation("bench.window")
        win_annot.__enter__()
    t0, window_s, calls, errors, each = window.run(
        seconds, np.random.default_rng(seed))
    if trace:
        win_annot.__exit__(None, None, None)
        jax.profiler.stop_trace()
    obs = repro.obs.snapshot().delta(before)
    compiles = compiles()
    spans = [s for s in repro.obs.recent_spans(64) if s.start_s >= t0]
    mem = memory_peak(devices)

    t_drawn = time.perf_counter()
    drawn = generate(cfg, seed)
    answer, err = window.one(CSRGraph(*drawn))
    if err:
        errors.append(err)
    drawn_s = time.perf_counter() - t_drawn
    failed, compared, refs, check_s = check(
        call, csrs + [drawn], kwargs, calls + [(len(csrs), answer)])
    reduced = None
    if trace:
        from . import trace as tr

        planes = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = tr.reduce(planes, host=span_intervals(
            spans, t0, tr.window_of(planes)[0]))
    least = [refs[i].get("least_bytes") for i, _ in calls]
    ctx = SimpleNamespace(calls=len(calls), window_s=window_s,
                          setup_s=setup_s, obs=obs, spans=spans,
                          trace=reduced, peaks=peak, cell=cell, config=cfg,
                          least_bytes=None if None in least else sum(least))
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(spec, cell_name, group):
        value = module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": failed == 0 and len(calls) > 0,
           "attempted": len(calls) + 1, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {
            "device_ops": [list(kv) for kv in reduced["device_ops"]],
            "idle_gaps": [list(kv) for kv in reduced["idle_gaps"]]}
    out["diagnostics"] = {"setup_marks_s": marks, "window_s": window_s,
                          "call_s": each, "window_compiles": compiles,
                          "drawn_call_s": drawn_s, "check_s": check_s,
                          "errors": errors[:3]}
    out["compared"] = compared
    return out


def count_compiles():
    """Count the programs JAX compiles from now on; the returned function
    stops counting and gives the count."""
    import jax

    n = [0]

    def listen(event, duration, **kw):
        n[0] += event == "/jax/core/compile/backend_compile_duration"

    jax.monitoring.register_event_duration_secs_listener(listen)

    def stop():
        jax.monitoring.unregister_event_duration_listener(listen)
        return n[0]

    return stop


def span_intervals(spans, t0: float, lo: int) -> list:
    """The program's spans of the window on the trace clock: the
    ``bench.window`` annotation, which starts at ``lo`` ns, opened just
    before ``t0`` on the host clock."""
    out = []

    def walk(sp):
        s = lo + int((sp.start_s - t0) * 1e9)
        out.append((sp.name, s, s + int(sp.duration_s * 1e9)))
        for c in sp.children:
            walk(c)

    for sp in spans:
        walk(sp)
    return out


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_process: float) -> int:
    args = parse(argv)
    try:
        spec = benchmark()
        cell = find_cell(spec, args.workload)
        out = run_cell(args.workload, config(cell["config"]),
                       traffic(cell["traffic"]), abs(args.seed),
                       args.seconds, bool(args.trace), t_process, spec=spec)
    except Refused as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 - any failure: no result line
        traceback.print_exc()
        return 1
    for name, c in out["compared"].items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
