"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only tableX,...]

Prints ``name,us_per_call,derived`` CSV lines and writes
artifacts/bench/<name>.csv per table.

Under ``--quick`` (the CI lane) the driver additionally gates on the
observability contract: every root ``BENCH_*.json`` trajectory touched by
the run must carry a ``"metrics"`` registry snapshot in its newest record
(``common.emit_trajectory`` stamps it; a benchmark bypassing that helper
fails the run).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]

# src path bootstrap lives in benchmarks/__init__.py (runs on package import)
from benchmarks import (  # noqa: E402
    batch_throughput,
    dist_scaling,
    fig2_optimizations,
    figs4_5_scaling,
    hotloop_overhead,
    hybrid_layout,
    roofline,
    serve_resilience,
    serve_throughput,
    setup_overhead,
    table1_priorities,
    table3_scaling,
    table4_quality,
    table5_amg,
    table6_cluster_gs,
)

ALL = {
    "table1": table1_priorities.run,
    "fig2": fig2_optimizations.run,
    "table3": table3_scaling.run,
    "table4": table4_quality.run,
    "table5": table5_amg.run,
    "table6": table6_cluster_gs.run,
    # dist before figs4_5: it generates the dry-run records axis B reads
    "dist": dist_scaling.run,
    "figs4_5": figs4_5_scaling.run,
    "roofline": roofline.run,
    "batch": batch_throughput.run,
    "hotloop": hotloop_overhead.run,
    "hybrid": hybrid_layout.run,
    "setup": setup_overhead.run,
    "serve": serve_throughput.run,
    "serve_resilience": serve_resilience.run,
}


def _check_trajectory_metrics(started_at: float) -> list[str]:
    """Root ``BENCH_*.json`` files modified during this run whose newest
    record is missing the ``"metrics"`` snapshot (the obs contract)."""
    bad = []
    for path in sorted(REPO_ROOT.glob("BENCH_*.json")):
        if path.stat().st_mtime < started_at:
            continue        # not touched by this run
        try:
            history = json.loads(path.read_text())
        except (json.JSONDecodeError, OSError):
            bad.append(f"{path.name}: unreadable")
            continue
        if not history or "metrics" not in history[-1]:
            bad.append(f"{path.name}: newest record lacks 'metrics'")
    return bad


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced problem sizes (CI)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(ALL))
    args = ap.parse_args()
    from repro.compile_cache import setup_compile_cache

    setup_compile_cache()
    names = list(ALL) if not args.only else args.only.split(",")
    started_at = time.time()
    for name in names:
        t0 = time.perf_counter()
        print(f"# --- {name} ---", flush=True)
        ALL[name](quick=args.quick)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)
    if args.quick:
        bad = _check_trajectory_metrics(started_at)
        if bad:
            for line in bad:
                print(f"# OBS GATE FAIL: {line}", flush=True)
            sys.exit(1)
        print("# obs gate: every touched BENCH_*.json carries a metrics "
              "snapshot", flush=True)


if __name__ == "__main__":
    main()
