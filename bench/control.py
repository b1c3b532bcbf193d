#!/usr/bin/env python3
"""The control of a cell's check, at the cell's own size: the plain
reference put in the program's place with one stated guarantee broken
(``control`` of ``bench/calls/<call>.py``), judged as a run judges the
program's answers, on every graph of the cell's pool (or on the data
seeds given).  Every limit is 0, so the control has to read above 0 on
at least one number of every graph, and the reference itself on none.

    python3 bench/control.py --workload mis2.laplace3d_100 [--seeds 4 5]

Prints one JSON line per graph; exits 1 if the check let a control pass.
"""
import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+",
                    help="data seeds (default: the traffic's pool)")
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.benchmark(), args.workload)
    cfg = harness.config(cell["config"])
    mix = harness.traffic(cell["traffic"])
    call = harness.module("calls", mix["call"])
    kwargs = mix.get("kwargs", {})
    seeds = args.seeds or [i + 1 for i in range(mix.get("graphs", 1))]
    passed = 0
    for seed in seeds:
        t0 = time.perf_counter()
        csr = harness.generate(cfg, seed)
        ref = call.reference(*csr, kwargs)
        _, ctrl = harness.judge(call, csr, ref, [call.control(*csr, kwargs)])
        _, own = harness.judge(call, csr, ref, [ref])
        caught = any(ctrl[k] > call.LIMITS[k] for k in ctrl)
        passed += not caught or any(own.values())
        print(json.dumps({"workload": args.workload, "data_seed": seed,
                          "control_caught": caught, "control": ctrl,
                          "reference": own,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
