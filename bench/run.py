#!/usr/bin/env python3
"""Run one benchmark cell: see ``bench/harness.py``.

    python3 bench/run.py --workload mis2.laplace3d_100 --seed 7 --seconds 40 --trace 0
"""
import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the checkout's root, not bench/, heads the path: bench/trace.py must
# not shadow the standard library's ``trace``
sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
