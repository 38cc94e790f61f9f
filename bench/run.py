#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with as many TPU chips as the
cell asks for.  Set-up (data, weights, the compile, the first rounds that
the check reads) is ``setup_s``; then rounds run back to back for
``--seconds`` (``--trace 0``: the end-to-end metrics) or a short traced
window (``--trace 1``: the per-layer metrics).  Last, the program's first
rounds are compared with a plain float32 reference, each number against
its limit, on standard error and under ``checks`` in the result.  The last
line of standard output is the result object.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for path in (REPO, os.path.join(REPO, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print("bench/run.py: no program under src/repro", file=sys.stderr)
        return 2
    from bench.lib import cells, harness
    from bench.lib.device import NoChip
    cell = cells.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START)
    except NoChip as e:
        print(f"bench/run.py: {e}; no result", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
