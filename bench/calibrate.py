#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] > readings.jsonl

For each seed: the program's first ``check_steps`` rounds against the plain
float32 reference (the lower readings: sound runs).  For each control seed
also the control (the reference in the configuration's lower precision, in
the program's place) and the faults the cell can have, each planted in the
reference put in the program's place: ``half_batch``, and ``no_exchange``
on more than one worker (a state left unchanged reads 1 by construction).
One JSON line per reading.  Not part of a benchmark run; run on the chips
the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    from bench.lib import cells, check, device, harness
    cell = cells.load_cell(args.workload)
    devices = device.chips(cell["chips"])
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    variants = ["control", "half_batch"]
    if cell["traffic"]["path"] == "mesh" and cell["traffic"]["mesh"][0] > 1:
        variants.append("no_exchange")
    for seed in seeds:
        t0 = time.perf_counter()
        runner = harness.make_runner(cell, seed, devices, "program")
        runner.setup()
        prog = runner.first_steps()
        runner.release()
        ref = runner.reference("f32")
        out = {"workload": cell["name"], "seed": seed, "variant": "program",
               **check.gaps(prog, ref), "loss": prog["loss"],
               "ref_loss": ref["loss"], "seconds": time.perf_counter() - t0}
        print(json.dumps(out), flush=True)
        if seed not in controls:
            continue
        for v in variants:
            t0 = time.perf_counter()
            other = runner.reference(v)
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "variant": v, **check.gaps(other, ref),
                              "loss": other["loss"],
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
