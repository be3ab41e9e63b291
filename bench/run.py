#!/usr/bin/env python3
"""Benchmark of filtered retrieval through ``CubeGraphService`` on a TPU.

Usage, from the root of a checkout::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

runs one cell of ``BENCHMARK.json`` in this process and prints, as the
last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; then ``checks``, each number compared with its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

Other modes, none of which prints a result line:

* ``--cpu-rehearsal``: the whole run at the configuration's rehearsal
  size on the CPU (kernels interpreted);
* ``--sweep R1,R2,...``: one set-up, then the open loop at each rate for
  ``--seconds``, reporting backlog and refusals (finds the knee);
* ``--control [NAME]``: the configuration's control (the reference one
  precision below), or the reference with the planted fault NAME, on the
  window's requests, with the numbers it gives.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated open-loop rates (queries/s)")
    ap.add_argument("--control", nargs="?", const="", default=None,
                    help="a stand-in: the control, or a fault's name")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import catalog, harness
    cell = catalog.cell(args.workload)
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        return harness.run_sweep(cell, args.seed, args.seconds, rates,
                                 args.cpu_rehearsal)
    if args.control is not None:
        return harness.run_control(cell, args.seed, args.seconds,
                                   args.control, args.cpu_rehearsal)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              rehearsal=args.cpu_rehearsal)
    if result is None:
        return 2
    if args.cpu_rehearsal:
        harness.log("cpu rehearsal reached its end (no result line off "
                    "the chip): " + json.dumps(result)[:2000])
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
