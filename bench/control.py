#!/usr/bin/env python3
"""Readings that the limits of ``bench/cells/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--preset e5m3_fixed] [--dtype bfloat16]

Runs the cell like ``bench/run.py`` once per seed, in one process (the
programs compile once), and prints one JSON line per seed with each number
compared.  With the configuration as it stands the readings are the sound
runs'; the options switch on one of the program's own lower-precision
paths in its place, the controls:

* ``--preset e5m3_fixed``: fixed 3-bit aligned mantissas (4-bit integers
  with sign) where the configuration states DSBP's predicted widths;
* ``--dtype bfloat16``: bfloat16 activations where it states float32.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):  # the benchmark, the program
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--preset", default=None,
                    help="DSBP preset in place of the configuration's")
    ap.add_argument("--dtype", default=None,
                    help="activation dtype in place of the configuration's")
    args = ap.parse_args(argv)
    bench = harness.Bench(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(bench, args.workload, seed, args.seconds, False,
                           t_start=time.perf_counter(), preset=args.preset,
                           dtype=args.dtype)
        print(json.dumps({"seed": seed, "preset": args.preset,
                          "dtype": args.dtype,
                          "correct": res["correct"],
                          "failed": res["failed"],
                          "checked": res["checked"],
                          "numbers": {k: v["value"]
                                      for k, v in res["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
