#!/usr/bin/env python3
"""Read a cell's compared numbers for the program and for the control.

    python bench/control.py --workload iris-k4096-catchup --seconds 5 \
        --seeds 101,102,103

Per seed, one run of the cell at its own size with a short window; after
the program's check, the reference computed in bfloat16 (the precision
below the float32 the configuration states) is put in the program's place
and compared with the float32 reference in the same way. Prints one JSON
line per seed: the program's numbers (the lower readings) and the
control's (the upper readings). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           control=True)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program": {k: c["value"] for k, c in res["checks"].items()},
            "control": res["control"], "metrics": res["metrics"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
