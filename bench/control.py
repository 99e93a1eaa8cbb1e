#!/usr/bin/env python3
"""Readings of the control, which ``correct`` has to refuse.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 11 12 13 [--qps Q]

For each seed, the numbers a run of the cell would compare if the control
(``bench/harness/control.py``: the plain reference one precision step
below the configuration's, or breaking one stated guarantee) had answered
the window's requests.  ``--qps`` sizes a closed-loop window: the queries a
run replays per second.  Prints one JSON line per seed.  Uses no device.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import control, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--qps", type=float, default=0.0)
    args = ap.parse_args(argv)
    cell = spec.resolve(BENCH.parent, args.workload)
    for seed in args.seeds:
        r = control.readings(cell, seed, args.seconds, args.qps)
        print(json.dumps({"workload": args.workload, "seed": seed, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
