#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` at the checkout's root (see ``bench/harness/spec.py``).
Phase lines go to standard error, ending with each number that decides
``correct`` beside its limit.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``.

The run needs a TPU with enough chips for the cell and the system's
compiled Pallas path; without them it exits non-zero and prints no result.
The JAX compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, otherwise
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import jax  # noqa: F401

        import repro  # noqa: F401
        from harness import cell, spec
    except ImportError as e:
        print(f"bench: cannot import the system ({e}); run from a checkout",
              file=sys.stderr)
        return 2
    try:
        out = cell.run(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    except (cell.BenchFailure, spec.SpecError) as e:
        print(f"bench: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
